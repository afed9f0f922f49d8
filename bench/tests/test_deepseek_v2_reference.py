"""The DeepSeek-V2 reference (``bench/reference/deepseek_v2.py``) against
the program: the same parameter tree, the same loss and gradients at a small
size, held shares that add up to the uncut layer, what ``covers`` refuses,
the FLOP count, and controls that fail where the program passes."""
import dataclasses
import functools
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, control, harness, model, spec
from bench.tests import tiny
from repro.configs import get_config
from repro.models import lm

CELL = "dsv2-lite.train.l1"

# Reduced widths: 1 dense + 2 MoE layers, 16 experts with 4 held, top-6.
SIZES = dict(num_layers=3, d_model=64, d_ff=96, vocab_size=256, num_heads=4,
             num_kv_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
             experts_held=4, top_k=6, d_expert_hidden=24,
             yarn_original_max_position=16)

# Readings on the CPU, bfloat16 compute: the program at 1 dense + 2 MoE
# layers, seeds 1-3, loss <= 4.6e-4, grad <= 7.3e-3, update <= 3.6e-3, and
# at 1 + 1, seed 1, 4.2e-4, 4.6e-3, 6.1e-3; fp8 grad >= 5.8e-2 and int8 grad
# >= 2.06e-2 at both.  The grad limit lies between, with room both ways;
# loss and update are tiny.LIMITS'.
LIMITS = dict(tiny.LIMITS, grad=1.4e-2)


def _conf(**over) -> dict:
    conf = json.load(open(os.path.join(tiny.BENCH, "configs",
                                       "deepseek-v2-lite.l1.json")))
    sizes = dict(SIZES, **over)
    conf.update(name="tiny", **sizes)
    conf["reduced"] = {k: [None, v] for k, v in sizes.items()}
    return conf


def _make(root: str, conf: dict) -> str:
    """A checkout at ``root`` whose BENCHMARK.json holds the one cell
    ``tiny.train`` of ``conf``; returns its bench directory."""
    bench = tiny.make(root)
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(conf, f)
    mix = json.load(open(os.path.join(tiny.BENCH, "traffic",
                                      "zipf-markov.2x4096.json")))
    mix.update(batch=4, seq_len=32, pool_batches=4)
    with open(os.path.join(bench, "traffic", "tiny.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "limits", "tiny.train.json"), "w") as f:
        json.dump(LIMITS, f)
    return bench


def _ref(bench=tiny.BENCH):
    return spec._load_module(os.path.join(bench, "reference",
                                          "deepseek_v2.py"), "dsv2_for_test")


def test_cell_resolves_to_the_registered_config():
    cell = spec.resolve(tiny.REPO, CELL)
    cfg = model.program_config(cell.config)
    base = get_config("deepseek-v2-lite")
    assert cfg.attention == base.attention
    assert cfg.moe == dataclasses.replace(base.moe, experts_held=8)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.vocab_size) == (6, 1,
                                                                      12800)
    cell.reference().covers(cfg, cell.config)
    model.check_tree(cell.reference().param_shapes(cell.config), cfg)


def test_tree_equals_the_programs():
    conf = _conf()
    cfg = model.program_config(conf)
    model.check_tree(_ref().param_shapes(conf), cfg)
    with pytest.raises(ValueError, match="parameter tree differs"):
        model.check_tree(_ref().param_shapes(dict(conf, experts_held=8)), cfg)


def test_flops_per_token_at_4096():
    # per layer: MLA 13,762,560; the dense SwiGLU 3*2048*10944; per MoE
    # layer the router 2048*64, 2 shared 3*2048*2816 and 0.75 routed
    # 3*2048*1408 = 23,920,640; head 2048*12800 -> N = 295,632,896,
    # 6N = 1,773,797,376; scores 6*6*16*(192+128)*4096 = 754,974,720
    assert harness.flops_per_token(spec.resolve(tiny.REPO, CELL)) \
        == 2_528_772_096


@pytest.mark.parametrize("change, match", [
    (lambda c, cfg: (dict(c, renormalize=True), dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, renormalize=True))),
     "unrenormalised"),
    (lambda c, cfg: (dict(c, experts_held=8), cfg), "holds 4 of 16"),
    (lambda c, cfg: (c, dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, experts_held=0))),
     "holds 16 of 16"),
])
def test_covers_refuses(change, match):
    conf = _conf()
    cfg = model.program_config(conf)
    conf, cfg = change(conf, cfg)
    with pytest.raises(ValueError, match=match):
        _ref().covers(cfg, conf)


def test_covers_refuses_more_than_one_chip():
    conf = _conf()
    with pytest.raises(ValueError, match="one chip's share"):
        _ref().covers(model.program_config(conf), conf, chips=4)


def _weights(conf, seed=7):
    return jax.jit(functools.partial(model.init_params,
                                     _ref().param_shapes(conf)))(
        model.seed_key(seed))


def test_program_matches_reference_loss_and_grads():
    """Both in float32 on the same weights and tokens: the program's loss
    and every leaf's gradient agree with the reference's to float32
    round-off (rtol 2e-5 on the loss, 2e-4 of each leaf's gradient norm:
    the two sum in different orders, and a routing flip, which the premise
    excludes, would move a leaf by far more)."""
    conf = _conf(dtype="float32")
    cfg = model.program_config(conf)
    ref = _ref()
    params = _weights(conf)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 256)
    dot = ref.make_dot()
    with jax.default_matmul_precision("highest"):
        want, gw = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, tokens, conf, dot)))(params)
        (got, aux), gg = jax.jit(jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, {"tokens": tokens}),
            has_aux=True))(params)
    assert 0.05 < float(aux["drop_frac"]) < 0.6  # premise: drops happen
    assert 0.1 < float(aux["held_frac"]) < 0.5  # and the share is partial
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    for p, g in model.flatten(gw).items():
        h = model.flatten(gg)[p]
        scale = float(jnp.linalg.norm(g))
        assert float(jnp.linalg.norm(h - g)) <= 2e-4 * scale + 1e-9, p


def test_reference_shares_add_up_to_the_uncut_layer():
    """In the reference too: four 4-expert shares of a 16-expert layer, the
    shared experts counted once, give the uncut layer's output."""
    conf = _conf()
    ref = _ref()
    p = jax.tree.map(lambda a: a[0], _weights(dict(conf, experts_held=16))[
        "layers"])["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 64))
    dot = ref.make_dot()
    full, bal, _ = ref._moe(p, x, dict(conf, experts_held=16), dot)
    shared = ref._swiglu(p["shared"], x, dot)
    total = shared
    for lo in range(0, 16, 4):
        perm = np.r_[lo:16, 0:lo]
        share = {"router": {"w": p["router"]["w"][:, perm]},
                 "experts": {k: v[perm[:4]] for k, v in p["experts"].items()},
                 "shared": p["shared"]}
        y, b, _ = ref._moe(share, x, conf, dot)
        total = total + y - shared
        np.testing.assert_allclose(float(b), float(bal), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(full), atol=1e-5)


@pytest.mark.parametrize("variant", ["fp8", "int8"])
def test_control_fails_where_the_program_passes(variant, tmp_path):
    """At 1 dense + 1 MoE layer, to keep the three compiles short."""
    bench = _make(str(tmp_path), _conf(num_layers=2))
    rows = control.sweep(str(tmp_path), "tiny.train", [1],
                         ["program", variant], chip_check=False,
                         compile_cache=False, bench_dir=bench,
                         out=open(os.devnull, "w"))
    for row in rows:
        ok, _ = compare.judge(row["readings"], LIMITS)
        assert ok == (row["variant"] == "program"), row
    shutil.rmtree(bench)


def test_dense_ffn_ms_stage_and_recorded_trace(tmp_path, monkeypatch):
    """The ninth name is a stage of its own where it is innermost; on the
    chip's trace of switch.train.l1, a program with no dense FFN, the
    reader's table agrees with bench/scopes' and the reader reads nothing."""
    import gzip

    from bench import reduce, scopes
    from bench.metrics import dense_ffn_ms
    assert dense_ffn_ms._stage(
        "jit(train_step)/transpose(jvp(fmoe.dense_ffn))/dot_general") == \
        "fmoe.dense_ffn"
    assert dense_ffn_ms._stage("jit(f)/fmoe.attention/fmoe.dense_ffn/mul") \
        == "fmoe.dense_ffn"
    assert dense_ffn_ms._stage("jit(f)/fmoe.dense_ffn/fmoe.experts/mul") \
        == "fmoe.experts"
    root = tmp_path / "checkout"
    prof = root / scopes.TRACE_DIR / "switch.train.l1" / "plugins" / \
        "profile" / "run"
    prof.mkdir(parents=True)
    path = str(prof / "host.xplane.pb")
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "switch.train.l1.scoped.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    monkeypatch.setattr(scopes, "ROOT", str(root))
    assert dense_ffn_ms.table_from_xplane(path) == \
        scopes.table_from_xplane(path)
    tr = reduce.load(path)
    run = harness.RunData(chips=1, steps=3, tokens_per_s=1.0,
                          flops_per_token=1.0, peaks={}, aux={}, counters={},
                          trace=tr, window_ns=reduce.span(tr, "bench.window"))
    assert dense_ffn_ms.read(run) is None
