"""The configuration's reference module owns the model: its parameter tree,
what it covers and its FLOPs.  A new architecture is files alone, the
existing cells' weights are what they were, and ``program_config`` checks
every program field a configuration file states, by name."""
import functools
import hashlib
import json
import math
import os

import jax
import numpy as np
import pytest

from bench import harness, model, spec
from bench.tests import tiny

SEED = 2**31 + 77

# Each leaf's shape and norm (math.fsum of the float32 squares) from the
# weights of ``SEED`` at tiny.py's widths, as the benchmark made them before
# the tree moved into the reference module.
PINNED = {
    "switch-base-128": {
        "embed/table": ([256, 64], 2.5619061990335523),
        "final_norm/scale": ([64], 8.0),
        "layers/attn/wk/w": ([1, 64, 64], 7.98695708282514),
        "layers/attn/wo/w": ([1, 64, 64], 7.9811631232950155),
        "layers/attn/wq/w": ([1, 64, 64], 8.120944130410253),
        "layers/attn/wv/w": ([1, 64, 64], 7.951235531980938),
        "layers/ffn/experts/wi": ([1, 8, 64, 128], 31.970691220677196),
        "layers/ffn/experts/wo": ([1, 8, 128, 64], 22.592581000800426),
        "layers/ffn/router/w": ([1, 64, 8], 2.8246278029904115),
        "layers/norm1/scale": ([1, 64], 8.0),
        "layers/norm2/scale": ([1, 64], 8.0),
        "lm_head/w": ([64, 256], 16.032693314611386),
    },
    "fastmoe-gpt": {
        "embed/table": ([256, 64], 2.5619061990335523),
        "final_norm/bias": ([64], 0.0),
        "final_norm/scale": ([64], 8.0),
        "layers/attn/wk/w": ([2, 64, 64], 11.188810271923767),
        "layers/attn/wo/w": ([2, 64, 64], 11.455190625754113),
        "layers/attn/wq/w": ([2, 64, 64], 11.156069136785824),
        "layers/attn/wv/w": ([2, 64, 64], 11.271927903174364),
        "layers/ffn/experts/wi": ([2, 8, 64, 128], 45.160481188761985),
        "layers/ffn/experts/wo": ([2, 8, 128, 64], 32.052849444725325),
        "layers/ffn/router/w": ([2, 64, 8], 3.948237988144998),
        "layers/norm1/bias": ([2, 64], 0.0),
        "layers/norm1/scale": ([2, 64], 11.313708498984761),
        "layers/norm2/bias": ([2, 64], 0.0),
        "layers/norm2/scale": ([2, 64], 11.313708498984761),
        "lm_head/w": ([64, 256], 16.170880723190177),
    },
}
TINY = {"switch-base-128": dict(layers=1, top_k=1),
        "fastmoe-gpt": dict(layers=2, top_k=2)}

# A reference whose tree differs from moe_lm's: moe_lm plus always-on
# shared experts, ``layers/ffn/shared/{wi,wo}``.
SHARED_LM = '''"""Test reference: moe_lm with shared experts."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "shared_lm_base", os.path.join(os.path.dirname(__file__), "moe_lm.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)


def _shared(conf):
    return conf["num_shared_experts"] * conf["d_expert_hidden"]


def param_shapes(conf):
    tree = base.param_shapes(conf)
    L, d, S = conf["num_layers"], conf["d_model"], _shared(conf)
    tree["layers"]["ffn"]["shared"] = {"wi": (L, d, S), "wo": (L, S, d)}
    return tree


def layer_leaves(conf):
    return base.layer_leaves(conf) + ["layers/ffn/shared/wi",
                                      "layers/ffn/shared/wo"]


expert_leaves = base.expert_leaves


def covers(cfg, conf, chips=1, dist=None):
    if cfg.moe.num_shared_experts != conf["num_shared_experts"]:
        raise ValueError("shared experts differ")


def flops_per_token(conf, seq_len):
    shared = 2 * conf["d_model"] * _shared(conf)
    return (base.flops_per_token(conf, seq_len)
            + 6 * conf["num_layers"] * shared)
'''


def _tiny_conf(root, arch):
    bench = tiny.make(root, arch=arch, **TINY[arch])
    return bench, json.load(open(os.path.join(bench, "configs", "tiny.json")))


def _digests(bench):
    out = {}
    for d, _, files in os.walk(bench):
        for f in files:
            if f.endswith(".pyc"):
                continue
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, bench)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.mark.parametrize("arch", sorted(PINNED))
def test_existing_cells_weights_unchanged(arch, tmp_path):
    bench, conf = _tiny_conf(str(tmp_path), arch)
    cell = spec.resolve(str(tmp_path), "tiny.train", bench)
    shapes = cell.reference().param_shapes(conf)
    params = jax.jit(functools.partial(model.init_params, shapes))(
        model.seed_key(SEED))
    got = {}
    for p, a in model.flatten(params).items():
        a = np.asarray(a)
        sq = np.square(a.astype(np.float64)).ravel()
        got[p] = (list(a.shape), math.sqrt(math.fsum(sq)))
    assert sorted(got) == sorted(PINNED[arch])
    for p, (shape, norm) in PINNED[arch].items():
        assert got[p][0] == shape, p
        # a changed fold-in order, scale or shape moves a norm by far more
        assert got[p][1] == pytest.approx(norm, rel=1e-6, abs=1e-12), p


def test_new_architecture_is_files_only(tmp_path):
    """A reference with another tree runs through resolution, the program
    checks, the weights and the FLOPs with no other bench file edited."""
    root = str(tmp_path)
    bench, conf = _tiny_conf(root, "fastmoe-gpt")
    before = _digests(bench)
    with open(os.path.join(bench, "reference", "shared_lm.py"), "w") as f:
        f.write(SHARED_LM)
    conf.update(reference="shared_lm", num_shared_experts=1)
    conf["reduced"]["num_shared_experts"] = [0, 1]
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(conf, f)

    cell = spec.resolve(root, "tiny.train", bench)
    cfg = model.program_config(cell.config)
    assert cfg.moe.num_shared_experts == 1
    prog = harness.build_program(cell, jax.devices()[:1])
    key, params, _, pool_host, _ = harness.start(cell, prog, SEED)
    shared = params["layers"]["ffn"]["shared"]
    assert shared["wi"].shape == (2, 64, 128)
    assert shared["wo"].shape == (2, 128, 64)
    assert pool_host.shape == (4, 8, 32)
    moe_lm = spec._load_module(os.path.join(bench, "reference", "moe_lm.py"),
                               "moe_lm_for_test")
    assert harness.flops_per_token(cell) == (
        moe_lm.flops_per_token(cell.config, 32) + 6 * 2 * 2 * 64 * 128)
    after = _digests(bench)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {os.path.join("configs", "tiny.json")}
    assert set(after) - set(before) == {os.path.join("reference",
                                                     "shared_lm.py")}


def test_moe_lm_refuses_shared_experts(tmp_path):
    root = str(tmp_path)
    bench, conf = _tiny_conf(root, "fastmoe-gpt")
    conf.update(num_shared_experts=1)
    conf["reduced"]["num_shared_experts"] = [0, 1]
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(conf, f)
    cell = spec.resolve(root, "tiny.train", bench)
    with pytest.raises(ValueError, match="no shared or dense residual"):
        harness.build_program(cell, jax.devices()[:1])


def test_reduced_key_naming_no_program_field_is_an_error(tmp_path):
    _, conf = _tiny_conf(str(tmp_path), "switch-base-128")
    conf["num_routed_experts"] = 8
    conf["reduced"]["num_routed_experts"] = [128, 8]
    with pytest.raises(ValueError, match="num_routed_experts"):
        model.program_config(conf)


@pytest.mark.parametrize("key, value", [
    ("norm", "rmsnorm"),  # top level
    ("kv_lora_rank", 512),  # attention, a width GQA leaves at 0
    ("num_shared_experts", 2),  # moe
    ("d_ff", 10944),  # top level, unused by an MoE layer
])
def test_stated_field_that_disagrees_is_an_error(key, value, tmp_path):
    _, conf = _tiny_conf(str(tmp_path), "fastmoe-gpt")
    conf[key] = value
    with pytest.raises(ValueError, match=key):
        model.program_config(conf)


def test_field_of_two_groups_is_an_error():
    # hymba-1.5b has attention and ssm groups, both with a head_dim
    conf = {"name": "t", "program_arch": "hymba-1.5b", "reduced": {},
            "head_dim": 64}
    with pytest.raises(ValueError, match="head_dim"):
        model.program_config(conf)


@pytest.mark.parametrize("workload", ["switch.train.l1", "gpt.train.ep4",
                                      "gpt.train.l1"])
def test_cells_resolve_to_their_registered_config(workload):
    """A cell's file, through the rule by name, gives the registered config
    with only its ``reduced`` keys changed."""
    from repro.configs import get_config

    cell = spec.resolve(tiny.REPO, workload)
    cfg = model.program_config(cell.config)
    base = get_config(cell.config["program_arch"])
    assert cfg.attention == base.attention and cfg.moe == base.moe
    changed = {k for k in ("num_layers", "vocab_size", "d_model")
               if getattr(cfg, k) != getattr(base, k)}
    assert changed == set(cell.config["reduced"])
    cell.reference().covers(cfg, cell.config)
