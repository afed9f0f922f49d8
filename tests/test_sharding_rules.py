"""Sharding-rule + sync-tag tests (paper §3.2 heterogeneity-aware sync).

These run on a single device using abstract meshes — they verify the *rules*,
not execution (tests/test_distributed.py covers execution)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.configs import ARCHS, get_config, reduced
from repro.core.sync import fastmoe_tag, grad_sync_axes, spec_axes
from repro.launch.sharding import _flat_paths, spec_for, tree_specs
from repro.models import lm
from repro.optim.adamw import AdamW


def _mesh(shape=(16, 16), axes=("data", "model")):
    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@pytest.fixture(scope="module")
def arctic_specs():
    cfg = get_config("arctic-480b")
    mesh = _mesh()
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    specs = tree_specs(shapes, mesh)
    return dict(_flat_paths(shapes)), dict(_flat_paths(specs))


def test_expert_params_shard_over_model(arctic_specs):
    shapes, specs = arctic_specs
    # (L, E, d, h): experts over model, hidden dim FSDP over data (the layout
    # that coincides with expert-internal TP — see sharding.py RULES comment)
    s = specs["layers/ffn/experts/wi_gate"]
    assert s == P(None, "model", None, "data")
    assert specs["layers/ffn/experts/wo"] == P(None, "model", "data", None)


def test_router_replicated_world_tag(arctic_specs):
    shapes, specs = arctic_specs
    s = specs["layers/ffn/router/w"]
    assert spec_axes(s) == set()
    assert fastmoe_tag("layers/ffn/router/w", s, ("data", "model")) == "world"


def test_attention_tp_dp_tag(arctic_specs):
    shapes, specs = arctic_specs
    s = specs["layers/attn/wq/w"]
    assert "model" in spec_axes(s)
    assert fastmoe_tag("layers/attn/wq/w", s, ("data", "model")) == "dp"


def test_expert_none_tag():
    s = P(None, "model", "data", None)
    tag = fastmoe_tag("layers/ffn/experts/wi_gate", s, ("data", "model"))
    assert tag == "none"


def test_grad_sync_axes_complement():
    assert grad_sync_axes(P("model", None), ("pod", "data", "model")) == ("pod", "data")
    assert grad_sync_axes(P(None), ("data", "model")) == ("data", "model")


def test_divisibility_guard_replicates():
    # vocab 49155 is not divisible by model=16 -> replicated on that dim
    spec = spec_for("embed/table", (49155, 2048), _mesh(), stacked=False)
    assert spec[0] is None
    assert spec[1] == ("data",) or spec[1] == "data"


def test_stacked_layer_dim_never_sharded():
    spec = spec_for("layers/attn/wq/w", (40, 2048, 2048), _mesh(), stacked=True)
    assert spec[0] is None


@pytest.mark.parametrize("arch", ["qwen2-72b", "deepseek-v2-236b", "rwkv6-7b",
                                  "hymba-1.5b", "whisper-tiny"])
def test_all_params_get_valid_specs(arch):
    cfg = get_config(arch)
    mesh = _mesh((2, 16, 16), ("pod", "data", "model"))
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    flat_shapes = dict(_flat_paths(shapes))
    flat_specs = dict(_flat_paths(tree_specs(shapes, mesh)))
    assert set(flat_shapes) == set(flat_specs)
    for path, spec in flat_specs.items():
        shape = flat_shapes[path].shape
        assert len(spec) <= len(shape), (path, spec, shape)
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert shape[i] % size == 0, (path, spec, shape)


def test_head_aware_rules():
    """Arch-aware overrides: heads not divisible by the model axis =>
    replicate the offending projections (§Perf, avoids SPMD replication)."""
    from repro.launch.sharding import rules_for, tree_specs
    mesh = _mesh()
    # arctic: H=56, KV=8 — both indivisible by 16 -> q/k/v/wo replicated
    cfg = get_config("arctic-480b")
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    specs = dict(_flat_paths(tree_specs(shapes, mesh, cfg=cfg)))
    assert "model" not in spec_axes(specs["layers/attn/wq/w"])
    assert "model" not in spec_axes(specs["layers/attn/wk/w"])
    # qwen2: H=64 divisible, KV=8 not -> q sharded, k/v replicated
    cfg = get_config("qwen2-72b")
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    specs = dict(_flat_paths(tree_specs(shapes, mesh, cfg=cfg)))
    assert "model" in spec_axes(specs["layers/attn/wq/w"])
    assert "model" not in spec_axes(specs["layers/attn/wk/w"])


def test_serve_mode_drops_fsdp():
    from repro.launch.sharding import tree_specs
    mesh = _mesh()
    cfg = get_config("qwen2-72b")
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    train = dict(_flat_paths(tree_specs(shapes, mesh, "train")))
    serve = dict(_flat_paths(tree_specs(shapes, mesh, "serve")))
    # FSDP (data) sharding present in train, absent in serve
    assert "data" in spec_axes(train["layers/ffn/wi_gate"])
    assert "data" not in spec_axes(serve["layers/ffn/wi_gate"])
    # TP (model) retained in both
    assert "model" in spec_axes(serve["layers/ffn/wi_gate"])


def test_cache_seq_sharding():
    from repro.launch.sharding import cache_specs
    from repro.models import lm as _lm
    cfg = get_config("qwen2-72b")
    mesh = _mesh()
    cache = jax.eval_shape(lambda: _lm.init_cache(cfg, 128, 32768))
    specs = dict(_flat_paths(cache_specs(cache, mesh, 128, seq_shard=True)))
    assert specs["k"][2] == "model"  # (L, B, W, KV, hd): window over model
    assert specs["positions"][2] == "model"
    default = dict(_flat_paths(cache_specs(cache, mesh, 128)))
    assert default["k"][-1] == "model"  # head_dim sharded by default


def test_sync_report_covers_three_tags():
    cfg = get_config("deepseek-v2-236b")
    mesh = _mesh()
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    flat_specs = dict(_flat_paths(tree_specs(shapes, mesh)))
    tags = {fastmoe_tag(p, s, ("data", "model")) for p, s in flat_specs.items()}
    assert tags == {"world", "dp", "none"}


@pytest.mark.parametrize("mesh_shape,mesh_axes", [
    ((1, 4), ("data", "model")),
    ((2, 16, 16), ("pod", "data", "model")),
], ids=["1x4", "2x16x16"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_adamw_moments_get_param_specs(arch, mesh_shape, mesh_axes):
    """Each AdamW moment leaf ``mu/<p>``, ``nu/<p>`` takes exactly the spec
    of the parameter ``<p>`` it mirrors (a stacked moment keeps its leading
    layer dim unsharded), and the step counter is replicated: a moment in
    another layout would be resharded inside every optimizer update."""
    cfg = get_config(arch)
    mesh = _mesh(mesh_shape, mesh_axes)
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(AdamW().init, params)
    pspecs = dict(_flat_paths(tree_specs(params, mesh)))
    ospecs = dict(_flat_paths(tree_specs(state, mesh)))
    assert ospecs.pop("step") == P()
    assert set(ospecs) == {f"{m}/{p}" for m in ("mu", "nu") for p in pspecs}
    for path, spec in pspecs.items():
        assert ospecs[f"mu/{path}"] == spec, (path, ospecs[f"mu/{path}"], spec)
        assert ospecs[f"nu/{path}"] == spec, (path, ospecs[f"nu/{path}"], spec)
