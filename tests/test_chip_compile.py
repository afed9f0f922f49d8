"""Main-path kernels compiled for a described TPU v5e (no chip attached).

The TPU compiler ships with jax; it compiles for a chip described by
``topologies.get_topology_desc`` and refuses what the chip would refuse:
tiles past the scoped-VMEM limit, unaligned slices, unimplemented
collectives.  Interpret mode (the CPU validation path of every other
kernel test) can show none of that.  Each case compiles at fastmoe-gpt
widths (d_model 1024, expert hidden 2048, 96 experts, 256 rows each) and
asserts the Pallas kernel is in the program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest worker imports this
file.  The worker that runs these tests holds the library until it exits.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

E, ROWS, D, H = 96, 256, 1024, 2048  # fastmoe-gpt expert layer
M = E * ROWS
BM = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(kernel: str):
    from repro.kernels import fused_ffn as ff
    from repro.kernels import fused_ffn_bwd as fb
    from repro.kernels import grouped_gemm as gg

    return {
        "fused_ffn": lambda x, wi, wo, dy, tg: ff.fused_ffn_tiled(
            x, (wi,), wo, tg, act="gelu"),
        "fused_ffn_bwd_dx": lambda x, wi, wo, dy, tg: fb.fused_ffn_bwd_dx_tiled(
            x, (wi,), wo, dy, tg, act="gelu"),
        "fused_ffn_bwd_dw": lambda x, wi, wo, dy, tg: fb.fused_ffn_bwd_dw_tiled(
            x, (wi,), wo, dy, tg, act="gelu"),
        "grouped_gemm": lambda x, wi, wo, dy, tg: gg.grouped_gemm_tiled(
            x, wi, tg),
    }[kernel]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kernel", ["fused_ffn", "fused_ffn_bwd_dx",
                                    "fused_ffn_bwd_dw", "grouped_gemm"])
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype):
    """Each main-path kernel compiles for one v5e core at full width.  The
    f32 dW case needs the VMEM-fitted hidden tile (fused_ffn_bwd
    ``_dw_block_h``): at the default bh=512 Mosaic runs out of VMEM."""
    dt = jnp.dtype(dtype)
    args = (_shape((M, D), dt, one_chip), _shape((E, D, H), dt, one_chip),
            _shape((E, H, D), dt, one_chip), _shape((M, D), dt, one_chip),
            _shape((M // BM,), jnp.int32, one_chip))
    compiled = jax.jit(_kernel_call(kernel)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_expert_ffn_grad_compiles_for_v5e(one_chip, monkeypatch):
    """The expert FFN as the train step runs it (``impl="fused"``): the
    custom_vjp forward + fused dX/dW backward through ``ops`` with
    capacity-style groups that are not whole row tiles (216 rows each, the
    fastmoe-gpt capacity at 8k tokens), so pad_to_tiles runs too.  ``ops``
    picks interpret mode from the default backend (the CPU here); the test
    steers it to the compiled kernels."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    rows, bf16 = 216, jnp.bfloat16

    def loss(x, wi, wo, sizes):
        y = ops.fused_grouped_ffn(x, (wi,), wo, sizes, "gelu")
        return jnp.sum(y.astype(jnp.float32) ** 2)

    args = (_shape((E * rows, D), bf16, one_chip),
            _shape((E, D, H), bf16, one_chip),
            _shape((E, H, D), bf16, one_chip),
            _shape((E,), jnp.int32, one_chip))
    txt = (jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args)
           .compile().as_text())
    # forward, dX and dW kernels all present
    assert txt.count("tpu_custom_call") >= 3, txt.count("tpu_custom_call")


def test_native_ragged_all_to_all_compiles_for_v5e(topo):
    """core/comm picks the transport from the devices in use: on a mesh of
    TPU devices the gate takes XLA's native ragged-all-to-all (only valid
    prefixes cross the wire) — the branch XLA:CPU cannot run, so it is
    compiled here, for four described v5e chips."""
    from repro.core import comm

    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(4), ("model",))
    mp, bound, d = 4, 64, D
    picked = []

    def run(s, sz):
        picked.append(comm.native_ragged_all_to_all())
        recv_sz = jax.lax.all_to_all(sz[0].reshape(mp, 1), "model", 0, 0,
                                     tiled=True).reshape(mp)
        return comm.ragged_all_to_all_shards(s[0], sz[0], recv_sz,
                                             "model")[None]

    fn = jax.shard_map(run, mesh=mesh, in_specs=(P("model"), P("model")),
                       out_specs=P("model"), check_vma=False)
    shard = NamedSharding(mesh, P("model"))
    args = (_shape((mp, mp, bound, d), jnp.bfloat16, shard),
            _shape((mp, mp), jnp.int32, shard))
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert picked == [True]
    assert "ragged-all-to-all" in txt


def _scope_counts(hlo: str) -> dict:
    """{scope: instructions of the optimized HLO whose op_name holds it}."""
    import re

    from repro.obs import scopes

    counts = dict.fromkeys(scopes.ALL, 0)
    for op in re.findall(r'op_name="([^"]*)"', hlo):
        for name in set(re.findall(r"fmoe\.[a-z_]+", op)) & set(counts):
            counts[name] += 1
    return counts


def test_train_step_scopes_name_instructions_for_v5e(topo, one_chip):
    """Every stage scope survives the TPU compiler: in the optimized HLO of
    a tiny one-chip step (the _moe_local path) and of a tiny 1x4 step (the
    _moe_a2a path, which adds fmoe.exchange) for described v5e chips, each
    of the eight names at least one instruction."""
    from jax.sharding import AxisType, Mesh

    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.launch.train import jit_train_step, make_train_step, moe_dist
    from repro.models import lm
    from repro.obs import scopes
    from repro.optim.adamw import AdamW

    opt = AdamW()
    tokens = jax.ShapeDtypeStruct((8, 64), jnp.int32)
    step_no = jax.ShapeDtypeStruct((), jnp.int32)

    def state(cfg, sharding=None):
        p = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
        o = jax.eval_shape(opt.init, p)
        if sharding is not None:
            p, o = jax.tree.map(lambda a: _shape(a.shape, a.dtype, sharding),
                                (p, o))
        return p, o

    cfg = reduced(get_config("switch-base-128"), num_layers=1, d_model=128)
    p, o = state(cfg, one_chip)
    local = (jax.jit(make_train_step(cfg, opt))
             .lower(p, o, {"tokens": _shape((8, 64), jnp.int32, one_chip)},
                    _shape((), jnp.int32, one_chip)).compile().as_text())
    got = _scope_counts(local)
    assert all(got[s] for s in scopes.ALL if s != scopes.EXCHANGE), got

    cfg = reduced(get_config("fastmoe-gpt"), num_layers=2, d_model=128)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    assert moe_dist(cfg, mesh, 8 * 64).mode == "a2a"
    step, _, _ = jit_train_step(cfg, opt, mesh, 8, 64)
    p, o = state(cfg)
    a2a = step.lower(p, o, {"tokens": tokens}, step_no).compile().as_text()
    got = _scope_counts(a2a)
    assert all(got.values()), got
