"""Fused expert FFN — grouped GEMM1 + activation + grouped GEMM2, one kernel.

``expert_ffn_pallas`` (two-pass) runs the expert FFN as two/three separate
grouped GEMMs, which materializes the (M, H) hidden activation in HBM between
them: at bf16 that is 2*M*H bytes written and read back per layer, pure HBM
traffic the MXU waits on.  This kernel keeps the hidden tile resident in
VMEM: for each row tile (bm rows of one expert ``g``) and each hidden tile
``j`` of width ``bh``,

    h_j   = act(x_tile @ wi[g][:, j])          # (bm, bh), VMEM only
    acc  += h_j @ wo[g][j, :]                  # (bm, N) f32 scratch

so the hidden activation never exists at (M, H) anywhere — only one (bm, bh)
tile at a time, consumed immediately by the second GEMM.  The f32 output
accumulator flushes once per row tile.

Grid (m_tiles, h_tiles): row tiles parallel, hidden tiles sequential
(``arbitrary``) because they accumulate into the same output block.  The
expert id per row tile is scalar-prefetched (same contract as
``grouped_gemm``: rows sorted by group and padded to ``bm`` multiples via
``repro.core.dispatch.pad_to_tiles``).

VMEM working set: x (bm, K) + per-projection weight tiles (K*bh + bh*N) +
f32 acc (bm, N).  Defaults (bm=128, bh=512) with d_model ≤ 2048 stay well
inside the ~16 MiB/core budget.

Backward is fused too (repro.kernels.fused_ffn_bwd wires through the
custom_vjp in repro.kernels.ops): dX and the grouped dW recompute the hidden
tile in VMEM from the saved x, so a full train step never materializes
(M, H) in HBM in either direction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BH = 512


def check_gating(ws: tuple, act: str) -> None:
    """swiglu needs (wi_gate, wi_up); every other act needs a single (wi,).

    A mismatch either ignores wi_up in forward while the two-pass backward
    uses it, or multiplies by None mid-trace — fail loudly instead.
    """
    if (len(ws) == 2) != (act == "swiglu"):
        raise ValueError(
            f"act='swiglu' requires ws=(wi_gate, wi_up); other activations "
            f"require ws=(wi,) — got {len(ws)} weight(s) with act={act!r}")


def _activate(g: jax.Array, u, act: str) -> jax.Array:
    """Activation between the GEMMs (mirrors repro.core.fmoe._act)."""
    if act == "swiglu":
        return jax.nn.silu(g) * u
    if act == "gelu":
        return jax.nn.gelu(g)
    if act == "rwkv":  # squared relu (RWKV channel-mix)
        return jnp.square(jax.nn.relu(g))
    return jax.nn.silu(g)


def _kernel(tile_group_ref, x_ref, *refs, n_h: int, act: str, gated: bool,
            h_tail: int):
    del tile_group_ref  # consumed by the index maps
    if gated:
        wg_ref, wu_ref, wo_ref, o_ref, acc_ref = refs
    else:
        wg_ref, wo_ref, o_ref, acc_ref = refs
        wu_ref = None

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = (jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
         if gated else None)
    # match the two-pass dataflow: the hidden activation is produced at the
    # working dtype (what grouped_matmul would have written to HBM) — here it
    # just never leaves VMEM
    h = _activate(g, u, act).astype(x.dtype)
    wo = wo_ref[0]
    if h_tail:
        # H % bh != 0: the last hidden tile's trailing columns/rows come
        # from out-of-bounds weight reads — unspecified values (NaN in the
        # interpreter, garbage on TPU).  Mask BOTH sides of the contraction:
        # a zeroed h column times a NaN wo row would still be NaN.
        limit = jnp.where(pl.program_id(1) == n_h - 1, h_tail, h.shape[1])
        col = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
        h = jnp.where(col < limit, h, jnp.zeros_like(h))
        row = jax.lax.broadcasted_iota(jnp.int32, wo.shape, 0)
        wo = jnp.where(row < limit, wo, jnp.zeros_like(wo))
    acc_ref[...] += jnp.dot(h, wo, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == n_h - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("act", "bm", "bh", "interpret"))
def fused_ffn_tiled(x: jax.Array, ws: tuple, wo: jax.Array,
                    tile_group: jax.Array, *, act: str = "swiglu",
                    bm: int = DEFAULT_BM, bh: int = DEFAULT_BH,
                    interpret: bool = False) -> jax.Array:
    """y = (act(x @ wi[g]) [* gate]) @ wo[g] with tile-aligned groups.

    x: (M, K), M % bm == 0, rows of one group confined to whole tiles;
    ws: (wi,) or (wi_gate, wi_up) each (E, K, H); wo: (E, H, N);
    tile_group: (M // bm,) int32 expert id per row tile.
    """
    M, K = x.shape
    E, K2, H = ws[0].shape
    E2, H2, N = wo.shape
    assert K == K2 and H == H2 and E == E2 and M % bm == 0, (
        x.shape, ws[0].shape, wo.shape, bm)
    check_gating(ws, act)
    gated = len(ws) == 2
    bh = min(bh, H)
    n_m, n_h = M // bm, pl.cdiv(H, bh)

    wi_spec = pl.BlockSpec((1, K, bh), lambda i, j, g: (g[i], 0, j))
    in_specs = [pl.BlockSpec((bm, K), lambda i, j, g: (i, 0))]
    in_specs += [wi_spec] * len(ws)
    in_specs += [pl.BlockSpec((1, bh, N), lambda i, j, g: (g[i], j, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_m, n_h),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, N), lambda i, j, g: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_h=n_h, act=act, gated=gated,
                          h_tail=H % bh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(tile_group, x, *ws, wo)
