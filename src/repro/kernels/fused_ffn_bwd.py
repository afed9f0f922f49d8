"""Fused expert-FFN backward — dX and grouped dW without the (M, H) hidden.

``fused_ffn.fused_ffn_tiled`` removed the (M, H) HBM round-trip from the
*forward*; until this module existed the custom_vjp fell back to the two-pass
grouped GEMMs, so every training step still materialized the hidden
activation (and its gradient) at (M, H) in HBM and paid two extra grouped
GEMMs of recompute.  Training is FastMoE's whole point (§4–5), so the
backward gets the same treatment: for each row tile (bm rows of one expert
``g``) and hidden tile ``j`` of width ``bh``, both kernels recompute the
hidden tile in VMEM from the saved x and consume it immediately —

dX kernel (grid (m_tiles, h_tiles), row tiles parallel, hidden sequential):

    g_j, u_j = x @ wi[g][:, j], x @ wi_up[g][:, j]   # (bm, bh), VMEM only
    dh_j     = dy @ wo[g][j, :]^T                    # (bm, bh), VMEM only
    dg_j,du_j= vjp(act)(g_j, u_j)(dh_j)              # exact act gradient
    acc     += dg_j @ wi[g][:, j]^T [+ du_j @ ...]   # (bm, K) f32 scratch

dW kernel (grid (h_tiles, m_tiles): row tiles *inner* so each expert's
(dwi[:, j] / dwo[j, :]) output block is visited by consecutive grid steps and
accumulates in VMEM across that expert's row tiles, f32):

    dwo[g][j, :] += h_j^T @ dy
    dwi[g][:, j] += x^T @ dg_j        (and dwi_up += x^T @ du_j)

Neither the hidden tile nor its gradient ever exists at (M, H) anywhere.
The activation gradient goes through ``jax.vjp`` of the *same*
``fused_ffn._activate`` the forward runs, so swiglu/gelu/rwkv/silu backward
is exact by construction (including gelu's tanh approximation).

Tail tiles (H % bh != 0) mask both sides of every contraction, like the
forward: out-of-bounds weight reads are unspecified (NaN in the
interpreter), and NaN * 0 is still NaN.

VMEM working set (dX): x (bm, K) + dy (bm, N) + weight tiles
(len(ws)*K*bh + bh*N) + f32 acc (bm, K); dW additionally holds the f32
output blocks (len(ws)*K*bh + bh*N).  Every block is double-buffered, so
dW at d_model 1024 with f32 weights and the default bh=512 needs ~19 MiB,
over the 16 MiB scoped-VMEM default; ``_dw_block_h`` halves dW's hidden
tile until its working set fits (dW's sums do not depend on bh).

``repro.kernels.ops`` wires both into ``fused_grouped_ffn``'s custom_vjp
(padding/unpadding rows via ``pad_to_tiles`` exactly like the forward) and
masks the dW of empty groups, whose output blocks no grid step visits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fused_ffn as ff


def _hidden_and_grads(x, dy, wg_ref, wu_ref, wo_ref, *, act, gated, h_tail,
                      j, n_h):
    """Shared per-tile recompute: hidden tile, dh, and activation grads.

    Returns (h, dg, du) with tail columns (and the weight tiles feeding dX)
    already masked; h is cast to x.dtype exactly like the forward.
    """
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = (jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
         if gated else None)
    # dh = dy @ wo^T, contracting the output dim — (bm, bh), VMEM only
    dh = jax.lax.dot_general(dy, wo_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if gated:
        h, act_vjp = jax.vjp(lambda a, b: ff._activate(a, b, act), g, u)
        dg, du = act_vjp(dh)
    else:
        h, act_vjp = jax.vjp(lambda a: ff._activate(a, None, act), g)
        (dg,), du = act_vjp(dh), None
    if h_tail:
        # last hidden tile: columns past H came from out-of-bounds weight
        # reads (unspecified values) — zero every tail column before it can
        # poison a contraction (NaN * 0 == NaN)
        limit = jnp.where(j == n_h - 1, h_tail, h.shape[1])
        col = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
        valid = col < limit
        h = jnp.where(valid, h, 0.0)
        dg = jnp.where(valid, dg, 0.0)
        if gated:
            du = jnp.where(valid, du, 0.0)
    return h.astype(x.dtype), dg, du


def _tail_mask_w(w, h_tail, j, n_h):
    """Zero the tail columns of a (K, bh) weight tile (rows of w^T)."""
    if not h_tail:
        return w
    limit = jnp.where(j == n_h - 1, h_tail, w.shape[1])
    col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    return jnp.where(col < limit, w, jnp.zeros_like(w))


def _dx_kernel(tile_group_ref, x_ref, dy_ref, *refs, n_h: int, act: str,
               gated: bool, h_tail: int):
    del tile_group_ref  # consumed by the index maps
    if gated:
        wg_ref, wu_ref, wo_ref, dx_ref, acc_ref = refs
    else:
        wg_ref, wo_ref, dx_ref, acc_ref = refs
        wu_ref = None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    dy = dy_ref[...]
    _, dg, du = _hidden_and_grads(x, dy, wg_ref, wu_ref, wo_ref, act=act,
                                  gated=gated, h_tail=h_tail, j=j, n_h=n_h)
    # dX += dg @ wi^T (contract the hidden dim); the hidden-grad tile is
    # consumed here and never leaves VMEM
    wg = _tail_mask_w(wg_ref[0], h_tail, j, n_h)
    acc_ref[...] += jax.lax.dot_general(
        dg.astype(x.dtype), wg, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if gated:
        wu = _tail_mask_w(wu_ref[0], h_tail, j, n_h)
        acc_ref[...] += jax.lax.dot_general(
            du.astype(x.dtype), wu, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_h - 1)
    def _flush():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _dw_kernel(tile_group_ref, x_ref, dy_ref, *refs, n_h: int, act: str,
               gated: bool, h_tail: int):
    if gated:
        wg_ref, wu_ref, wo_ref, dwg_ref, dwu_ref, dwo_ref = refs
    else:
        wg_ref, wo_ref, dwg_ref, dwo_ref = refs
        wu_ref = dwu_ref = None
    j = pl.program_id(0)
    i = pl.program_id(1)
    # first row tile of this expert's block: zero the freshly-mapped output
    # blocks (they accumulate in VMEM across the group's consecutive tiles)
    first = (i == 0) | (tile_group_ref[i]
                        != tile_group_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _init():
        dwg_ref[...] = jnp.zeros_like(dwg_ref)
        dwo_ref[...] = jnp.zeros_like(dwo_ref)
        if gated:
            dwu_ref[...] = jnp.zeros_like(dwu_ref)

    x = x_ref[...]
    dy = dy_ref[...]
    h, dg, du = _hidden_and_grads(x, dy, wg_ref, wu_ref, wo_ref, act=act,
                                  gated=gated, h_tail=h_tail, j=j, n_h=n_h)
    # dwo[j, :] += h^T @ dy ; dwi[:, j] += x^T @ dg  (contract the rows);
    # padded rows are zero in BOTH x and dy, so they contribute nothing
    dwo_ref[...] += jax.lax.dot_general(
        h, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[None]
    dwg_ref[...] += jax.lax.dot_general(
        x, dg.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[None]
    if gated:
        dwu_ref[...] += jax.lax.dot_general(
            x, du.astype(x.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]


def _common_dims(x, ws, wo, dy, bm, bh):
    M, K = x.shape
    E, K2, H = ws[0].shape
    E2, H2, N = wo.shape
    M2, N2 = dy.shape
    assert (K == K2 and H == H2 and E == E2 and M == M2 and N == N2
            and M % bm == 0), (x.shape, ws[0].shape, wo.shape, dy.shape, bm)
    bh = min(bh, H)
    return M, K, H, N, E, bh, M // bm, pl.cdiv(H, bh)


_VMEM_BUDGET = 16 * 2**20  # Mosaic's default scoped-VMEM limit per core


def _dw_block_h(bm: int, K: int, N: int, bh: int, n_w: int, x_bytes: int,
                w_bytes: int) -> int:
    """Largest hidden tile, halving from ``bh`` (down to one 128-lane tile),
    whose dW working set fits ``_VMEM_BUDGET``: double-buffered row, weight
    and f32 output blocks plus the (bm, bh) f32 recompute intermediates."""
    def need(b):
        w_cols = n_w * K * b + b * N
        blocks = bm * (K + N) * x_bytes + w_cols * w_bytes + w_cols * 4
        return 2 * blocks + (2 + 2 * n_w) * bm * b * 4
    while bh > 128 and need(bh) > _VMEM_BUDGET:
        bh //= 2
    return bh


def _wi_spec(K, bh, index_map):
    return pl.BlockSpec((1, K, bh), index_map)


@functools.partial(jax.jit,
                   static_argnames=("act", "bm", "bh", "interpret"))
def fused_ffn_bwd_dx_tiled(x: jax.Array, ws: tuple, wo: jax.Array,
                           dy: jax.Array, tile_group: jax.Array, *,
                           act: str = "swiglu", bm: int = ff.DEFAULT_BM,
                           bh: int = ff.DEFAULT_BH,
                           interpret: bool = False) -> jax.Array:
    """dX for y = act(x @ wi[g]) @ wo[g], hidden/dhidden tiles VMEM-only.

    Same tiling contract as ``fused_ffn_tiled``: rows sorted by group and
    padded to ``bm`` multiples, ``tile_group`` scalar-prefetched.
    """
    ff.check_gating(ws, act)
    gated = len(ws) == 2
    M, K, H, N, E, bh, n_m, n_h = _common_dims(x, ws, wo, dy, bm, bh)

    in_specs = [pl.BlockSpec((bm, K), lambda i, j, g: (i, 0)),
                pl.BlockSpec((bm, N), lambda i, j, g: (i, 0))]
    in_specs += [_wi_spec(K, bh, lambda i, j, g: (g[i], 0, j))] * len(ws)
    in_specs += [pl.BlockSpec((1, bh, N), lambda i, j, g: (g[i], j, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_m, n_h),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, K), lambda i, j, g: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, K), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dx_kernel, n_h=n_h, act=act, gated=gated,
                          h_tail=H % bh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, K), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(tile_group, x, dy, *ws, wo)


@functools.partial(jax.jit,
                   static_argnames=("act", "bm", "bh", "interpret"))
def fused_ffn_bwd_dw_tiled(x: jax.Array, ws: tuple, wo: jax.Array,
                           dy: jax.Array, tile_group: jax.Array, *,
                           act: str = "swiglu", bm: int = ff.DEFAULT_BM,
                           bh: int = ff.DEFAULT_BH, interpret: bool = False):
    """Grouped (dwi..., dwo) in f32, hidden tiles recomputed in VMEM.

    Row tiles are the *inner* grid dim so each expert's weight-grad block is
    revisited by consecutive steps only (the legal Pallas accumulation
    pattern).  Blocks of groups that own no row tiles are never written —
    the caller masks empty groups (``repro.kernels.ops`` does).
    """
    ff.check_gating(ws, act)
    gated = len(ws) == 2
    M, K, H, N, E, bh, n_m, _ = _common_dims(x, ws, wo, dy, bm, bh)
    bh = _dw_block_h(bm, K, N, bh, len(ws), x.dtype.itemsize,
                     wo.dtype.itemsize)
    n_h = pl.cdiv(H, bh)

    in_specs = [pl.BlockSpec((bm, K), lambda j, i, g: (i, 0)),
                pl.BlockSpec((bm, N), lambda j, i, g: (i, 0))]
    in_specs += [_wi_spec(K, bh, lambda j, i, g: (g[i], 0, j))] * len(ws)
    in_specs += [pl.BlockSpec((1, bh, N), lambda j, i, g: (g[i], j, 0))]
    dwi_spec = _wi_spec(K, bh, lambda j, i, g: (g[i], 0, j))
    out_specs = [dwi_spec] * len(ws)
    out_specs += [pl.BlockSpec((1, bh, N), lambda j, i, g: (g[i], j, 0))]
    out_shape = [jax.ShapeDtypeStruct((E, K, H), jnp.float32)] * len(ws)
    out_shape += [jax.ShapeDtypeStruct((E, H, N), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_h, n_m),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
    )
    outs = pl.pallas_call(
        functools.partial(_dw_kernel, n_h=n_h, act=act, gated=gated,
                          h_tail=H % bh),
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(tile_group, x, dy, *ws, wo)
    return tuple(outs[:len(ws)]), outs[len(ws)]
