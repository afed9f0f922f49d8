"""Pallas grouped GEMM — the TPU-native FMoELinear (paper §3.1/§4, C2).

Computes ``y[i] = x[i] @ w[g(i)]`` for rows ``x`` sorted by group, with every
group's block padded to a multiple of the row tile ``bm`` (see
``repro.core.dispatch.pad_to_tiles``).  One kernel whose grid covers every
(group-row-tile × n-tile × k-tile) replaces FastMoE's CUDA multi-stream
concurrent expert execution: the MXU is time-shared by tiles instead of SMs
being shared by streams.

Tiling: grid (m_tiles, n_tiles, k_tiles), blocks x (bm, bk) / w (1, bk, bn) /
out (bm, bn), f32 accumulator in VMEM scratch; the expert id of each row tile
is scalar-prefetched so the right expert's weight tile streams HBM->VMEM.
VMEM working set = bm*bk + bk*bn + 2*bm*bn floats; defaults (128, 512, 512)
-> ~1.6 MiB, comfortably inside the ~16 MiB/core VMEM budget while keeping
all matmul dims multiples of the 128-lane MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



DEFAULT_BM = 128
DEFAULT_BK = 512
DEFAULT_BN = 512


def _kernel(tile_group_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """One (m, n, k) grid step: acc += x_tile @ w[g]_tile."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def grouped_gemm_tiled(x: jax.Array, w: jax.Array, tile_group: jax.Array, *,
                       bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                       bn: int = DEFAULT_BN, interpret: bool = False) -> jax.Array:
    """y = x @ w[tile_group[row_tile]] with tile-aligned groups.

    x: (M, K) with M % bm == 0 and rows of one group confined to whole tiles;
    w: (E, K, N); tile_group: (M // bm,) int32.
    """
    M, K = x.shape
    E, K2, N = w.shape
    assert K == K2 and M % bm == 0, (x.shape, w.shape, bm)
    bk = min(bk, K)
    bn = min(bn, N)
    n_m, n_n, n_k = M // bm, pl.cdiv(N, bn), pl.cdiv(K, bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, g: (i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, g: (g[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, g: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(tile_group, x, w)
