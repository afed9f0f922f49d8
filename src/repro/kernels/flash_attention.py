"""Pallas flash attention (TPU target) — the fix for the memory-bound
roofline pairs (EXPERIMENTS.md §Perf C): the (S, S) score tile never leaves
VMEM, so the HBM traffic XLA counts for the jnp blockwise scan disappears.

Grid (batch, q_head, q_tiles, kv_tiles) with the kv dim innermost and
sequential; online-softmax stats (m, l) and the output accumulator live in
VMEM scratch across kv steps.  GQA is handled by indexing the kv head as
q_head // (H // KV) in the BlockSpec index maps.  Causal + sliding-window
masking via block-local iota against absolute positions; the window rides in
as a scalar-prefetch arg so one compiled kernel serves every layer of a
mixed-window stack (Hymba).

Block sizes (bq, bk) default 128: VMEM working set =
bq*dk + 2*bk*dk + bq*bk + 2*bq*dv floats ~= 0.4 MiB at dk=dv=128 — far
inside the ~16 MiB budget; MXU dims all multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 128
DEFAULT_BK = 128
_NEG = -1e30


def _kernel(win_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_k: int, causal: bool, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32)  # (bq, dk)
    k = k_ref[0, :, 0, :].astype(jnp.float32)  # (bk, dk)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    i_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    j_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    dist = i_pos - j_pos
    mask = dist < win_ref[0]
    if causal:
        mask &= dist >= 0
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jax.lax.dot(p, v_ref[0, :, 0, :].astype(jnp.float32)))
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: jax.Array | int, causal: bool = True,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False) -> jax.Array:
    """softmax(q k^T / sqrt(dk)) v, fused.

    q: (B, S, H, dk); k, v: (B, Skv, KV, dk|dv) with H % KV == 0;
    S % bq == 0 and Skv % bk == 0 (callers pad; model seqs are powers of 2).
    window: int32 scalar — attend to 0 <= i - j < window (pass >= Skv for
    full attention).
    """
    B, S, H, dk = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // KV
    bq = min(bq, S)
    bk = min(bk, Skv)
    assert S % bq == 0 and Skv % bk == 0, (S, bq, Skv, bk)
    n_q, n_k = S // bq, Skv // bk

    win = jnp.asarray(window, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, 1, dk), lambda b, h, i, j, w: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, dk), lambda b, h, i, j, w: (b, j, h // G, 0)),
            pl.BlockSpec((1, bk, 1, dv), lambda b, h, i, j, w: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, dv), lambda b, h, i, j, w: (b, i, h, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),  # m
            pltpu.VMEM((bq,), jnp.float32),  # l
            pltpu.VMEM((bq, dv), jnp.float32),  # acc
        ],
    )
    kernel = functools.partial(_kernel, bq=bq, bk=bk, n_k=n_k, causal=causal,
                               scale=dk ** -0.5)

    def body(win, q, k, v):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, S, H, dv), q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
        )(win, q, k, v)

    return body(win, q, k, v)
