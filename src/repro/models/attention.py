"""Attention blocks: GQA (llama/qwen/starcoder-style) and MLA (DeepSeek-V2).

Full-sequence paths use a blockwise online-softmax ("flash"-pattern) scan over
KV chunks so the (S, S) score matrix is never materialized — required for the
prefill_32k shape where a dense 32k x 32k x heads score tensor would exceed
HBM.  Sliding windows are traced per-layer scalars so a scanned layer stack
can mix windowed and global layers (Hymba).

Decode paths run one query against a ring-buffer KV cache (absolute positions
stored alongside so RoPE is applied at write time and window/causal masks are
position-exact).  MLA decode uses the *absorbed* form: only the compressed
latent (kv_lora + rope_k) is cached and W_uk/W_uv are folded into the query /
output projections — the memory advantage that motivates MLA.  The latent is
RMS-normed before the up-projections (DeepSeek-V2's ``kv_a_layernorm``), so
the cache holds the normed latent, and YaRN (``AttentionConfig.yarn``) sets
the rope frequencies and the softmax scale.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.models.layers import (apply_norm, apply_rope, linear, linear_init,
                                 norm_init)

_NEG = -1e30


# ---------------------------------------------------------------------------
# Blockwise (flash-pattern) multi-head attention
# ---------------------------------------------------------------------------


# KV-chunk size for the online-softmax scan.  Overridable (e.g. the roofline
# layer-probe sets it to the full sequence so no inner while-loop hides
# attention FLOPs from XLA's trip-count-blind cost analysis).
DEFAULT_CHUNK = 1024
_CHUNK_OVERRIDE: list = [None]  # set via chunk_override() during tracing
# score dtype for the blockwise scan: f32 (default) or bf16 (§Perf —
# halves the dominant HBM term; m/l softmax stats stay f32)
SCORE_DTYPE: list = [jnp.float32]


def chunk_override(value):
    """Context manager: force the KV-chunk size while tracing/lowering."""
    return _list_override(_CHUNK_OVERRIDE, value)


def score_dtype(value):
    """Context manager: set the blockwise-attention score dtype (§Perf)."""
    return _list_override(SCORE_DTYPE, value)


def _list_override(cell, value):
    import contextlib

    @contextlib.contextmanager
    def _cm():
        old = cell[0]
        cell[0] = value
        try:
            yield
        finally:
            cell[0] = old
    return _cm()


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        window: jax.Array | int, q_offset: int = 0,
                        chunk: int | None = None, causal: bool = True,
                        scale: float | None = None,
                        recompute: bool = False) -> jax.Array:
    """softmax(scale q k^T) v with online softmax over KV chunks.

    q: (B, Sq, H, dk); k: (B, Skv, KV, dk); v: (B, Skv, KV, dv).
    window: scalar — attend only to keys with 0 <= i - j < window (i absolute
    query pos = q_offset + row).  Pass Skv (or larger) for full attention.
    scale: the softmax scale, dk ** -0.5 by default.
    recompute: the backward pass recomputes each chunk's scores instead of
    keeping them (the model's ``remat == "full"``): a scan saves its body's
    residuals per step, which would materialize the full (Sq, Skv) scores
    and probabilities.
    """
    B, Sq, H, dk = q.shape
    Skv_real, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // KV
    chunk = min(chunk or _CHUNK_OVERRIDE[0] or DEFAULT_CHUNK, Skv_real)
    n_pad = (-Skv_real) % chunk
    if n_pad:  # pad keys to a chunk multiple; padded slots masked out below
        pad = [(0, 0), (0, n_pad), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    Skv = Skv_real + n_pad
    n_chunks = Skv // chunk

    sdt = SCORE_DTYPE[0]
    neg = _NEG if sdt == jnp.float32 else -3e38  # bf16 max ~3.39e38
    qg = q.reshape(B, Sq, KV, G, dk).astype(sdt)
    scale = dk ** -0.5 if scale is None else scale
    i_pos = q_offset + jnp.arange(Sq)  # absolute query positions
    window = jnp.asarray(window, jnp.int32)

    kc = k.reshape(B, n_chunks, chunk, KV, dk)
    vc = v.reshape(B, n_chunks, chunk, KV, dv)

    def body(carry, inp):
        m, l, acc = carry
        kj, vj, j0 = inp
        s = jnp.einsum("bskgd,bckd->bskgc", qg, kj.astype(sdt)) * sdt(scale)
        j_pos = j0 + jnp.arange(chunk)
        dist = i_pos[:, None] - j_pos[None, :]  # (Sq, chunk)
        mask = (dist < window) & (j_pos < Skv_real)[None, :]
        if causal:
            mask &= (dist >= 0)
        s = jnp.where(mask[None, :, None, None, :], s, sdt(neg))
        m_new = jnp.maximum(m, s.max(-1).astype(jnp.float32))
        p = jnp.exp(s.astype(jnp.float32) - m_new[..., None]).astype(sdt)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, dtype=jnp.float32)
        acc = acc * corr[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", p, vj.astype(sdt),
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    if recompute and n_chunks > 1:
        body = jax.checkpoint(body, prevent_cse=False)
    m0 = jnp.full((B, Sq, KV, G), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, dv), jnp.float32)
    js = jnp.arange(n_chunks) * chunk
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), js))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, dv).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_positions: jax.Array, pos: jax.Array,
                     window: jax.Array | int) -> jax.Array:
    """One-token attention against a ring-buffer cache.

    q: (B, 1, H, dk); caches (B, W, KV, d*); kv_positions (B, W) absolute
    positions of cached entries (-1 = empty); pos: scalar or (B, 1)
    per-sequence current positions.
    """
    B, _, H, dk = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, dk).astype(jnp.float32)
    s = jnp.einsum("bkgd,bwkd->bkgw", qg, k_cache.astype(jnp.float32)) * dk ** -0.5
    dist = pos - kv_positions  # (B, W)
    valid = (kv_positions >= 0) & (dist >= 0) & (dist < jnp.asarray(window, jnp.int32))
    s = jnp.where(valid[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgw,bwkd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array  # (B, W, KV, dk)
    v: jax.Array  # (B, W, KV, dv)
    positions: jax.Array  # (B, W) absolute positions, -1 empty


def gqa_init(rng: jax.Array, d_model: int, cfg: AttentionConfig, dtype) -> dict:
    ks = jax.random.split(rng, 4)
    return {
        "wq": linear_init(ks[0], d_model, cfg.num_heads * cfg.head_dim, dtype, cfg.qkv_bias),
        "wk": linear_init(ks[1], d_model, cfg.num_kv_heads * cfg.head_dim, dtype, cfg.qkv_bias),
        "wv": linear_init(ks[2], d_model, cfg.num_kv_heads * cfg.head_dim, dtype, cfg.qkv_bias),
        "wo": linear_init(ks[3], cfg.num_heads * cfg.head_dim, d_model, dtype),
    }


def gqa_apply(params: dict, x: jax.Array, cfg: AttentionConfig, *,
              window: jax.Array | int, positions: Optional[jax.Array] = None,
              kv_x: Optional[jax.Array] = None, causal: bool = True,
              return_kv: bool = False, recompute: bool = False):
    """Full-sequence GQA.  kv_x (cross-attention source) defaults to x.
    ``return_kv`` additionally returns the (post-RoPE) k, v for prefill
    cache population; ``recompute`` as in :func:`blockwise_attention`."""
    B, S, _ = x.shape
    src = x if kv_x is None else kv_x
    Skv = src.shape[1]
    q = linear(params["wq"], x).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = linear(params["wk"], src).reshape(B, Skv, cfg.num_kv_heads, cfg.head_dim)
    v = linear(params["wv"], src).reshape(B, Skv, cfg.num_kv_heads, cfg.head_dim)
    if causal:  # self-attention: rotate q and k
        pos = jnp.arange(S) if positions is None else positions
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, jnp.arange(Skv), cfg.rope_theta)
    out = blockwise_attention(q, k, v, window=window, causal=causal,
                              recompute=recompute)
    y = linear(params["wo"], out.reshape(B, S, -1))
    if return_kv:
        return y, (k, v)
    return y


def _per_seq_pos(pos: jax.Array, B: int) -> jax.Array:
    """Normalize pos to (B,): scalars broadcast (continuous batching passes a
    per-sequence position vector)."""
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos


def gqa_decode(params: dict, x: jax.Array, cache: KVCache, pos: jax.Array,
               cfg: AttentionConfig, *, window: jax.Array | int) -> tuple:
    """One-token decode; writes (k, v, pos) into each sequence's ring slot
    pos[b] % W.  ``pos``: scalar or (B,) per-sequence positions."""
    B, _, _ = x.shape
    W = cache.k.shape[1]
    posb = _per_seq_pos(pos, B)  # (B,)
    q = linear(params["wq"], x).reshape(B, 1, cfg.num_heads, cfg.head_dim)
    k = linear(params["wk"], x).reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
    v = linear(params["wv"], x).reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, posb[:, None], cfg.rope_theta)
    k = apply_rope(k, posb[:, None], cfg.rope_theta)
    slots = posb % W
    bidx = jnp.arange(B)
    new_cache = KVCache(
        cache.k.at[bidx, slots].set(k[:, 0].astype(cache.k.dtype)),
        cache.v.at[bidx, slots].set(v[:, 0].astype(cache.v.dtype)),
        cache.positions.at[bidx, slots].set(posb),
    )
    out = decode_attention(q, new_cache.k, new_cache.v, new_cache.positions,
                           posb[:, None], window)
    return linear(params["wo"], out.reshape(B, 1, -1)), new_cache


def fill_kv_cache(cache: KVCache, k: jax.Array, v: jax.Array, *,
                  start: int = 0) -> KVCache:
    """Prefill: write S (post-RoPE) rows into the ring starting at absolute
    position ``start``; only the last W survive if S exceeds the ring."""
    B, S = k.shape[:2]
    W = cache.k.shape[1]
    tail = max(0, S - W)
    pos_abs = start + jnp.arange(tail, S)
    slots = pos_abs % W
    return KVCache(
        cache.k.at[:, slots].set(k[:, tail:].astype(cache.k.dtype)),
        cache.v.at[:, slots].set(v[:, tail:].astype(cache.v.dtype)),
        cache.positions.at[:, slots].set(
            jnp.broadcast_to(pos_abs, (B, S - tail)).astype(jnp.int32)),
    )


def fill_mla_cache(cache: MLACache, ckv: jax.Array, kr: jax.Array, *,
                   start: int = 0) -> MLACache:
    """Prefill the compressed-latent cache (ckv (B,S,lora), the normed latent
    ``mla_apply(return_kv=True)`` returns; kr (B,S,rope), rotated)."""
    B, S = ckv.shape[:2]
    W = cache.ckv.shape[1]
    tail = max(0, S - W)
    pos_abs = start + jnp.arange(tail, S)
    slots = pos_abs % W
    return MLACache(
        cache.ckv.at[:, slots].set(ckv[:, tail:].astype(cache.ckv.dtype)),
        cache.kr.at[:, slots].set(kr[:, tail:].astype(cache.kr.dtype)),
        cache.positions.at[:, slots].set(
            jnp.broadcast_to(pos_abs, (B, S - tail)).astype(jnp.int32)),
    )


def gqa_init_cache(batch: int, max_len: int, cfg: AttentionConfig, dtype) -> KVCache:
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.full((batch, max_len), -1, jnp.int32))


# ---------------------------------------------------------------------------
# Paged (blocked) KV cache — continuous-batching serving
# ---------------------------------------------------------------------------
#
# The pool replaces the per-slot ring with shared physical blocks of
# ``block_size`` rows; each decode slot owns a block *table* mapping its
# logical block j (positions [j*bs, (j+1)*bs)) to a pool row, so
# heterogeneous sequence lengths never fragment a contiguous ring.  Entry
# order inside the gathered per-slot view equals the absolute position
# ((p // bs) * bs + p % bs == p), and unallocated/stale entries carry
# position -1, so decode_attention masks them to an exact-zero softmax
# weight — the paged read is **bitwise identical** to a ring cache of length
# blocks_per_slot * block_size (tests/test_scheduler.py locks this).
#
# Pool row 0 is the permanent null block (never written; -1 positions) that
# unallocated table entries point at; row 1 is the scratch block that
# absorbs writes from inactive slots (table rows all-null), so a fixed-width
# decode batch can tick with empty slots without corrupting shared state.

NULL_BLOCK = 0
SCRATCH_BLOCK = 1
RESERVED_BLOCKS = 2


class PagedKVCache(NamedTuple):
    k: jax.Array  # (P, bs, KV, dk) shared block pool
    v: jax.Array  # (P, bs, KV, dv)
    positions: jax.Array  # (P, bs) absolute positions, -1 empty


class PagedMLACache(NamedTuple):
    ckv: jax.Array  # (P, bs, kv_lora)
    kr: jax.Array  # (P, bs, qk_rope)
    positions: jax.Array  # (P, bs)


def gqa_init_paged(num_blocks: int, block_size: int, cfg: AttentionConfig,
                   dtype) -> PagedKVCache:
    shape = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                        jnp.full((num_blocks, block_size), -1, jnp.int32))


def mla_init_paged(num_blocks: int, block_size: int, cfg: AttentionConfig,
                   dtype) -> PagedMLACache:
    return PagedMLACache(
        jnp.zeros((num_blocks, block_size, cfg.kv_lora_rank), dtype),
        jnp.zeros((num_blocks, block_size, cfg.qk_rope_head_dim), dtype),
        jnp.full((num_blocks, block_size), -1, jnp.int32))


def _paged_target(tables: jax.Array, posb: jax.Array, bs: int):
    """(pb, off): write target per slot.  Null-block entries (inactive or
    out-of-table positions) redirect to the scratch block."""
    nb = tables.shape[1]
    blk = jnp.clip(posb // bs, 0, nb - 1)
    pb = tables[jnp.arange(posb.shape[0]), blk]
    pb = jnp.where(pb == NULL_BLOCK, SCRATCH_BLOCK, pb)
    return pb, posb % bs


def _paged_view(pool_leaf: jax.Array, tables: jax.Array) -> jax.Array:
    """Gather a per-slot contiguous view: (P, bs, ...) x (B, nb) ->
    (B, nb*bs, ...).  Entry index == absolute position."""
    g = jnp.take(pool_leaf, tables, axis=0)  # (B, nb, bs, ...)
    B, nb, bs = g.shape[:3]
    return g.reshape(B, nb * bs, *g.shape[3:])


def gqa_decode_paged(params: dict, x: jax.Array, cache: PagedKVCache,
                     tables: jax.Array, pos: jax.Array, cfg: AttentionConfig,
                     *, window: jax.Array | int) -> tuple:
    """One-token decode against the shared block pool.  ``tables`` (B, nb)
    int32 maps each slot's logical blocks to pool rows (0 = unallocated)."""
    B = x.shape[0]
    bs = cache.k.shape[1]
    posb = _per_seq_pos(pos, B)
    q = linear(params["wq"], x).reshape(B, 1, cfg.num_heads, cfg.head_dim)
    k = linear(params["wk"], x).reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
    v = linear(params["wv"], x).reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, posb[:, None], cfg.rope_theta)
    k = apply_rope(k, posb[:, None], cfg.rope_theta)
    pb, off = _paged_target(tables, posb, bs)
    new_cache = PagedKVCache(
        cache.k.at[pb, off].set(k[:, 0].astype(cache.k.dtype)),
        cache.v.at[pb, off].set(v[:, 0].astype(cache.v.dtype)),
        cache.positions.at[pb, off].set(posb),
    )
    out = decode_attention(q, _paged_view(new_cache.k, tables),
                           _paged_view(new_cache.v, tables),
                           _paged_view(new_cache.positions, tables),
                           posb[:, None], window)
    return linear(params["wo"], out.reshape(B, 1, -1)), new_cache


def mla_decode_paged(params: dict, x: jax.Array, cache: PagedMLACache,
                     tables: jax.Array, pos: jax.Array, cfg: AttentionConfig,
                     *, window: jax.Array | int) -> tuple:
    """Absorbed-form MLA decode against the shared latent block pool."""
    B = x.shape[0]
    H = cfg.num_heads
    bs = cache.ckv.shape[1]
    posb = _per_seq_pos(pos, B)
    q_nope, q_rope = _mla_q(params, x, cfg)
    q_rope = apply_rope(q_rope, posb[:, None], cfg.rope_theta, cfg.yarn)
    ckv, kr = _mla_latent(params, x, posb[:, None], cfg)
    ckv, kr = ckv[:, 0], kr[:, 0, 0]  # (B, lora), (B, rope)

    pb, off = _paged_target(tables, posb, bs)
    new_cache = PagedMLACache(
        cache.ckv.at[pb, off].set(ckv.astype(cache.ckv.dtype)),
        cache.kr.at[pb, off].set(kr.astype(cache.kr.dtype)),
        cache.positions.at[pb, off].set(posb),
    )
    ckv_v = _paged_view(new_cache.ckv, tables)  # (B, nb*bs, lora)
    kr_v = _paged_view(new_cache.kr, tables)
    pos_v = _paged_view(new_cache.positions, tables)

    q_eff = jnp.einsum("bhd,hrd->bhr", q_nope[:, 0].astype(jnp.float32),
                       params["w_uk"].astype(jnp.float32))
    s = jnp.einsum("bhr,bwr->bhw", q_eff, ckv_v.astype(jnp.float32))
    s += jnp.einsum("bhd,bwd->bhw", q_rope[:, 0].astype(jnp.float32),
                    kr_v.astype(jnp.float32))
    s *= _mla_scale(cfg)
    dist = posb[:, None] - pos_v
    valid = (pos_v >= 0) & (dist >= 0) & (dist < jnp.asarray(window, jnp.int32))
    s = jnp.where(valid[:, None, :], s, _NEG)
    p = jax.nn.softmax(s, -1)
    o_lat = jnp.einsum("bhw,bwr->bhr", p, ckv_v.astype(jnp.float32))
    out = jnp.einsum("bhr,hrd->bhd", o_lat, params["w_uv"].astype(jnp.float32))
    out = out.reshape(B, 1, H * cfg.v_head_dim).astype(x.dtype)
    return linear(params["wo"], out), new_cache


# ---------------------------------------------------------------------------
# MLA block (DeepSeek-V2)
# ---------------------------------------------------------------------------


class MLACache(NamedTuple):
    ckv: jax.Array  # (B, W, kv_lora) compressed latent
    kr: jax.Array  # (B, W, qk_rope) decoupled rope key (shared across heads)
    positions: jax.Array  # (B, W)


def mla_init(rng: jax.Array, d_model: int, cfg: AttentionConfig, dtype) -> dict:
    ks = jax.random.split(rng, 7)
    H = cfg.num_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {
        "w_dkv": linear_init(ks[0], d_model, cfg.kv_lora_rank, dtype),
        "w_kr": linear_init(ks[1], d_model, cfg.qk_rope_head_dim, dtype),
        # per-head up-projections from the latent
        "w_uk": (jax.random.normal(ks[2], (H, cfg.kv_lora_rank, cfg.qk_nope_head_dim))
                 * cfg.kv_lora_rank ** -0.5).astype(dtype),
        "w_uv": (jax.random.normal(ks[3], (H, cfg.kv_lora_rank, cfg.v_head_dim))
                 * cfg.kv_lora_rank ** -0.5).astype(dtype),
        "wo": linear_init(ks[4], H * cfg.v_head_dim, d_model, dtype),
        # RMSNorm on the compressed latent (kv_a_layernorm)
        "kv_norm": norm_init(cfg.kv_lora_rank, "rmsnorm"),
    }
    if cfg.q_lora_rank:
        p["w_dq"] = linear_init(ks[5], d_model, cfg.q_lora_rank, dtype)
        p["w_uq"] = linear_init(ks[6], cfg.q_lora_rank, H * qd, dtype)
    else:
        p["w_q"] = linear_init(ks[5], d_model, H * qd, dtype)
    return p


def _mla_q(params: dict, x: jax.Array, cfg: AttentionConfig):
    B, S, _ = x.shape
    H = cfg.num_heads
    if "w_dq" in params:
        q = linear(params["w_uq"], linear(params["w_dq"], x))
    else:
        q = linear(params["w_q"], x)
    q = q.reshape(B, S, H, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    return jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)  # q_nope, q_rope


def _mla_latent(params: dict, x: jax.Array, positions: jax.Array,
                cfg: AttentionConfig):
    """(ckv (B, S, lora), kr (B, S, 1, rope)): the normed compressed latent
    and the rotated decoupled rope key — what the cache holds."""
    B, S, _ = x.shape
    ckv = apply_norm(params["kv_norm"], linear(params["w_dkv"], x), "rmsnorm")
    kr = linear(params["w_kr"], x).reshape(B, S, 1, cfg.qk_rope_head_dim)
    return ckv, apply_rope(kr, positions, cfg.rope_theta, cfg.yarn)


def _mla_scale(cfg: AttentionConfig) -> float:
    """Softmax scale over the (nope + rope)-wide query-key product."""
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * cfg.softmax_scale_factor)


def mla_apply(params: dict, x: jax.Array, cfg: AttentionConfig, *,
              window: jax.Array | int,
              positions: Optional[jax.Array] = None,
              return_kv: bool = False, recompute: bool = False):
    """Training/prefill MLA: materialize per-head K/V from the latent and run
    blockwise attention on concat(nope, rope) keys.  ``return_kv`` returns
    the compressed (ckv, kr) latents for prefill cache population;
    ``recompute`` as in :func:`blockwise_attention`."""
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(params, x, cfg)
    pos = jnp.arange(S) if positions is None else positions
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta, cfg.yarn)

    ckv, kr = _mla_latent(params, x, jnp.arange(S), cfg)
    k_nope = jnp.einsum("bsr,hrd->bshd", ckv, params["w_uk"])
    v = jnp.einsum("bsr,hrd->bshd", ckv, params["w_uv"])

    q = jnp.concatenate([q_nope, q_rope], -1)  # (B,S,H,nope+rope)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr, (B, S, H, cfg.qk_rope_head_dim))], -1)
    out = blockwise_attention(q, k, v, window=window, scale=_mla_scale(cfg),
                              recompute=recompute)
    y = linear(params["wo"], out.reshape(B, S, -1))
    if return_kv:
        return y, (ckv, kr[:, :, 0, :])
    return y


def mla_decode(params: dict, x: jax.Array, cache: MLACache, pos: jax.Array,
               cfg: AttentionConfig, *, window: jax.Array | int) -> tuple:
    """Absorbed-form decode: score latents directly, cache only (ckv, kr).
    ``pos``: scalar or (B,) per-sequence positions."""
    B = x.shape[0]
    H = cfg.num_heads
    W = cache.ckv.shape[1]
    posb = _per_seq_pos(pos, B)
    q_nope, q_rope = _mla_q(params, x, cfg)  # (B,1,H,*)
    q_rope = apply_rope(q_rope, posb[:, None], cfg.rope_theta, cfg.yarn)
    ckv, kr = _mla_latent(params, x, posb[:, None], cfg)
    ckv, kr = ckv[:, 0], kr[:, 0, 0]  # (B, lora), (B, rope)

    slots = posb % W
    bidx = jnp.arange(B)
    cache = MLACache(cache.ckv.at[bidx, slots].set(ckv.astype(cache.ckv.dtype)),
                     cache.kr.at[bidx, slots].set(kr.astype(cache.kr.dtype)),
                     cache.positions.at[bidx, slots].set(posb))

    # absorb W_uk into q: q_eff (B,H,lora) scores against cached latents
    q_eff = jnp.einsum("bhd,hrd->bhr", q_nope[:, 0].astype(jnp.float32),
                       params["w_uk"].astype(jnp.float32))
    s = jnp.einsum("bhr,bwr->bhw", q_eff, cache.ckv.astype(jnp.float32))
    s += jnp.einsum("bhd,bwd->bhw", q_rope[:, 0].astype(jnp.float32),
                    cache.kr.astype(jnp.float32))
    s *= _mla_scale(cfg)
    dist = posb[:, None] - cache.positions
    valid = (cache.positions >= 0) & (dist >= 0) & (dist < jnp.asarray(window, jnp.int32))
    s = jnp.where(valid[:, None, :], s, _NEG)
    p = jax.nn.softmax(s, -1)
    o_lat = jnp.einsum("bhw,bwr->bhr", p, cache.ckv.astype(jnp.float32))
    out = jnp.einsum("bhr,hrd->bhd", o_lat, params["w_uv"].astype(jnp.float32))
    out = out.reshape(B, 1, H * cfg.v_head_dim).astype(x.dtype)
    return linear(params["wo"], out), cache


def mla_init_cache(batch: int, max_len: int, cfg: AttentionConfig, dtype) -> MLACache:
    return MLACache(jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
                    jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
                    jnp.full((batch, max_len), -1, jnp.int32))
