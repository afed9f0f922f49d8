"""Per-layer blocks for every assigned family, with a uniform interface so the
layer stack can be jax.lax.scan'ed (homogeneous params + per-layer window
scalars) and jax.remat'ed.

Families:
  dense / moe / vlm / audio-decoder : [norm -> attn -> norm -> ffn/moe]
  ssm (rwkv6)                       : [norm -> time_mix -> norm -> channel_mix|moe]
  hybrid (hymba)                    : [norm -> (attn || mamba) fused -> norm -> ffn/moe]
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.balance import MoEMetrics
from repro.core.fmoe import DistConfig, _ffn_init, dense_ffn, fmoe_apply, fmoe_init
from repro.models import attention as A
from repro.models import mamba as M
from repro.models import rwkv6 as R
from repro.models.layers import apply_norm, norm_init
from repro.obs.scopes import ATTENTION, scoped

FULL_WINDOW = 1 << 30  # "no window" sentinel (larger than any seq len)


def layer_windows(cfg: ModelConfig) -> jax.Array:
    """(L,) per-layer attention window (FULL_WINDOW for global layers)."""
    a = cfg.attention
    L = cfg.num_layers
    if a is None or a.sliding_window is None:
        return jnp.full((L,), FULL_WINDOW, jnp.int32)
    w = jnp.full((L,), a.sliding_window, jnp.int32)
    for g in a.global_layers:
        w = w.at[g].set(FULL_WINDOW)
    return w


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _ffn_block_init(rng: jax.Array, cfg: ModelConfig, dtype) -> dict:
    if cfg.moe is not None:
        return fmoe_init(rng, cfg.d_model, cfg.moe, act=cfg.act,
                         d_ff_dense=cfg.d_ff, dtype=dtype)
    return _ffn_init(rng, 0, cfg.d_model, cfg.d_ff, cfg.act, dtype)


def layer_init(rng: jax.Array, cfg: ModelConfig, *, cross: bool = False) -> dict:
    """One decoder layer.  ``cross=True`` adds cross-attention (whisper dec)."""
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 6)
    d = cfg.d_model
    p: dict = {"norm1": norm_init(d, cfg.norm), "norm2": norm_init(d, cfg.norm)}
    if cfg.family == "ssm":
        p["rwkv"] = R.rwkv_init(ks[0], cfg, dtype)
        if cfg.moe is not None:  # fmoefy'd rwkv: MoE replaces channel-mix
            p["ffn"] = _ffn_block_init(ks[1], cfg, dtype)
        return p
    a = cfg.attention
    init_attn = A.mla_init if a.kind == "mla" else A.gqa_init
    p["attn"] = init_attn(ks[0], d, a, dtype)
    if cfg.family == "hybrid":
        p["mamba"] = M.mamba_init(ks[1], d, cfg.ssm, dtype)
        p["norm_a"] = norm_init(d, cfg.norm)
        p["norm_m"] = norm_init(d, cfg.norm)
    if cross:
        p["norm_cross"] = norm_init(d, cfg.norm)
        p["cross_attn"] = A.gqa_init(ks[2], d, a, dtype)
    p["ffn"] = _ffn_block_init(ks[3], cfg, dtype)
    return p


# ---------------------------------------------------------------------------
# FFN / mixer application
# ---------------------------------------------------------------------------


def _apply_ffn(p: dict, cfg: ModelConfig, x: jax.Array,
               dist: Optional[DistConfig], impl: str = "einsum", l2p=None,
               rng=None):
    """``l2p``: this layer's logical->physical gate-id table, scanned out of
    a stacked per-layer placement by models/lm.py (None = shared/no plan).
    ``rng``: optional per-layer gate key (exploration routers: noisy_topk /
    gumbel); None keeps every router deterministic."""
    if cfg.moe is not None:
        return fmoe_apply(p, x, cfg.moe, act=cfg.act, dist=dist, impl=impl,
                          l2p=l2p, rng=rng)
    return dense_ffn(p, x, cfg.act), None


@scoped(ATTENTION)
def _mixer_seq(p: dict, cfg: ModelConfig, x: jax.Array, window,
               state: Optional[Any]):
    """Token mixer over a full sequence.  Returns (y, new_state)."""
    if cfg.family == "ssm":
        return R.time_mix(p["rwkv"], x, state, cfg)
    a = cfg.attention
    rc = cfg.remat == "full"  # the backward recomputes each chunk's scores
    if cfg.family == "hybrid":
        y_a = A.gqa_apply(p["attn"], x, a, window=window, recompute=rc)
        y_m, mstate = M.mamba_apply(p["mamba"], x, state, cfg.ssm)
        y = 0.5 * (apply_norm(p["norm_a"], y_a, cfg.norm)
                   + apply_norm(p["norm_m"], y_m, cfg.norm))
        return y, mstate
    if a.kind == "mla":
        return A.mla_apply(p["attn"], x, a, window=window, recompute=rc), None
    return A.gqa_apply(p["attn"], x, a, window=window, recompute=rc), None


@scoped(ATTENTION)
def _mixer_decode(p: dict, cfg: ModelConfig, x: jax.Array, cache, pos, window,
                  block_tables=None):
    if cfg.family == "ssm":
        # single-step time-mix via the seq path with S=1 and the cached shift
        y, new_state = R.time_mix(p["rwkv"], x, cache, cfg)
        return y, new_state
    a = cfg.attention
    if cfg.family == "hybrid":
        y_a, kv = A.gqa_decode(p["attn"], x, cache["attn"], pos, a, window=window)
        y_m, ms = M.mamba_apply(p["mamba"], x, cache["mamba"], cfg.ssm)
        y = 0.5 * (apply_norm(p["norm_a"], y_a, cfg.norm)
                   + apply_norm(p["norm_m"], y_m, cfg.norm))
        return y, {"attn": kv, "mamba": ms}
    if block_tables is not None:  # paged/blocked pool (continuous batching)
        if a.kind == "mla":
            return A.mla_decode_paged(p["attn"], x, cache, block_tables, pos,
                                      a, window=window)
        return A.gqa_decode_paged(p["attn"], x, cache, block_tables, pos,
                                  a, window=window)
    if a.kind == "mla":
        return A.mla_decode(p["attn"], x, cache, pos, a, window=window)
    return A.gqa_decode(p["attn"], x, cache, pos, a, window=window)


# ---------------------------------------------------------------------------
# Full-sequence layer (train / prefill)
# ---------------------------------------------------------------------------


def _constrain_attn_batch(x: jax.Array, dist: Optional[DistConfig]):
    """§Perf: when attention weights are replicated over the model axis
    (head-count not divisible), shard the attention *batch* over every mesh
    axis instead — scores shrink by the model-axis size for the price of two
    small activation reshards."""
    if dist is None or not dist.constrain_tokens:
        return x
    total = 1
    for a in dist.token_axes:
        total *= dist.mesh.shape[a]
    if not dist.token_axes or x.shape[0] % total:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(dist.mesh, P(dist.token_axes, None, None)))


def layer_apply_seq(p: dict, cfg: ModelConfig, x: jax.Array, *, window,
                    dist: Optional[DistConfig] = None,
                    enc_out: Optional[jax.Array] = None,
                    mixer_state: Optional[Any] = None,
                    impl: str = "einsum", l2p=None, rng=None):
    """x (B, S, d) -> (x, MoEMetrics|None).  mixer_state: SSM initial state
    (zeros created by the caller for ssm/hybrid families)."""
    xn = apply_norm(p["norm1"], x, cfg.norm)
    if cfg.family not in ("ssm", "hybrid"):
        xn = _constrain_attn_batch(xn, dist)
    h, _ = _mixer_seq(p, cfg, xn, window, mixer_state)
    x = x + h
    if enc_out is not None:  # whisper decoder cross-attention
        h = A.gqa_apply(p["cross_attn"], apply_norm(p["norm_cross"], x, cfg.norm),
                        cfg.attention, window=FULL_WINDOW, kv_x=enc_out,
                        causal=False)
        x = x + h
    if cfg.family == "ssm" and cfg.moe is None:
        h, _ = R.channel_mix(p["rwkv"], apply_norm(p["norm2"], x, cfg.norm),
                             mixer_state)
        metrics = None
    else:
        h, metrics = _apply_ffn(p.get("ffn"), cfg, apply_norm(p["norm2"], x, cfg.norm), dist,
                                impl, l2p, rng)
    return x + h, metrics


# ---------------------------------------------------------------------------
# Prefill layer: full-sequence forward that also populates the decode cache
# ---------------------------------------------------------------------------


def layer_apply_prefill(p: dict, cfg: ModelConfig, x: jax.Array, cache, *,
                        window, dist: Optional[DistConfig] = None,
                        start: int = 0, impl: str = "einsum", l2p=None):
    """x (B, S, d), per-layer cache -> (x, filled_cache, MoEMetrics|None).

    One full-sequence pass writes every position's K/V (or recurrent state)
    into the cache so decoding can continue at position S — O(1) model
    passes for the prompt instead of S decode steps."""
    xn = apply_norm(p["norm1"], x, cfg.norm)
    a = cfg.attention

    if cfg.family == "ssm":
        h, c1 = R.time_mix(p["rwkv"], xn, cache, cfg)
        x = x + h
        xn2 = apply_norm(p["norm2"], x, cfg.norm)
        if cfg.moe is None:
            h, c2 = R.channel_mix(p["rwkv"], xn2, c1)
            return x + h, c2, None
        h, metrics = _apply_ffn(p["ffn"], cfg, xn2, dist, impl, l2p)
        return x + h, c1, metrics

    if cfg.family == "hybrid":
        y_a, (k, v) = A.gqa_apply(p["attn"], xn, a, window=window,
                                  return_kv=True)
        kv = A.fill_kv_cache(cache["attn"], k, v, start=start)
        y_m, ms = M.mamba_apply(p["mamba"], xn, cache["mamba"], cfg.ssm)
        h = 0.5 * (apply_norm(p["norm_a"], y_a, cfg.norm)
                   + apply_norm(p["norm_m"], y_m, cfg.norm))
        x = x + h
        new_cache = {"attn": kv, "mamba": ms}
    elif cfg.family == "audio":
        h, (k, v) = A.gqa_apply(p["attn"], xn, a, window=window,
                                return_kv=True)
        x = x + h
        q = apply_norm(p["norm_cross"], x, cfg.norm)
        h = A.gqa_apply(p["cross_attn"], q, a, window=FULL_WINDOW,
                        kv_x=cache["enc_out"], causal=False)
        x = x + h
        new_cache = {"self": A.fill_kv_cache(cache["self"], k, v, start=start),
                     "enc_out": cache["enc_out"]}
    elif a.kind == "mla":
        h, (ckv, kr) = A.mla_apply(p["attn"], xn, a, window=window,
                                   return_kv=True)
        x = x + h
        new_cache = A.fill_mla_cache(cache, ckv, kr, start=start)
    else:
        h, (k, v) = A.gqa_apply(p["attn"], xn, a, window=window,
                                return_kv=True)
        x = x + h
        new_cache = A.fill_kv_cache(cache, k, v, start=start)

    h, metrics = _apply_ffn(p["ffn"], cfg, apply_norm(p["norm2"], x, cfg.norm),
                            dist, impl, l2p)
    return x + h, new_cache, metrics


# ---------------------------------------------------------------------------
# One-token decode layer
# ---------------------------------------------------------------------------


def layer_apply_decode(p: dict, cfg: ModelConfig, x: jax.Array, cache, pos, *,
                       window, dist: Optional[DistConfig] = None,
                       impl: str = "einsum", l2p=None, block_tables=None):
    """x (B, 1, d), per-layer cache -> (x, new_cache, MoEMetrics|None).

    ``block_tables`` (B, nb) switches the attention cache to the paged block
    pool (models/attention paged decode) — plain attention families only;
    recurrent-state caches (ssm/hybrid) and the audio enc-out dict keep the
    contiguous per-slot layout."""
    if block_tables is not None and cfg.family in ("ssm", "hybrid", "audio"):
        raise NotImplementedError(
            f"paged KV cache is not supported for family {cfg.family!r}")
    if cfg.family == "ssm":
        h, c1 = R.time_mix(p["rwkv"], apply_norm(p["norm1"], x, cfg.norm), cache, cfg)
        x = x + h
        if cfg.moe is None:
            h, c2 = R.channel_mix(p["rwkv"], apply_norm(p["norm2"], x, cfg.norm), c1)
            return x + h, c2, None
        h, metrics = _apply_ffn(p["ffn"], cfg, apply_norm(p["norm2"], x, cfg.norm), dist,
                                impl, l2p)
        return x + h, c1, metrics

    attn_cache = cache["attn"] if isinstance(cache, dict) and "attn" in cache \
        and cfg.family != "hybrid" else cache
    if cfg.family == "audio":
        h, kv = A.gqa_decode(p["attn"], apply_norm(p["norm1"], x, cfg.norm),
                             cache["self"], pos, cfg.attention, window=window)
        x = x + h
        # cross attention against precomputed encoder K/V
        q = apply_norm(p["norm_cross"], x, cfg.norm)
        h = A.gqa_apply(p["cross_attn"], q, cfg.attention, window=FULL_WINDOW,
                        kv_x=cache["enc_out"], causal=False)
        x = x + h
        h, metrics = _apply_ffn(p["ffn"], cfg, apply_norm(p["norm2"], x, cfg.norm), dist,
                                impl, l2p)
        return x + h, {"self": kv, "enc_out": cache["enc_out"]}, metrics

    h, new_cache = _mixer_decode(p, cfg, apply_norm(p["norm1"], x, cfg.norm),
                                 attn_cache, pos, window,
                                 block_tables=block_tables)
    x = x + h
    h, metrics = _apply_ffn(p["ffn"], cfg, apply_norm(p["norm2"], x, cfg.norm), dist,
                            impl, l2p)
    return x + h, new_cache, metrics


# ---------------------------------------------------------------------------
# Per-layer cache/state construction
# ---------------------------------------------------------------------------


def layer_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype,
                enc_out: Optional[jax.Array] = None):
    a = cfg.attention
    if cfg.family == "ssm":
        return R.rwkv_init_state(batch, cfg, dtype)
    if cfg.family == "hybrid":
        return {"attn": A.gqa_init_cache(batch, cache_len, a, dtype),
                "mamba": M.mamba_init_state(batch, cfg.d_model, cfg.ssm, dtype)}
    if cfg.family == "audio":
        return {"self": A.gqa_init_cache(batch, cache_len, a, dtype),
                "enc_out": enc_out if enc_out is not None else jnp.zeros(
                    (batch, cfg.encoder.num_frames, cfg.d_model), dtype)}
    if a is not None and a.kind == "mla":
        return A.mla_init_cache(batch, cache_len, a, dtype)
    return A.gqa_init_cache(batch, cache_len, a, dtype)


def layer_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                      dtype):
    """Per-layer paged block pool (plain attention families only)."""
    a = cfg.attention
    if cfg.family in ("ssm", "hybrid", "audio") or a is None:
        raise NotImplementedError(
            f"paged KV cache is not supported for family {cfg.family!r}")
    if a.kind == "mla":
        return A.mla_init_paged(num_blocks, block_size, a, dtype)
    return A.gqa_init_paged(num_blocks, block_size, a, dtype)


def mixer_state(cfg: ModelConfig, batch: int, dtype):
    """Zero SSM state for full-sequence processing (ssm / hybrid)."""
    if cfg.family == "ssm":
        return R.rwkv_init_state(batch, cfg, dtype)
    if cfg.family == "hybrid":
        return M.mamba_init_state(batch, cfg.d_model, cfg.ssm, dtype)
    return None
