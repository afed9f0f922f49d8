"""Full language models assembled from configs.

Public API (all functional):
  init_params(rng, cfg)                        -> params pytree
  forward(params, cfg, tokens, ...)            -> (logits, MoEMetrics)
  loss_fn(params, cfg, batch, ...)             -> (loss, aux dict)
  init_cache(cfg, batch, cache_len, ...)       -> stacked decode cache
  decode_step(params, cfg, tokens, pos, cache) -> (logits, new_cache, metrics)

The layer stack is stored stacked (leading L dim on every leaf) and applied
with jax.lax.scan (+ jax.remat per layer when cfg.remat == "full") — essential
for compile time at 80 layers x 512 devices.  Per-layer sliding windows ride
along as a scanned (L,) array so Hymba's global layers coexist with windowed
ones inside one homogeneous scan.  Leading dense-FFN layers
(``cfg.num_dense_layers``, DeepSeek's first_k_dense_replace) are a stack of
their own, ``dense_layers``, scanned before the ``layers`` stack.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.balance import MoEMetrics
from repro.core.fmoe import DistConfig
from repro.models import attention as A
from repro.models import blocks as B
from repro.models.layers import (apply_norm, embed_init, embed_lookup,
                                 linear, linear_init, norm_init, unembed)
from repro.obs.scopes import HEAD_LOSS, scoped


def _n_experts(cfg: ModelConfig) -> int:
    return cfg.moe.num_experts if cfg.moe is not None else 1


def _layer_tables(cfg: ModelConfig, dist: Optional[DistConfig]):
    """Split a per-layer placement riding on ``dist`` for the layer scan.

    A :class:`repro.placement.plan.PerLayerPlacement` can't pass into
    fmoe_apply whole (each layer has its own gate-id table but the scan
    needs one static geometry), so it splits here: ``dist.placement``
    becomes the shared-geometry representative plan, and the stacked
    ``(L, E)`` logical->physical tables return separately to ride the scan
    as per-layer xs (blocks._apply_ffn threads each row as ``l2p``).
    Returns ``(dist, tables | None)``.
    """
    if dist is None or dist.placement is None:
        return dist, None
    from repro.placement.plan import PerLayerPlacement
    place = dist.placement
    if not isinstance(place, PerLayerPlacement):
        return dist, None
    place.validate()
    if place.num_layers != cfg.stack_layers:
        raise ValueError(
            f"per-layer placement has {place.num_layers} layers, "
            f"config has {cfg.stack_layers} MoE layers")
    tables = jnp.asarray(place.logical_to_physical, jnp.int32)  # (L, E)
    return dist._replace(placement=place.geometry), tables


def _cast_params(p, dtype):
    """Cast float params to the compute dtype at point of use (master weights
    stay float32; the router re-promotes to f32 internally)."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        and a.dtype != dtype else a, p)


def _stacked_layer_init(rng: jax.Array, cfg: ModelConfig, n: int,
                        cross: bool = False) -> dict:
    keys = jax.random.split(rng, n)
    return jax.vmap(lambda k: B.layer_init(k, cfg, cross=cross))(keys)


def _dense_cfg(cfg: ModelConfig) -> ModelConfig:
    """The config a leading dense layer runs: the model's, FFN of width
    ``d_ff`` in place of the MoE."""
    return dataclasses.replace(cfg, moe=None)


def init_params(rng: jax.Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(rng, 6)
    dtype = jnp.dtype(cfg.param_dtype)
    p: dict = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "layers": _stacked_layer_init(ks[1], cfg, cfg.stack_layers,
                                      cross=cfg.family == "audio"),
        "final_norm": norm_init(cfg.d_model, cfg.norm),
    }
    if cfg.num_dense_layers:
        p["dense_layers"] = _stacked_layer_init(ks[4], _dense_cfg(cfg),
                                                cfg.num_dense_layers)
    if not cfg.tie_embeddings:
        p["lm_head"] = linear_init(ks[2], cfg.d_model, cfg.vocab_size, dtype)
    if cfg.encoder is not None:
        p["enc_layers"] = _stacked_layer_init(ks[3], cfg, cfg.encoder.num_layers)
        p["enc_norm"] = norm_init(cfg.d_model, cfg.norm)
    return p


@scoped(HEAD_LOSS)
def _logits(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    return linear(params["lm_head"], x.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Encoder (whisper) — bidirectional stack over stubbed frame embeddings
# ---------------------------------------------------------------------------


def encode(params: dict, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, F, d_model) precomputed conv-frontend embeddings (stub)."""
    x = frames.astype(jnp.dtype(cfg.dtype))

    def body(x, p_l):
        p_l = _cast_params(p_l, jnp.dtype(cfg.dtype))
        h = A.gqa_apply(p_l["attn"], apply_norm(p_l["norm1"], x, cfg.norm),
                        cfg.attention, window=B.FULL_WINDOW, causal=False)
        x = x + h
        from repro.core.fmoe import dense_ffn
        h = dense_ffn(p_l["ffn"], apply_norm(p_l["norm2"], x, cfg.norm), cfg.act)
        return (x + h).astype(x.dtype), None

    if cfg.remat == "full":
        body = jax.remat(body)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return apply_norm(params["enc_norm"], x, cfg.norm)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
            frames: Optional[jax.Array] = None,
            patches: Optional[jax.Array] = None,
            dist: Optional[DistConfig] = None, impl: str = "einsum",
            layer_loads: bool = False, rng: Optional[jax.Array] = None):
    """tokens (B, S) -> (logits (B, S', V), MoEMetrics).

    vlm: ``patches`` (B, P, d) are prepended; logits cover the full combined
    sequence (caller slices text positions for the loss).
    audio: ``frames`` (B, F, d) go through the encoder; decoder cross-attends.
    ``layer_loads=True`` additionally returns the per-layer expert load
    stack (L, E) — expert skew is per layer, and the per-layer placement
    planner feeds on this instead of the layer-summed ``metrics.load``.
    ``rng`` arms gate exploration (noisy_topk / gumbel routers): it splits
    into per-layer keys riding the layer scan; None keeps routing
    deterministic (the eval/serve stance for every router).
    """
    dtype = jnp.dtype(cfg.dtype)
    dist, tables = _layer_tables(cfg, dist)
    x = embed_lookup(params["embed"], tokens, dtype)
    if cfg.frontend == "vision" and patches is not None:
        x = jnp.concatenate([patches.astype(dtype), x], axis=1)
    enc_out = None
    if cfg.family == "audio":
        enc_out = encode(params, cfg, frames)

    batch = x.shape[0]
    windows = B.layer_windows(cfg)
    if cfg.num_dense_layers:
        x = _dense_stack(params["dense_layers"], cfg, x,
                         windows[:cfg.num_dense_layers], dist)
        windows = windows[cfg.num_dense_layers:]
    state0 = B.mixer_state(cfg, batch, dtype)
    n_e = _n_experts(cfg)
    want_loads = layer_loads and cfg.moe is not None
    has_rng = rng is not None and cfg.moe is not None

    def body(carry, xs):
        x, metrics = carry
        p_l, window = xs[:2]
        rest = xs[2:]
        l2p = rest[0] if tables is not None else None
        rng_l = rest[int(tables is not None)] if has_rng else None
        x, m = B.layer_apply_seq(_cast_params(p_l, dtype), cfg, x,
                                 window=window, dist=dist,
                                 enc_out=enc_out, mixer_state=state0,
                                 impl=impl, l2p=l2p, rng=rng_l)
        metrics = metrics + m if m is not None else metrics
        return ((x.astype(dtype), metrics),
                m.load if want_loads else None)

    if cfg.remat == "full":
        body = jax.remat(body)
    xs = (params["layers"], windows)
    if tables is not None:
        xs += (tables,)
    if has_rng:
        xs += (jax.random.split(rng, cfg.stack_layers),)
    (x, metrics), loads = jax.lax.scan(body, (x, MoEMetrics.zero(n_e)), xs)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _logits(params, cfg, x)
    if layer_loads:
        if loads is None:
            loads = jnp.zeros((cfg.stack_layers, n_e))
        return logits, metrics, loads
    return logits, metrics


def _dense_stack(params: dict, cfg: ModelConfig, x: jax.Array,
                 windows: jax.Array, dist: Optional[DistConfig]) -> jax.Array:
    """The leading dense layers over x (B, S, d), scanned like the MoE
    stack."""
    dtype = jnp.dtype(cfg.dtype)
    dcfg = _dense_cfg(cfg)

    def body(x, xs):
        p_l, window = xs
        x, _ = B.layer_apply_seq(_cast_params(p_l, dtype), dcfg, x,
                                 window=window, dist=dist)
        return x.astype(dtype), None

    if cfg.remat == "full":
        body = jax.remat(body)
    x, _ = jax.lax.scan(body, x, (params, windows))
    return x


@scoped(HEAD_LOSS)
def _next_token_ce(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy, in float32."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean()


def loss_fn(params: dict, cfg: ModelConfig, batch: dict, *,
            dist: Optional[DistConfig] = None, impl: str = "einsum",
            rng: Optional[jax.Array] = None):
    """Next-token cross-entropy + MoE aux losses.  batch: {"tokens", and
    optionally "frames"/"patches"}.  ``impl`` picks the expert kernels
    (einsum | pallas | fused — see repro.core.fmoe.EXPERT_FNS).  ``rng``
    arms train-time gate exploration (see :func:`forward`)."""
    tokens = batch["tokens"]
    logits, metrics, loads = forward(params, cfg, tokens,
                                     frames=batch.get("frames"),
                                     patches=batch.get("patches"), dist=dist,
                                     impl=impl, layer_loads=True, rng=rng)
    if cfg.frontend == "vision" and "patches" in batch:
        logits = logits[:, batch["patches"].shape[1]:]  # text positions only
    ce = _next_token_ce(logits, tokens)
    loss = ce
    L = max(cfg.stack_layers, 1)  # the MoE losses average over MoE layers
    if cfg.moe is not None:
        loss = loss + (cfg.moe.balance_loss_weight * metrics.aux_loss
                       + cfg.moe.z_loss_weight * metrics.z_loss) / L
    aux = {"ce": ce, "aux_loss": metrics.aux_loss, "z_loss": metrics.z_loss,
           "drop_frac": metrics.drop_frac / L,
           "held_frac": metrics.held_frac / L,
           "load": metrics.load / L,  # per-expert load for the §6 monitor
           "load_layers": loads}  # (L, E) per-layer load (per-layer planner)
    obs = metrics.obs
    if obs is not None:
        # device-side telemetry (repro.obs.counters), summed over layers —
        # rides the same device->host transfer as the loss
        aux.update(wire_elems=obs.wire_elems, wire_bytes=obs.wire_bytes,
                   wire_bytes_intra=obs.wire_bytes_intra,
                   wire_bytes_inter=obs.wire_bytes_inter,
                   dropped=obs.dropped, shadow_hits=obs.shadow_hits,
                   imbalance=obs.imbalance / L)  # per-layer avg
    return loss, aux


# ---------------------------------------------------------------------------
# Prefill: one full pass that fills the decode cache (serving fast path)
# ---------------------------------------------------------------------------


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array, cache: Any, *,
            frames: Optional[jax.Array] = None,
            patches: Optional[jax.Array] = None,
            dist: Optional[DistConfig] = None, impl: str = "einsum"):
    """tokens (B, S) + empty cache -> (logits (B, S', V), filled cache,
    metrics).  Decoding then continues at position S' with decode_step."""
    _check_no_dense_layers(cfg, "prefill")
    dtype = jnp.dtype(cfg.dtype)
    dist, tables = _layer_tables(cfg, dist)
    x = embed_lookup(params["embed"], tokens, dtype)
    if cfg.frontend == "vision" and patches is not None:
        x = jnp.concatenate([patches.astype(dtype), x], axis=1)
    if cfg.family == "audio":
        enc_out = encode(params, cfg, frames)
        L = cfg.num_layers
        cache = dict(cache)
        cache["enc_out"] = jnp.broadcast_to(
            enc_out[None].astype(dtype), (L,) + enc_out.shape)

    windows = B.layer_windows(cfg)
    n_e = _n_experts(cfg)

    def body(carry, xs):
        x, metrics = carry
        p_l, window, cache_l = xs[:3]
        l2p = xs[3] if tables is not None else None
        x, new_cache_l, m = B.layer_apply_prefill(
            _cast_params(p_l, dtype), cfg, x, cache_l, window=window,
            dist=dist, impl=impl, l2p=l2p)
        metrics = metrics + m if m is not None else metrics
        return (x.astype(dtype), metrics), new_cache_l

    xs = (params["layers"], windows, cache)
    if tables is not None:
        xs += (tables,)
    (x, metrics), new_cache = jax.lax.scan(
        body, (x, MoEMetrics.zero(n_e)), xs)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return _logits(params, cfg, x), new_cache, metrics


# ---------------------------------------------------------------------------
# Decode (one token, KV/state cache)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, *,
               enc_out: Optional[jax.Array] = None) -> Any:
    """Stacked (leading L dim) decode cache for the layer stack."""
    _check_no_dense_layers(cfg, "a decode cache")
    dtype = jnp.dtype(cfg.dtype)
    one = B.layer_cache(cfg, batch, cache_len, dtype, enc_out=enc_out)
    L = cfg.num_layers
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one)


def _check_no_dense_layers(cfg: ModelConfig, what: str) -> None:
    """Serving covers one homogeneous layer stack: leading dense layers
    have no decode cache yet."""
    if cfg.num_dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: {what} is not supported for a model with "
            f"{cfg.num_dense_layers} leading dense layer(s); it trains only")


def supports_paged(cfg: ModelConfig) -> bool:
    """Whether the family's decode cache can be paged (plain attention
    ring caches; recurrent/hybrid/audio state caches cannot)."""
    return (cfg.attention is not None
            and cfg.family not in ("ssm", "hybrid", "audio"))


def init_paged_cache(cfg: ModelConfig, num_blocks: int,
                     block_size: int) -> Any:
    """Stacked (leading L dim) paged block pool shared by all decode slots.

    Pool leaves have no batch dim — slots address it through per-slot block
    tables passed to ``decode_step(block_tables=...)``.  Rows 0/1 are the
    reserved null/scratch blocks (models/attention)."""
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged KV cache is not supported for family {cfg.family!r}")
    _check_no_dense_layers(cfg, "a paged decode cache")
    dtype = jnp.dtype(cfg.dtype)
    one = B.layer_paged_cache(cfg, num_blocks, block_size, dtype)
    L = cfg.num_layers
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one)


def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                pos: jax.Array, cache: Any, *,
                dist: Optional[DistConfig] = None, impl: str = "einsum",
                block_tables: Optional[jax.Array] = None,
                layer_loads: bool = False):
    """tokens (B, 1) at absolute position ``pos`` -> (logits (B, 1, V),
    new_cache, metrics).  A per-layer ``dist.placement`` is honored: each
    layer's decode MoE (usually the psum mode) routes through its own
    gate-id table, with shadowed hot experts served locally outside the
    reduction (launch/serve.py wires this for the production decode step).

    ``block_tables`` (B, nb) reads/writes the cache through the paged block
    pool (``init_paged_cache``) instead of per-slot rings.  ``layer_loads=
    True`` additionally returns the (L, E) per-layer expert-load stack as a
    fourth output — the online serve-time replan feed (mirrors
    ``forward(layer_loads=True)``)."""
    _check_no_dense_layers(cfg, "decode")
    dtype = jnp.dtype(cfg.dtype)
    dist, tables = _layer_tables(cfg, dist)
    x = embed_lookup(params["embed"], tokens, dtype)
    cache_len = _cache_len(cfg, cache, block_tables)
    windows = jnp.minimum(B.layer_windows(cfg),
                          jnp.int32(cache_len)) if cache_len else B.layer_windows(cfg)
    n_e = _n_experts(cfg)
    want_loads = layer_loads and cfg.moe is not None

    def body(carry, xs):
        x, metrics = carry
        p_l, window, cache_l = xs[:3]
        l2p = xs[3] if tables is not None else None
        x, new_cache_l, m = B.layer_apply_decode(
            _cast_params(p_l, dtype), cfg, x, cache_l, pos,
            window=window, dist=dist, impl=impl, l2p=l2p,
            block_tables=block_tables)
        metrics = metrics + m if m is not None else metrics
        return ((x.astype(dtype), metrics),
                (new_cache_l, m.load if want_loads else None))

    xs = (params["layers"], windows, cache)
    if tables is not None:
        xs += (tables,)
    (x, metrics), (new_cache, loads) = jax.lax.scan(
        body, (x, MoEMetrics.zero(n_e)), xs)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _logits(params, cfg, x)
    if layer_loads:
        if loads is None:
            loads = jnp.zeros((cfg.num_layers, n_e))
        return logits, new_cache, metrics, loads
    return logits, new_cache, metrics


def _cache_len(cfg: ModelConfig, cache: Any,
               block_tables: Optional[jax.Array] = None) -> int:
    """Ring-buffer length (0 for pure-state caches).  With a paged pool the
    visible length is the gathered per-slot view: table width x block size."""
    if cfg.family == "ssm":
        return 0
    leaf = cache
    if cfg.family == "hybrid":
        leaf = cache["attn"]
    elif cfg.family == "audio":
        leaf = cache["self"]
    if block_tables is not None:
        return block_tables.shape[1] * leaf.positions.shape[-1]
    return leaf.positions.shape[-1]
