"""Shared layer primitives: norms, RoPE, embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(d: int, kind: str) -> dict:
    p = {"scale": jnp.ones((d,))}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,))
    return p


def apply_norm(params: dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    else:  # rmsnorm
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        y = y * params["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, yarn=None) -> jax.Array:
    """(head_dim/2,) rotary inverse frequencies theta^(-2i/head_dim).

    ``yarn`` (``AttentionConfig.yarn``: factor, original context, beta_fast,
    beta_slow, cos/sin scale) blends each frequency with its interpolation
    freq/factor: dims that turn more than beta_fast times over the original
    context keep the original frequency, dims that turn fewer than beta_slow
    times take the interpolated one, and a linear ramp joins the two
    (YaRN, arXiv:2309.00071, as DeepSeek-V2 defines it)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is None:
        return freqs
    factor, original, beta_fast, beta_slow, _ = yarn

    def dim_of(turns):  # the dim that turns ``turns`` times over ``original``
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """Rotate (..., seq, heads, head_dim) by per-position angles.

    positions: broadcastable to (..., seq) — absolute token positions.
    ``yarn``: see :func:`rope_freqs`; its last entry scales cos and sin.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, yarn)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., None, :]  # (..., seq, 1, hd/2) broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None and yarn[-1] != 1.0:
        cos, sin = cos * yarn[-1], sin * yarn[-1]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------


def embed_init(rng: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> dict:
    return {"table": (jax.random.normal(rng, (vocab, d)) * 0.02).astype(dtype)}


def embed_lookup(params: dict, tokens: jax.Array, dtype) -> jax.Array:
    return params["table"][tokens].astype(dtype)


def unembed(params: dict, x: jax.Array) -> jax.Array:
    """Logits in float32 (numerically-sensitive softmax upstream)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      params["table"].astype(jnp.float32))


def linear_init(rng: jax.Array, d_in: int, d_out: int, dtype=jnp.float32,
                bias: bool = False) -> dict:
    p = {"w": (jax.random.normal(rng, (d_in, d_out)) * d_in ** -0.5).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(params: dict, x: jax.Array) -> jax.Array:
    y = x @ params["w"]
    if "b" in params:
        y = y + params["b"]
    return y
