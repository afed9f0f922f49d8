"""Top-k gate networks (paper §2.1, Algorithm 1).

The gate scores every expert for every token and selects the top-k.  FastMoE
lets users swap the gate; we support the two standard score policies and keep
the router in float32 (routing decisions are precision-sensitive).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.obs.scopes import ROUTER, scoped


class GateOutput(NamedTuple):
    """Routing decision for a flat batch of T tokens."""

    expert_ids: jax.Array  # (T, k) int32 — selected expert per slot
    combine_weights: jax.Array  # (T, k) float32 — mixing weight per slot
    probs: jax.Array  # (T, E) float32 — full router distribution (for aux losses)
    logits: jax.Array  # (T, E) float32 (for z-loss)


def gate_init(rng: jax.Array, d_model: int, num_experts: int,
              dtype=jnp.float32) -> dict:
    scale = d_model ** -0.5
    return {"w": (jax.random.normal(rng, (d_model, num_experts)) * scale).astype(dtype)}


def gate_forward(params: dict, x: jax.Array, cfg: MoEConfig, *,
                 rng: jax.Array | None = None) -> GateOutput:
    """Score and select experts for flat tokens ``x`` of shape (T, d)."""
    router_dtype = jnp.dtype(cfg.router_dtype)
    logits = jnp.asarray(x, router_dtype) @ jnp.asarray(params["w"], router_dtype)
    if rng is not None:  # optional exploration jitter (train-time)
        logits = logits + jax.random.normal(rng, logits.shape, router_dtype) * 0.01
    probs = jax.nn.softmax(logits, axis=-1)

    k = cfg.top_k
    if cfg.gate_policy == "softmax_topk":
        weights, expert_ids = jax.lax.top_k(probs, k)
    elif cfg.gate_policy == "topk_softmax":
        top_logits, expert_ids = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(top_logits, axis=-1)
    else:
        raise ValueError(f"unknown gate_policy {cfg.gate_policy!r}")

    if cfg.renormalize:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return GateOutput(expert_ids.astype(jnp.int32), weights.astype(router_dtype),
                      probs, logits)


# ---------------------------------------------------------------------------
# Gate variants (paper §3.1: the gate is user-swappable)
# ---------------------------------------------------------------------------


def noisy_topk_init(rng: jax.Array, d_model: int, num_experts: int) -> dict:
    """Shazeer et al. 2017 noisy top-k gate — the original gate of the MoE
    line FastMoE implements.  Learned per-expert noise scale."""
    k1, k2 = jax.random.split(rng)
    scale = d_model ** -0.5
    return {"w": jax.random.normal(k1, (d_model, num_experts)) * scale,
            "w_noise": jax.random.normal(k2, (d_model, num_experts)) * scale * 0.1}


def router_init(rng: jax.Array, d_model: int, cfg: MoEConfig,
                dtype=jnp.float32) -> dict:
    """Router params for ``cfg.router``.

    Every variant carries ``w`` (the live gate).  ``noisy_topk`` adds
    ``w_noise``; the exploration routers (``noisy_topk``/``gumbel``) and
    ``frozen`` also carry ``w_frozen`` — the StableMoE-style lightweight
    router the live gate distills into during stage 1, so switching
    ``router`` to "frozen" mid-run (launch/train ``--freeze_router_at``) is
    a pure config change with no param-tree surgery.

    The rng is split ONLY for variants that draw extra params: the default
    ``topk`` (and ``expert_choice``) stream must stay bit-identical to the
    pre-zoo ``gate_init(rng, ...)`` — seeds, checkpoints, and every
    routing-sensitive differential test depend on it.
    """
    if cfg.router not in ("noisy_topk", "gumbel", "frozen"):
        return gate_init(rng, d_model, cfg.num_experts, dtype=dtype)
    k1, k2 = jax.random.split(rng)
    if cfg.router == "noisy_topk":
        p = noisy_topk_init(k1, d_model, cfg.num_experts)
        p = {k: v.astype(dtype) for k, v in p.items()}
    else:
        p = gate_init(k1, d_model, cfg.num_experts, dtype=dtype)
    scale = d_model ** -0.5
    p["w_frozen"] = (jax.random.normal(k2, (d_model, cfg.num_experts))
                     * scale).astype(dtype)
    return p


def gumbel_topk_forward(params: dict, x: jax.Array, cfg: MoEConfig, *,
                        rng: jax.Array | None = None) -> GateOutput:
    """Gumbel-perturbed top-k (StableMoE-style exploration): selection runs
    on ``logits + temperature * Gumbel(0,1)`` while combine weights stay the
    *clean* softmax probabilities gathered at the selected ids (renormalized)
    — noise explores the assignment, not the mixture.  With ``rng=None`` or
    temperature 0 this is exactly the deterministic softmax top-k gate."""
    router_dtype = jnp.dtype(cfg.router_dtype)
    logits = (jnp.asarray(x, router_dtype)
              @ jnp.asarray(params["w"], router_dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    sel = logits
    if rng is not None and cfg.router_temperature > 0:
        u = jax.random.uniform(rng, logits.shape, router_dtype,
                               minval=jnp.finfo(router_dtype).tiny, maxval=1.0)
        sel = logits + cfg.router_temperature * -jnp.log(-jnp.log(u))
    _, expert_ids = jax.lax.top_k(sel, cfg.top_k)
    weights = jnp.take_along_axis(probs, expert_ids, axis=-1)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return GateOutput(expert_ids.astype(jnp.int32),
                      weights.astype(router_dtype), probs, logits)


def frozen_forward(params: dict, x: jax.Array, cfg: MoEConfig) -> GateOutput:
    """StableMoE stage 2: score through the frozen distilled router.

    ``w_frozen`` is stop-gradiented, so the routing *strategy* never moves
    again — gate-id tables are stable, and placement replans become pure
    load responses.  Combine weights still read the frozen scores (softmax
    over the selected k), so gradients keep flowing to the token
    representations through the mixture."""
    wf = jax.lax.stop_gradient(jnp.asarray(params["w_frozen"], jnp.float32))
    logits = jnp.asarray(x, jnp.float32) @ wf
    probs = jax.nn.softmax(logits, axis=-1)
    top_logits, expert_ids = jax.lax.top_k(logits, cfg.top_k)
    weights = jax.nn.softmax(top_logits, axis=-1)
    return GateOutput(expert_ids.astype(jnp.int32), weights, probs, logits)


@scoped(ROUTER)
def route_tokens(params: dict, x: jax.Array, cfg: MoEConfig, *,
                 rng: jax.Array | None = None) -> GateOutput:
    """Dispatch to the token-choice router selected by ``cfg.router``.

    Expert-choice is not a token-choice gate (it emits an (E, C) token grid,
    not (T, k) expert ids) — the MoE paths branch on it before calling here.
    """
    if cfg.router == "topk":
        return gate_forward(params, x, cfg, rng=rng)
    if cfg.router == "noisy_topk":
        return noisy_topk_forward(params, x, cfg, rng=rng)
    if cfg.router == "gumbel":
        return gumbel_topk_forward(params, x, cfg, rng=rng)
    if cfg.router == "frozen":
        return frozen_forward(params, x, cfg)
    raise ValueError(f"unknown router {cfg.router!r}")


def router_distill_loss(params: dict, x: jax.Array, g: GateOutput) -> jax.Array:
    """StableMoE stage-1 distillation: cross-entropy of the lightweight
    frozen-router-to-be against the live gate's top-1 assignment.  Gradients
    reach only ``w_frozen`` (inputs and targets are stop-gradiented), so the
    distillation rides the aux-loss channel without perturbing the live
    gate."""
    xf = jax.lax.stop_gradient(jnp.asarray(x, jnp.float32))
    logits = xf @ jnp.asarray(params["w_frozen"], jnp.float32)
    target = jax.lax.stop_gradient(g.expert_ids[:, 0])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, target[:, None], axis=-1).mean()


def noisy_topk_forward(params: dict, x: jax.Array, cfg: MoEConfig, *,
                       rng: jax.Array | None = None) -> GateOutput:
    """H(x) = x.W + eps * softplus(x.W_noise); top-k over H (train-time noise
    encourages exploration; deterministic when rng is None)."""
    xf = jnp.asarray(x, jnp.float32)
    clean = xf @ jnp.asarray(params["w"], jnp.float32)
    logits = clean
    if rng is not None:
        noise_scale = jax.nn.softplus(
            xf @ jnp.asarray(params["w_noise"], jnp.float32))
        logits = clean + jax.random.normal(rng, clean.shape) * noise_scale
    probs = jax.nn.softmax(logits, axis=-1)
    top_logits, expert_ids = jax.lax.top_k(logits, cfg.top_k)
    weights = jax.nn.softmax(top_logits, axis=-1)
    return GateOutput(expert_ids.astype(jnp.int32), weights, probs, logits)


def expert_choice_forward(params: dict, x: jax.Array, cfg: MoEConfig, *,
                          capacity: int) -> tuple:
    """Expert-choice routing (Zhou et al. 2022, beyond-paper): each EXPERT
    picks its top-``capacity`` tokens instead of tokens picking experts —
    perfectly load-balanced by construction (no aux loss, no drops beyond
    the capacity itself).

    Returns (token_idx (E, C) int32, weights (E, C) f32, probs (T, E),
    logits (T, E)).
    """
    logits = jnp.asarray(x, jnp.float32) @ jnp.asarray(params["w"], jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    # scores transposed: experts choose tokens
    weights, token_idx = jax.lax.top_k(probs.T, capacity)  # (E, C)
    return token_idx.astype(jnp.int32), weights, probs, logits


def expert_choice_moe(params: dict, x: jax.Array, cfg: MoEConfig, *,
                      act: str = "swiglu", capacity_factor: float = 2.0):
    """Full expert-choice MoE layer (gather by expert choice, FFN, scatter-add
    back weighted).  Single-worker reference implementation — the dispatched
    expert-choice paths in core/fmoe must match it (differentially tested)."""
    from repro.core import dispatch as D
    from repro.core.fmoe import expert_ffn

    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    T = xf.shape[0]
    E = cfg.num_experts
    C = D.ec_capacity(T, E, capacity_factor)
    token_idx, weights, probs, _ = expert_choice_forward(
        params["router"], xf, cfg, capacity=C)
    bufs = xf[token_idx]  # (E, C, d)
    out = expert_ffn(params["experts"], bufs, act)
    y = D.combine_ec(out, token_idx, weights, T).astype(xf.dtype)
    return y.reshape(shape), probs
