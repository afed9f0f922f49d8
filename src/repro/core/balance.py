"""Load-balance losses and monitoring (paper §6 lists these as future work —
implemented here as a beyond-paper feature, following Switch/GShard)."""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.counters import ObsCounters
from repro.obs.scopes import ROUTER, scoped


class MoEMetrics(NamedTuple):
    """Per-MoE-layer metrics, accumulable across layers (all arrays).

    ``obs`` carries the device-side telemetry counters (repro.obs.counters)
    through the same layer-scan accumulation; monitor-feeding constructions
    that never '+'-accumulate may leave it None (the default).
    """

    aux_loss: jax.Array  # scalar — Switch load-balance loss
    z_loss: jax.Array  # scalar — router logit z-loss
    load: jax.Array  # (E,) float32 — fraction of tokens assigned per expert
    drop_frac: jax.Array  # scalar — fraction of (token, slot) pairs dropped
    obs: Any = None  # Optional[ObsCounters] — wire/drop/shadow counters
    # scalar — fraction of (token, slot) assignments routed to experts the
    # layer holds: experts_held / num_experts under even routing on a
    # chip's share (MoEConfig.experts_held), 1 where every expert is held
    held_frac: Any = None

    @staticmethod
    def zero(num_experts: int) -> "MoEMetrics":
        z = jnp.zeros(())
        return MoEMetrics(z, z, jnp.zeros((num_experts,)), z,
                          ObsCounters.zero(), z)

    def __add__(self, other: "MoEMetrics") -> "MoEMetrics":
        return MoEMetrics(*(b if a is None else a if b is None else a + b
                            for a, b in zip(self, other)))


def load_balance_loss(probs: jax.Array, expert_ids: jax.Array,
                      num_experts: int) -> jax.Array:
    """Switch-Transformer aux loss: E * sum_e f_e * P_e.

    f_e = fraction of tokens whose top-1 choice is e; P_e = mean router prob.
    Minimized (=1) at uniform routing.
    """
    top1 = expert_ids[:, 0]
    f = jnp.mean(jax.nn.one_hot(top1, num_experts, dtype=probs.dtype), axis=0)
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


@scoped(ROUTER)
def router_z_loss(logits: jax.Array) -> jax.Array:
    """ST-MoE z-loss: mean(logsumexp(logits)^2) — keeps router logits small."""
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)


def load_metrics(load_counts: jax.Array, keep: jax.Array | None,
                 num_assignments: jax.Array | int) -> tuple[jax.Array, jax.Array]:
    """(normalized per-expert load, dropped fraction) — the paper's §6
    'load-balance monitor'."""
    total = jnp.maximum(jnp.asarray(num_assignments, jnp.float32), 1.0)
    load = load_counts.astype(jnp.float32) / total
    if keep is None:
        drop = jnp.zeros(())
    else:
        drop = 1.0 - jnp.sum(keep.astype(jnp.float32)) / total
    return load, drop
