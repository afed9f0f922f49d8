"""AdamW with decoupled weight decay, global-norm clipping, and an optional
bf16 second-moment ("m8"-style) memory saving for trillion-param MoE runs."""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs.scopes import OPTIMIZER, scoped


class AdamWState(NamedTuple):
    step: jax.Array
    mu: dict
    nu: dict


class AdamW(NamedTuple):
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    moment_dtype: str = "float32"  # "bfloat16" halves optimizer HBM

    def init(self, params) -> AdamWState:
        dt = jnp.dtype(self.moment_dtype)
        zeros = lambda p: jnp.zeros(p.shape, dt)
        return AdamWState(jnp.zeros((), jnp.int32),
                          jax.tree.map(zeros, params),
                          jax.tree.map(zeros, params))

    @scoped(OPTIMIZER)
    def update(self, grads, state: AdamWState, params, *,
               lr_scale: jax.Array | float = 1.0):
        """Returns (new_params, new_state, grad_norm)."""
        gnorm = global_norm(grads)
        if self.clip_norm is not None:
            scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        step = state.step + 1
        b1c = 1 - self.b1 ** step.astype(jnp.float32)
        b2c = 1 - self.b2 ** step.astype(jnp.float32)
        lr = self.lr * lr_scale

        def upd(g, m, v, p):
            gf = g.astype(jnp.float32)
            m_new = self.b1 * m.astype(jnp.float32) + (1 - self.b1) * gf
            v_new = self.b2 * v.astype(jnp.float32) + (1 - self.b2) * gf * gf
            mhat = m_new / b1c
            vhat = v_new / b2c
            delta = mhat / (jnp.sqrt(vhat) + self.eps) + self.weight_decay * p.astype(jnp.float32)
            return ((p.astype(jnp.float32) - lr * delta).astype(p.dtype),
                    m_new.astype(m.dtype), v_new.astype(v.dtype))

        out = jax.tree.map(upd, grads, state.mu, state.nu, params)
        new_params = jax.tree.map(lambda t: t[0], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
        new_mu = jax.tree.map(lambda t: t[1], out,
                              is_leaf=lambda t: isinstance(t, tuple))
        new_nu = jax.tree.map(lambda t: t[2], out,
                              is_leaf=lambda t: isinstance(t, tuple))
        return new_params, AdamWState(step, new_mu, new_nu), gnorm


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))
