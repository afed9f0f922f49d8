"""Stable names for the stages of the train step, as ``jax.named_scope``s.

Each stage of the step runs under one of the eight names below, set once in
the shared helper that does the work, so every MoE path (``_moe_local``,
``_moe_a2a``, ``_moe_a2a_ragged``, ``_moe_psum``) carries them without a
line of its own:

==================  =====================================================
``fmoe.router``     ``gate.route_tokens``, ``fmoe._ec_route``, the balance
                    and z losses
``fmoe.dispatch``   the capacity and ragged plans and scatters of
                    ``core/dispatch.py``, the cross-rank ragged plans
``fmoe.exchange``   the collectives of ``core/comm.py`` and the exchanges of
                    ``core/pipeline.py``, both ways, counts included
``fmoe.experts``    the expert FFNs (``EXPERT_FNS`` and ``RAGGED_FNS``)
``fmoe.combine``    the gathers of ``core/dispatch.py`` (``combine_*``)
``fmoe.attention``  the token mixer (``blocks._mixer_seq`` / ``_mixer_decode``)
``fmoe.head_loss``  the output head (``lm._logits``) and the cross-entropy
``fmoe.optimizer``  ``AdamW.update``, global-norm clip included
==================  =====================================================

One more name, ``fmoe.dense_ffn`` (``core.fmoe.dense_ffn``), marks the FFNs
every token runs: leading dense layers, shared experts, dense residuals and
dense models' FFNs.  It stands outside ``ALL`` because the eight stages are
in every MoE train step and it is not; ``bench/scopes.py`` leaves it
unscoped, and a reader that wants it builds its own table.

A scope is compile-time metadata: it sets the ``op_name`` of the HLO
instructions traced inside it and adds no operation, collective or host
work.  JAX carries the name stack through ``jvp``/``transpose`` and remat,
so a scope names its forward pass, its backward pass (``op_name`` then reads
``.../transpose(jvp(...))/...``) and any recompute.  Scopes nest: where one
stage runs inside another (the expert FFN inside the pipelined exchange),
the innermost name is the op's stage.  ``bench/scopes.py`` reduces a device
trace by these names.
"""
from __future__ import annotations

import functools

import jax

ROUTER = "fmoe.router"
DISPATCH = "fmoe.dispatch"
EXCHANGE = "fmoe.exchange"
EXPERTS = "fmoe.experts"
COMBINE = "fmoe.combine"
ATTENTION = "fmoe.attention"
HEAD_LOSS = "fmoe.head_loss"
OPTIMIZER = "fmoe.optimizer"

ALL = (ROUTER, DISPATCH, EXCHANGE, EXPERTS, COMBINE, ATTENTION, HEAD_LOSS,
       OPTIMIZER)
DENSE_FFN = "fmoe.dense_ffn"


def scope(name: str):
    """``jax.named_scope(name)``: the one place the program opens a scope."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: run the function under ``scope(name)``, looked up at call
    time."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
