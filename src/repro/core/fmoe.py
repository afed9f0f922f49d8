"""The FMoE layer — paper §3 (system design) + §4 (reordered computation).

Functional analogue of FastMoE's ``FMoE`` / ``FMoETransformerMLP``:

* arbitrary expert networks via an overloadable ``expert_fn`` (paper §3.1);
* scatter → batched per-expert GeMM → gather reordering (paper §4, Fig 4);
* expert parallelism across workers with explicit all-to-all global data
  exchange (paper §3.2, Fig 2), realized as ``shard_map`` + ``lax.all_to_all``
  over the ``model`` mesh axis;
* a ``psum`` mode for decode-time shapes where tokens cannot be sharded
  across the expert axis (each rank computes its local experts for all its
  tokens, partial outputs are psum-combined);
* load-balance losses + monitoring (paper §6 future work, beyond-paper).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core import dispatch as D
from repro.core import pipeline
from repro.core.balance import MoEMetrics, load_balance_loss, load_metrics, router_z_loss
from repro.core.gate import (expert_choice_forward, gate_forward, gate_init,
                             route_tokens, router_distill_loss, router_init)
from repro.obs import counters as obs_counters
from repro.obs.counters import ObsCounters


class DistConfig(NamedTuple):
    """How the MoE layer is distributed over the device mesh.

    mode "a2a" (tokens sharded over the expert axis too -> all-to-all
    exchange, the paper's §3.2 pattern) is chosen automatically when
    ``expert_axis`` appears in ``token_axes``; otherwise "psum".

    Beyond-paper options (§Perf):
      tp_axis — expert-internal tensor parallelism: expert hidden dims stay
        sharded over this axis and activations psum, instead of FSDP
        all-gathering the expert weights every layer.
      constrain_tokens — pin the flat-token sharding for the shared/dense
        residual FFNs so XLA doesn't replicate the token array when leaving
        the shard_map region (fixes the SPMD "involuntary rematerialization").
      placement — an ExpertPlacement (repro.placement.plan): params are in
        its physical order, gate ids are remapped through its index table,
        and shadowed hot experts run replicated outside the all-to-all (a2a
        modes) or outside the psum reduction (decode mode).  At the model
        level this may be a PerLayerPlacement — models/lm.py splits it into
        the shared geometry (which rides here) plus per-layer gate-id
        tables threaded through the layer scan (fmoe_apply's ``l2p``).
      overlap_chunks — §5.2 smart schedule: split the a2a payload into this
        many capacity micro-shards and pipeline exchange with expert compute
        (repro.core.pipeline).  0/1 = serial; values that don't divide the
        capacity degrade to the nearest feasible depth.  Bit-exact vs serial.
      wire_dtype — cast a2a payloads to this dtype across the wire only
        ("bf16" halves exchange bytes; accumulation/combine stay f32).
      ragged_bound — rows per peer shard of the ragged (dropless) exchange
        (cfg.dispatch == "ragged" under a2a mode): the static pad-to-max-
        per-peer width that keeps the variable-size exchange jit-able.
        0 = T_local*k, which provably never drops; a smaller bound shrinks
        wire bytes toward actual load at the price of GShard-style drops
        when one peer's shard overflows (tracked in metrics.drop_frac).
      node_axis — hierarchical two-level ragged exchange: the name of the
        *inter-node* mesh axis (launch/mesh make_local_mesh(node=...)).
        When set and leading ``expert_axes`` (ranks node-major), the ragged
        a2a splits into an intra-node aggregation hop over the remaining
        (fast) expert axes and a slim inter-node hop over this (slow) axis
        that carries only truly-needed rows — per-source padding never
        crosses a node boundary.  Bit-exact vs. the flat exchange.  None, or
        a mesh without the axis, keeps the flat single-level exchange.
      inter_bound — rows per slim per-node shard of the inter-node hop
        (0 = n_inner * ragged_bound, which never drops at this stage); a
        smaller value shrinks inter-node wire bytes toward actual load, with
        overflow rows dropped by the forwarding agent (also in drop_frac).
        launch/train's ``ragged_bound=auto`` calibrates both bounds from the
        LoadMonitor's EMAs.
    """

    mesh: Any
    token_axes: tuple  # mesh axes sharding the flat token dim
    # single axis name, or a tuple of axes (e.g. ("pod", "model") for
    # cross-pod expert parallelism, §Perf multi-pod)
    expert_axis: Any = "model"
    tp_axis: Optional[str] = None
    constrain_tokens: bool = False
    fsdp_axis: Optional[str] = None  # constrain bf16-cast weights sharded
    # so the per-layer FSDP gather moves bf16, not the f32 master (§Perf)
    placement: Any = None  # Optional[repro.placement.plan.ExpertPlacement]
    overlap_chunks: int = 0  # §5.2 pipelined exchange (0/1 = serial)
    wire_dtype: Optional[str] = None  # a2a payload dtype ("bf16" | None)
    ragged_bound: int = 0  # dropless-exchange peer-shard rows (0 = T*k)
    # device-side telemetry counters (repro.obs.counters) riding the metrics
    # output.  They are derived from static shapes + values the paths already
    # reduce (no extra collectives — tests/test_obs.py locks the HLO diff);
    # False pins them to zeros, which is what that regression test compares
    # against.
    obs: bool = True
    node_axis: Optional[str] = None  # inter-node axis of the two-level
    # ragged exchange (must lead expert_axes); None = flat exchange
    inter_bound: int = 0  # slim inter-node shard rows (0 = n_inner * bound)
    router: Optional[str] = None  # override cfg.router for this distribution
    # (e.g. launch/serve pins the decode router without touching the model
    # config); None = use MoEConfig.router

    @classmethod
    def local(cls, placement=None) -> "DistConfig":
        """Single-worker carrier: no mesh, no collectives.  fmoe_apply routes
        a ``mesh=None`` dist to the local §4 path, so this is how a placement
        (index-table routing over physically reordered params) rides the one
        distribution-config channel without a device mesh — the replacement
        for the deprecated bare ``placement=`` kwarg."""
        return cls(None, (), placement=placement)

    @property
    def expert_axes(self) -> tuple:
        return (self.expert_axis if isinstance(self.expert_axis, tuple)
                else (self.expert_axis,))

    @property
    def mode(self) -> str:
        return ("a2a" if all(a in self.token_axes for a in self.expert_axes)
                else "psum")

    @property
    def expert_parallelism(self) -> int:
        n = 1
        for a in self.expert_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def wire_jnp_dtype(self):
        """jnp dtype for a2a payloads, or None for the activation dtype."""
        if self.wire_dtype is None:
            return None
        if self.wire_dtype in ("bf16", "bfloat16"):
            return jnp.bfloat16
        return jnp.dtype(self.wire_dtype)


# ---------------------------------------------------------------------------
# Expert networks (the default expert: a transformer FFN)
# ---------------------------------------------------------------------------


def _ffn_init(rng: jax.Array, num: int, d: int, h: int, act: str, dtype) -> dict:
    ks = jax.random.split(rng, 3)
    si, so = d ** -0.5, h ** -0.5
    shape_i, shape_o = (num, d, h), (num, h, d)
    if num == 0:
        shape_i, shape_o = (d, h), (h, d)
    p = {"wo": (jax.random.normal(ks[2], shape_o) * so).astype(dtype)}
    if act == "swiglu":
        p["wi_gate"] = (jax.random.normal(ks[0], shape_i) * si).astype(dtype)
        p["wi_up"] = (jax.random.normal(ks[1], shape_i) * si).astype(dtype)
    else:
        p["wi"] = (jax.random.normal(ks[0], shape_i) * si).astype(dtype)
    return p


def _act(h: jax.Array, act: str) -> jax.Array:
    if act == "gelu":
        return jax.nn.gelu(h)
    if act == "rwkv":  # squared relu (RWKV channel-mix)
        return jnp.square(jax.nn.relu(h))
    return jax.nn.silu(h)  # swiglu gate handled by caller


def dense_ffn(params: dict, x: jax.Array, act: str) -> jax.Array:
    """Plain (non-expert) FFN on (..., d)."""
    if act == "swiglu":
        h = jax.nn.silu(x @ params["wi_gate"]) * (x @ params["wi_up"])
    else:
        h = _act(x @ params["wi"], act)
    return h @ params["wo"]


def expert_ffn(params: dict, xs: jax.Array, act: str) -> jax.Array:
    """Default ``expert_fn``: batched per-expert FFN on (E, n, d) buffers.

    One einsum per projection = one big GeMM batched over experts — the MXU
    analogue of FMoELinear's multi-stream concurrent expert execution (C2).
    """
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("end,edh->enh", xs, params["wi_gate"]))
        h = h * jnp.einsum("end,edh->enh", xs, params["wi_up"])
    else:
        h = _act(jnp.einsum("end,edh->enh", xs, params["wi"]), act)
    return jnp.einsum("enh,ehd->end", h, params["wo"])


def _expert_ws(params: dict, act: str) -> tuple:
    """(wi_gate, wi_up) for swiglu, (wi,) otherwise — the kernels' contract."""
    return ((params["wi_gate"], params["wi_up"]) if act == "swiglu"
            else (params["wi"],))


def expert_ffn_pallas(params: dict, xs: jax.Array, act: str) -> jax.Array:
    """expert_fn backed by the Pallas grouped-GEMM kernel (equal-size groups)."""
    from repro.kernels import grouped_gemm as gg
    from repro.kernels import ops  # lazy: keeps core importable without kernels

    E, n, d = xs.shape
    flat = xs.reshape(E * n, d)
    sizes = jnp.full((E,), n, jnp.int32)
    aligned = n % gg.DEFAULT_BM == 0  # whole row tiles: skip pad/gather
    if act == "swiglu":
        h = jax.nn.silu(ops.grouped_matmul(flat, params["wi_gate"], sizes,
                                           "pallas", gg.DEFAULT_BM, aligned))
        h = h * ops.grouped_matmul(flat, params["wi_up"], sizes,
                                   "pallas", gg.DEFAULT_BM, aligned)
    else:
        h = _act(ops.grouped_matmul(flat, params["wi"], sizes,
                                    "pallas", gg.DEFAULT_BM, aligned), act)
    return ops.grouped_matmul(h, params["wo"], sizes,
                              "pallas", gg.DEFAULT_BM, aligned).reshape(E, n, -1)


def expert_ffn_fused(params: dict, xs: jax.Array, act: str) -> jax.Array:
    """expert_fn backed by the fused GEMM1+act+GEMM2 Pallas kernel.

    Unlike the two-pass path, the (M, H) hidden activation never
    materializes in HBM — in the forward or the backward (fused dX / dW
    kernels via the custom_vjp; see repro.kernels.fused_ffn_bwd).
    """
    from repro.kernels import fused_ffn as ffk
    from repro.kernels import ops  # lazy: keeps core importable without kernels

    E, n, d = xs.shape
    flat = xs.reshape(E * n, d)
    sizes = jnp.full((E,), n, jnp.int32)
    aligned = n % ffk.DEFAULT_BM == 0  # whole row tiles: skip pad/gather
    return ops.fused_grouped_ffn(flat, _expert_ws(params, act), params["wo"],
                                 sizes, act, ffk.DEFAULT_BM, ffk.DEFAULT_BH,
                                 aligned).reshape(E, n, -1)


EXPERT_FNS: dict[str, Callable] = {
    "einsum": expert_ffn,
    "pallas": expert_ffn_pallas,
    "fused": expert_ffn_fused,
}


# Ragged (dropless) analogues: expert-sorted (T*k, d) rows with variable
# group sizes.  "einsum"/"pallas" run the two-pass grouped GEMMs;
# "fused" runs the fused fwd+bwd kernels — same selection axis as
# EXPERT_FNS so every dispatch mode exposes every impl.


def ragged_ffn_two_pass(params: dict, xs: jax.Array, group_sizes: jax.Array,
                        act: str, impl: str = "pallas") -> jax.Array:
    from repro.kernels import ops

    return ops.ffn_two_pass(xs, _expert_ws(params, act), params["wo"],
                            group_sizes, act, impl)


def ragged_ffn_fused(params: dict, xs: jax.Array, group_sizes: jax.Array,
                     act: str) -> jax.Array:
    from repro.kernels import ops

    return ops.fused_grouped_ffn(xs, _expert_ws(params, act), params["wo"],
                                 group_sizes, act)


RAGGED_FNS: dict[str, Callable] = {
    # "einsum" = the XLA grouped-GEMM primitive (ragged_dot), matching the
    # batched-XLA-GEMMs contract of EXPERT_FNS["einsum"] on this path
    "einsum": functools.partial(ragged_ffn_two_pass, impl="xla"),
    "pallas": ragged_ffn_two_pass,
    "fused": ragged_ffn_fused,
}


# ---------------------------------------------------------------------------
# Layer init
# ---------------------------------------------------------------------------


def fmoe_init(rng: jax.Array, d_model: int, cfg: MoEConfig, *, act: str = "swiglu",
              d_ff_dense: int = 0, dtype=jnp.float32) -> dict:
    """Parameters for one MoE FFN block."""
    ks = jax.random.split(rng, 4)
    params = {
        "router": router_init(ks[0], d_model, cfg, dtype=jnp.float32),
        "experts": _ffn_init(ks[1], cfg.num_experts, d_model,
                             cfg.d_expert_hidden, act, dtype),
    }
    if cfg.num_shared_experts:
        params["shared"] = _ffn_init(
            ks[2], 0, d_model, cfg.num_shared_experts * cfg.d_expert_hidden,
            act, dtype)
    if cfg.dense_residual:
        params["dense"] = _ffn_init(ks[3], 0, d_model, d_ff_dense or cfg.d_expert_hidden,
                                    act, dtype)
    return params


# ---------------------------------------------------------------------------
# Local (single-worker) forward — paper §4 reordering
# ---------------------------------------------------------------------------


def _route_table(place, l2p):
    """The in-graph logical->physical gate-id table for one layer.

    ``l2p`` is the per-layer table threaded through the models' layer scan
    (a traced (E,) int32 array — see models/lm.py); when absent, the shared
    plan's static table applies.  None = identity routing.
    """
    if l2p is not None:
        return jnp.asarray(l2p, jnp.int32)
    if place is not None and not place.is_identity:
        return jnp.asarray(place.logical_to_physical)
    return None


def _axes_size(dist: "DistConfig", axes) -> int:
    """Static number of ranks in the given mesh-axis group (1 if empty)."""
    n = 1
    for a in axes:
        n *= int(dist.mesh.shape[a])
    return n


def _imbalance(owned_load: jax.Array, mp: int, E_local: int) -> jax.Array:
    """max/mean of per-expert-rank received load from an already-global
    physical-order owned-expert load vector (no collective of its own)."""
    per_rank = owned_load.astype(jnp.float32).reshape(mp, E_local).sum(axis=1)
    return per_rank.max() / jnp.maximum(per_rank.mean(), 1e-6)


def _aux_loss(router: dict, x: jax.Array, g, cfg: MoEConfig) -> jax.Array:
    """Balance loss, plus the StableMoE stage-1 distillation term whenever a
    frozen-router-to-be is riding along (its gradient reaches only
    ``w_frozen``, so the live gate is unperturbed)."""
    aux = load_balance_loss(g.probs, g.expert_ids, cfg.num_experts)
    if cfg.router != "frozen" and "w_frozen" in router:
        aux = aux + router_distill_loss(router, x, g)
    return aux


def _ec_route(router: dict, x: jax.Array, cfg: MoEConfig, table):
    """Expert-choice routing shared by the four MoE paths.

    Returns (C, token_idx (E, C) logical order, ti_phys (E, C) physical
    order, weights (E, C), logits).  Uniform exact capacities mean the
    physical grid is a pure row permutation of the logical one.
    """
    C = D.ec_capacity(x.shape[0], cfg.num_experts, cfg.capacity_factor)
    token_idx, weights, _, logits = expert_choice_forward(
        router, x, cfg, capacity=C)
    return C, token_idx, D.ec_to_physical(token_idx, table), weights, logits


def _ec_flat_load(E: int) -> jax.Array:
    """Expert-choice load is flat by construction — every expert takes
    exactly C rows (the LoadMonitor sees imbalance 1.0 and the placement
    planner treats it as a no-replan signal)."""
    return jnp.full((E,), 1.0 / E, jnp.float32)


def _moe_local(x: jax.Array, router: dict, experts: dict, cfg: MoEConfig,
               act: str, expert_fn: Callable, rng=None, placement=None,
               impl: str = "einsum", l2p=None):
    T = x.shape[0]
    table = _route_table(placement, l2p)
    if cfg.router == "expert_choice":
        C, token_idx, ti_phys, ec_w, logits = _ec_route(router, x, cfg, table)
        E = cfg.num_experts
        if cfg.dispatch == "ragged":
            # the degenerate uniform-ragged case: group_sizes == C everywhere
            xs = x[ti_phys.reshape(-1)]  # (E*C, d) physical-expert-major
            ys = RAGGED_FNS[impl](experts, xs,
                                  jnp.full((E,), C, jnp.int32), act)
            out = ys.reshape(E, C, -1)
        else:
            out = expert_fn(experts, x[ti_phys], act)  # (E, C, dout)
        if table is not None:
            out = out[table]  # combine in logical order (bitwise invariant)
        y = D.combine_ec(out, token_idx, ec_w, T)
        metrics = MoEMetrics(jnp.zeros(()), router_z_loss(logits),
                             _ec_flat_load(E), jnp.zeros(()),
                             obs_counters.local_counters(dropped=jnp.zeros(())))
        return y, metrics
    g = route_tokens(router, x, cfg, rng=rng)
    expert_ids = g.expert_ids
    if table is not None:
        # experts arrive in the plan's physical order; route through the
        # logical->physical index table (routing semantics unchanged)
        expert_ids = table[expert_ids]
    if cfg.dispatch == "ragged":
        plan = D.make_ragged_plan(expert_ids, cfg.num_experts)
        xs = D.dispatch_ragged(x, plan)  # (T*k, d) expert-sorted
        # impl is a first-class axis here too: the grouped kernels take
        # variable group sizes directly, so "fused" runs the fused fwd+bwd
        # on the dropless path (no capacity padding, no (M, H) in HBM)
        ys = RAGGED_FNS[impl](experts, xs, plan.group_sizes, act)
        y = D.combine_ragged(ys, plan, g.combine_weights)
        load, drop = load_metrics(plan.group_sizes, None, T * cfg.top_k)
    else:
        C = D.expert_capacity(T, cfg.num_experts, cfg.top_k, cfg.capacity_factor)
        plan = D.make_capacity_plan(expert_ids, cfg.num_experts, C)
        buf = D.dispatch_capacity(x, plan, cfg.num_experts)  # scatter (Fig 4)
        out = expert_fn(experts, buf, act)  # batched per-expert GeMM
        y = D.combine_capacity(out, plan, g.combine_weights)  # gather
        load, drop = load_metrics(plan.load, plan.keep, T * cfg.top_k)
    if table is not None:
        load = load[table]  # logical order
    metrics = MoEMetrics(_aux_loss(router, x, g, cfg),
                         router_z_loss(g.logits), load, drop,
                         obs_counters.local_counters(
                             dropped=drop * (T * cfg.top_k)))
    return y, metrics


# ---------------------------------------------------------------------------
# Distributed forward — paper §3.2 global data exchange
# ---------------------------------------------------------------------------


def _moe_a2a(x, router, experts, extra, shadow, l2p, cfg: MoEConfig, act,
             expert_fn, dist: DistConfig, impl: str = "einsum", rng=None):
    """Tokens sharded over all mesh axes; experts sharded over ``expert_axis``.

    Per-rank: gate -> dispatch into (E, C, d) -> all-to-all over the expert
    axis -> local experts compute on (E_local, mp*C, d) -> reverse all-to-all
    -> combine.  The Fig-2 "exchange sizes" step survives as the counts
    all-to-all feeding the load monitor.

    With ``dist.overlap_chunks > 1`` the payload exchange runs as the §5.2
    smart schedule instead: capacity micro-shards whose ppermute-decomposed
    sends/returns pipeline with the expert compute (repro.core.pipeline) —
    bit-exact vs the serial schedule.  ``dist.wire_dtype`` casts payloads
    across the wire on either path.

    With a ``dist.placement``, ``experts`` hold only the *owned* physical
    slots and ``shadow`` the replicated hot experts: gate ids go through the
    plan's index table, owned buffer rows take the (possibly shrunk) a2a,
    and shadowed rows are computed locally from the broadcast ``shadow``
    weights — skipped in the exchanged payload entirely.
    """
    from repro.placement.shadow import merge_outputs, shadow_spec, split_buffer

    ax = dist.expert_axis
    mp = dist.expert_parallelism
    E = cfg.num_experts
    t, d = x.shape
    place = dist.placement
    if place is not None and place.is_identity and l2p is None:
        place = None
    table = _route_table(place, l2p)

    ec = cfg.router == "expert_choice"
    if ec:
        # experts pick tokens: exact uniform capacities, the (E, C, d) buffer
        # is a plain gather and the exchange machinery below runs unchanged
        C, token_idx, ti_phys, ec_w, ec_logits = _ec_route(router, x, cfg,
                                                           table)
        g = plan = None
        spec = shadow_spec(place, E, C)
        # the planner's capacity shrink prices padded a2a bytes; EC buffers
        # are exactly sized, so a shrink would only drop — restore C for all
        spec = spec._replace(main_capacity=C, shadow_capacity=C)
        buf = x[ti_phys]  # (E, C, d)
        assigned = jnp.full((E,), C, jnp.int32)
    else:
        if rng is not None:
            for a_ in dist.token_axes:
                rng = jax.random.fold_in(rng, jax.lax.axis_index(a_))
        g = route_tokens(router, x, cfg, rng=rng)
        C = D.expert_capacity(t, E, cfg.top_k, cfg.capacity_factor)
        spec = shadow_spec(place, E, C)
        expert_ids = g.expert_ids
        if table is not None:
            expert_ids = table[expert_ids]
        if place is not None:
            plan = D.make_capacity_plan(expert_ids, E,
                                        tuple(int(c) for c in spec.capacities))
        else:
            plan = D.make_capacity_plan(expert_ids, E, C)
        buf = D.dispatch_capacity(x, plan, E)  # (E, width, d)
        assigned = plan.load
    E_ns = spec.num_owned  # physical slots [0, E_ns) take the a2a
    E_local = E_ns // mp
    Cm = spec.main_capacity
    buf, buf_shadow = split_buffer(buf, spec)

    # ---- global data exchange (Fig 2), owned experts only ----
    n_chunks = pipeline.resolve_chunks(dist.overlap_chunks or 1, Cm)
    counts = assigned[:E_ns].reshape(mp, E_local)
    # §5.2 follow-on: with chunking the counts exchange decomposes into
    # ppermutes too, so the pipelined HLO has no blocking all-to-all at all
    incoming = pipeline.counts_all_to_all(counts, ax, mp,
                                          decompose=n_chunks > 1)  # per-src
    wire = dist.wire_jnp_dtype

    def compute(b):
        # b: (E_local, rows, d) row-independent expert compute
        if dist.tp_axis:
            # Expert-internal TP: expert hidden dims stay sharded over
            # tp_axis (no per-layer FSDP weight all-gather / grad
            # reduce-scatter).  Different tp ranks hold different tokens, so
            # gather tokens first and reduce-scatter the partial outputs
            # back to own shard.
            b = jax.lax.all_gather(b, dist.tp_axis, axis=1, tiled=True)
            o = expert_fn(experts, b, act)  # partial over hidden shards
            return jax.lax.psum_scatter(o, dist.tp_axis, scatter_dimension=1,
                                        tiled=True)
        return expert_fn(experts, b, act)

    # §5.2 smart schedule: pipeline the exchange with expert compute in
    # capacity micro-shards; shadowed experts fill the first wire bubble.
    # n_chunks == 1 runs the same helper as one serial exchange each way.
    fill_fn = (lambda: expert_fn(shadow, buf_shadow, act)) if shadow else None
    out, out_shadow = pipeline.pipelined_expert_exchange(
        buf.reshape(mp, E_local, Cm, d), ax, mp, n_chunks, compute,
        fill_fn=fill_fn, wire_dtype=wire, decompose=n_chunks > 1)
    out = out.reshape(E_ns, Cm, -1)

    # ---- shadowed hot experts: every rank, own tokens, zero a2a bytes ----
    out = merge_outputs(out, out_shadow, spec)
    if ec:
        out_log = out if table is None else out[table]
        y = D.combine_ec(out_log, token_idx, ec_w, t)
    else:
        y = D.combine_capacity(out, plan, g.combine_weights)

    # shared-expert / dense-residual FFNs on the LOCAL token shard with
    # replicated weights — avoids the full-token replication SPMD falls back
    # to when these cross the shard_map boundary (§Perf fix)
    for p in extra.values():
        y = y + dense_ffn(p, x, act)

    # ---- metrics: the Fig-2 counts exchange feeds the load monitor ----
    axes = tuple(dist.token_axes)
    other_axes = tuple(a for a in axes if a not in dist.expert_axes)
    recv_local = incoming.sum(0)  # (E_local,) tokens arriving at my experts
    load_global = jax.lax.all_gather(recv_local, ax, tiled=True)  # (E_ns,)
    if other_axes:
        load_global = jax.lax.psum(load_global, other_axes)
    if spec.num_shadow:
        # shadowed experts never cross the wire; their global load is the
        # psum of local assignment counts over every token-holding axis
        shadow_load = jax.lax.psum(assigned[E_ns:], axes)
        load_global = jnp.concatenate([load_global,
                                       shadow_load.astype(load_global.dtype)])
    if dist.obs:
        # telemetry derived BEFORE the logical-order gather: the owned
        # physical slots [0, E_ns) are what the exchange actually moved
        imbalance = _imbalance(load_global[:E_ns], mp, E_local)
        shadow_hits = (shadow_load.astype(jnp.float32).sum()
                       if spec.num_shadow else jnp.zeros(()))
    if table is not None:
        # back to logical expert order for the monitor
        load_global = load_global[table]
    load, _ = load_metrics(load_global, None,
                           jnp.maximum(load_global.sum(), 1))
    if ec:
        drop = jnp.zeros(())  # exact capacities: nothing to drop
    else:
        _, drop = load_metrics(plan.load, plan.keep, t * cfg.top_k)
    drop_pm = jax.lax.pmean(drop, axes)
    if dist.obs:
        obs = obs_counters.exchange_counters(
            frac=pipeline.wire_fraction(mp, decompose=n_chunks > 1),
            fwd_rows=E_ns * Cm, d_in=d, in_dtype=x.dtype,
            ret_rows=E_ns * Cm, d_out=out.shape[-1], out_dtype=out.dtype,
            counts_elems=E_ns, wire_dtype=wire,
            dropped=drop_pm * (t * cfg.top_k * _axes_size(dist, axes)),
            shadow_hits=shadow_hits, imbalance=imbalance)
    else:
        obs = ObsCounters.zero()
    metrics = MoEMetrics(
        jnp.zeros(()) if ec
        else jax.lax.pmean(_aux_loss(router, x, g, cfg), axes),
        jax.lax.pmean(router_z_loss(ec_logits if ec else g.logits), axes),
        load,
        drop_pm,
        obs,
    )
    return y, metrics


def _moe_a2a_ragged(x, router, experts, extra, shadow, l2p, cfg: MoEConfig,
                    act, expert_fn, dist: DistConfig, impl: str = "einsum",
                    rng=None):
    """Dropless (ragged) expert parallelism — the load-sized exchange.

    Where the capacity path pads every expert to C rows before the wire,
    this path moves the rank's expert-*sorted* rows in per-peer shards:

      1. counts all-to-all — each rank tells peer p how many rows it routed
         to each of p's experts (the Fig-2 "exchange sizes" step, now load-
         bearing instead of monitor-only);
      2. payload exchange — sorted rows scattered into ``(mp, bound, d)``
         pad-to-max-per-peer shards (``dist.ragged_bound``; default
         T_local*k never drops), each shard a ppermute-decomposable
         micro-shardable exchange (core/pipeline), wire-cast per
         ``dist.wire_dtype``;
      3. the receiver compacts the valid prefixes (lengths = received
         counts) into one expert-sorted array and runs the grouped ragged
         kernels (RAGGED_FNS[impl] — einsum/pallas/fused, incl. the fused
         fwd+bwd kernel with its variable/empty group support);
      4. the return exchange inverts the permutation (tiled a2a is its own
         inverse) and ``combine_ragged`` applies the gate weights.

    Shadowed hot experts (dist.placement) never cross the wire: their rows
    are the sorted array's tail segment, computed locally from the broadcast
    ``shadow`` weights inside the first chunk's wire bubble.
    """
    from repro.core import comm

    del expert_fn  # the grouped ragged kernels (RAGGED_FNS[impl]) apply
    ax = dist.expert_axis
    mp = dist.expert_parallelism
    E = cfg.num_experts
    t, d = x.shape
    place = dist.placement
    if place is not None and place.is_identity and l2p is None:
        place = None
    table = _route_table(place, l2p)

    E_ns = E  # physical slots [0, E_ns) take the a2a; the rest are shadowed
    if place is not None:
        E_ns = place.num_owned
    E_local = E_ns // mp
    ec = cfg.router == "expert_choice"
    if ec:
        # exact capacities = the degenerate uniform-ragged case: the sorted
        # rows are the gathered (E, C) token grid flattened physical-major,
        # with group_sizes == C everywhere — the exchange runs unchanged
        C, token_idx, ti_phys, ec_w, ec_logits = _ec_route(router, x, cfg,
                                                           table)
        g = plan = None
        n = E * C
        gs_phys = jnp.full((E,), C, jnp.int32)
        x_sorted = x[ti_phys.reshape(-1)]  # (n, d)
    else:
        if rng is not None:
            for a_ in dist.token_axes:
                rng = jax.random.fold_in(rng, jax.lax.axis_index(a_))
        g = route_tokens(router, x, cfg, rng=rng)
        expert_ids = g.expert_ids
        if table is not None:
            expert_ids = table[expert_ids]
        n = t * cfg.top_k
        plan = D.make_ragged_plan(expert_ids, E)  # full physical-order sort
        gs_phys = plan.group_sizes
        x_sorted = D.dispatch_ragged(x, plan)  # (n, d)
    B = dist.ragged_bound or n
    xplan = D.make_ragged_xplan(gs_phys, n, E_ns, mp, B)
    send = (jnp.zeros((mp * B, d), x.dtype)
            .at[xplan.send_dest].set(x_sorted, mode="drop")
            .reshape(mp, B, d))

    # shadow filler: the sorted tail [num_owned_rows, n) shifted to offset 0
    # (an exchange-free grouped-FFN call issued inside the first wire bubble)
    fill_fn = None
    shadow_dest = None
    if shadow:
        i = jnp.arange(n, dtype=jnp.int32)
        shadow_dest = jnp.where(i >= xplan.num_owned_rows,
                                i - xplan.num_owned_rows, n).astype(jnp.int32)
        xs_sh = jnp.zeros((n, d), x.dtype).at[shadow_dest].set(x_sorted,
                                                               mode="drop")
        fill_fn = lambda: RAGGED_FNS[impl](shadow, xs_sh,
                                           gs_phys[E_ns:], act)

    wire = dist.wire_jnp_dtype
    node_ax = dist.node_axis
    n_nodes = int(dist.mesh.shape[node_ax]) if node_ax in dist.expert_axes \
        else 1
    hier = 1 < n_nodes < mp
    agg_dropped = None
    if not hier:
        n_chunks = pipeline.resolve_chunks(dist.overlap_chunks or 1, B)
        recv, incoming, fill_out = comm.exchange_ragged(
            send, xplan.peer_counts, ax, mp, n_chunks=n_chunks,
            wire_dtype=wire, fill_fn=fill_fn)

        # compact the valid shard prefixes into expert-sorted rows (src-major
        # within an expert = global token order for contiguous token shards)
        cplan, gs_local = D.ragged_recv_compact(incoming, B)
        xs = (jnp.zeros((mp * B, d), x.dtype)
              .at[cplan].set(recv.reshape(mp * B, d), mode="drop"))
        ys = RAGGED_FNS[impl](experts, xs, gs_local, act)
        out = ys.at[cplan].get(mode="fill", fill_value=0)  # to shard slots

        ret = comm.return_ragged(out.reshape(mp, B, -1), ax, mp,
                                 n_chunks=n_chunks, wire_dtype=wire)
    else:
        # ---- two-level exchange: aggregate on the node, slim across it ----
        if dist.expert_axes[0] != node_ax:
            raise ValueError(
                f"node_axis {node_ax!r} must lead expert_axes "
                f"{dist.expert_axes!r} (ranks are node-major)")
        inner_axes = tuple(a for a in dist.expert_axes if a != node_ax)
        inner_ax = inner_axes[0] if len(inner_axes) == 1 else inner_axes
        n_inner = mp // n_nodes
        IB = dist.inter_bound or n_inner * B  # slim shard rows (0 = no-drop)
        # only the slow inter-node leg is chunked/pipelined; the node-local
        # hops ride the fast links serially (and decomposed alongside)
        n_chunks = pipeline.resolve_chunks(dist.overlap_chunks or 1, IB)
        decomp = n_chunks > 1
        shards, cnt_agg = comm.exchange_ragged_intra(
            send.reshape(n_nodes, n_inner, B, d),
            xplan.peer_counts.reshape(n_nodes, n_inner, E_local),
            inner_ax, n_inner, decompose=decomp, wire_dtype=wire)
        aplan = D.make_hier_agg(cnt_agg, B, IB)
        agg_dropped = aplan.dropped
        slim = (jnp.zeros((n_nodes * IB, d), x.dtype)
                .at[aplan.agg_dest].set(
                    shards.reshape(n_nodes * n_inner * B, d), mode="drop")
                .reshape(n_nodes, IB, d))
        if decomp and impl in ("pallas", "fused"):
            # per-received-chunk expert compute: each inter chunk's counts
            # are known before its payload lands, so the grouped kernels run
            # on chunk c while chunk c+1 is in flight.  Gated to the Pallas
            # kernels: they accumulate group-relative and stay bitwise under
            # regrouping, XLA's ragged einsum does not (see _moe_psum).
            # Forward values are bitwise-identical to the serial compute;
            # the backward would NOT be (splitting the grouped-GEMM weight
            # -grad accumulation across chunks reassociates the f32 sums),
            # so a custom_vjp pins the backward to the serial leg's VJP —
            # both directions stay bit-exact vs. the flat exchange.
            w_rows = IB // n_chunks
            dt = x.dtype
            incoming = pipeline.counts_all_to_all(
                aplan.kept_counts.reshape(n_nodes, n_inner * E_local),
                node_ax, n_nodes, decompose=True).reshape(cnt_agg.shape)
            cplan, gs_local = D.ragged_recv_compact_hier(incoming, IB)
            cdest, cgs = D.hier_chunk_plans(incoming, IB, n_chunks)

            def _serial_leg(ex, slim_, cplan_, gs_):
                recv = pipeline.chunked_all_to_all(
                    slim_, node_ax, n_nodes, n_chunks, wire_dtype=wire,
                    decompose=True)
                xs = (jnp.zeros((n_nodes * IB, d), dt)
                      .at[cplan_].set(recv.reshape(n_nodes * IB, d),
                                      mode="drop"))
                ys_ = RAGGED_FNS[impl](ex, xs, gs_, act)
                out_ = ys_.at[cplan_].get(mode="fill", fill_value=0)
                return pipeline.chunked_all_to_all(
                    out_.reshape(n_nodes, IB, -1), node_ax, n_nodes,
                    n_chunks, wire_dtype=wire, decompose=True)

            # plan arrays ride as explicit primals (jax 0.4.x custom_vjp
            # rejects closed-over tracers); their cotangents are float0
            @jax.custom_vjp
            def _inter_leg(ex, slim_, cplan_, gs_, cdest_, cgs_):
                def chunk_fn(rc, c):
                    mini = (jnp.zeros((n_nodes * w_rows, d), dt)
                            .at[cdest_[c]].set(
                                rc.reshape(n_nodes * w_rows, d), mode="drop"))
                    ys_c = RAGGED_FNS[impl](ex, mini, cgs_[c], act)
                    return (ys_c.at[cdest_[c]].get(mode="fill", fill_value=0)
                            .reshape(n_nodes, w_rows, -1))
                ret_, _ = pipeline.hier_ragged_pipeline(
                    slim_, node_ax, n_nodes, n_chunks, chunk_fn,
                    wire_dtype=wire)
                return ret_

            def _inter_fwd(ex, slim_, cplan_, gs_, cdest_, cgs_):
                return (_inter_leg(ex, slim_, cplan_, gs_, cdest_, cgs_),
                        (ex, slim_, cplan_, gs_, cdest_, cgs_))

            def _inter_bwd(res, g):
                ex, slim_, cplan_, gs_, cdest_, cgs_ = res
                _, vjp = jax.vjp(
                    lambda e, s: _serial_leg(e, s, cplan_, gs_), ex, slim_)
                d_ex, d_slim = vjp(g)
                f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
                return (d_ex, d_slim, f0(cplan_), f0(gs_), f0(cdest_),
                        f0(cgs_))

            _inter_leg.defvjp(_inter_fwd, _inter_bwd)
            fill_out = fill_fn() if fill_fn is not None else None
            ret_slim = _inter_leg(experts, slim, cplan, gs_local, cdest, cgs)
        else:
            recv, incoming, fill_out = comm.exchange_ragged_inter(
                slim, aplan.kept_counts, node_ax, n_nodes, n_chunks=n_chunks,
                wire_dtype=wire, fill_fn=fill_fn)
            cplan, gs_local = D.ragged_recv_compact_hier(incoming, IB)
            xs = (jnp.zeros((n_nodes * IB, d), x.dtype)
                  .at[cplan].set(recv.reshape(n_nodes * IB, d), mode="drop"))
            ys = RAGGED_FNS[impl](experts, xs, gs_local, act)
            out = ys.at[cplan].get(mode="fill", fill_value=0)
            ret_slim = comm.return_ragged_inter(
                out.reshape(n_nodes, IB, -1), aplan.kept_counts, incoming,
                node_ax, n_nodes, n_chunks=n_chunks, wire_dtype=wire)
        # de-aggregate (outputs back to padded sibling shards), then invert
        # the intra hop — ret lands in the flat (mp, B) shard layout
        d_out = ret_slim.shape[-1]
        padded = (ret_slim.reshape(n_nodes * IB, d_out)
                  .at[aplan.agg_dest].get(mode="fill", fill_value=0)
                  .reshape(n_nodes, n_inner, B, d_out))
        ret = comm.return_ragged_intra(
            padded, inner_ax, n_inner, decompose=decomp,
            wire_dtype=wire).reshape(mp, B, d_out)
    y_sorted = (ret.reshape(mp * B, -1)
                .at[xplan.send_dest].get(mode="fill", fill_value=0))
    if shadow:
        y_sorted = y_sorted + fill_out.at[shadow_dest].get(mode="fill",
                                                           fill_value=0)
    if ec:
        out_grid = y_sorted.reshape(E, C, -1)
        out_log = out_grid if table is None else out_grid[table]
        y = D.combine_ec(out_log, token_idx, ec_w, t)
    else:
        y = D.combine_ragged(y_sorted, plan, g.combine_weights)

    for p in extra.values():  # see _moe_a2a (§Perf residual fix)
        y = y + dense_ffn(p, x, act)

    # ---- metrics: global assigned load + bound-overflow drops ----
    axes = tuple(dist.token_axes)
    load_global = jax.lax.psum(gs_phys, axes)
    if dist.obs:
        # physical order: owned slots [0, E_ns) took the exchange, the tail
        # [E_ns, E) are shadowed hot experts served locally on every rank
        imbalance = _imbalance(load_global[:E_ns], mp, E_local)
        shadow_hits = (load_global[E_ns:].astype(jnp.float32).sum()
                       if E_ns < E else jnp.zeros(()))
    if table is not None:
        load_global = load_global[table]
    load, _ = load_metrics(load_global, None,
                           jnp.maximum(load_global.sum(), 1))
    dropped = (xplan.num_owned_rows - xplan.keep.sum()).astype(jnp.float32)
    drop_pm = jax.lax.pmean(dropped / n, axes)
    if agg_dropped is not None:
        # rows the forwarding agents truncated at the inter bound — summed
        # over agents (= ranks), normalized to the same global fraction
        drop_pm = drop_pm + (jax.lax.psum(agg_dropped, axes)
                             / (n * _axes_size(dist, axes)))
    if dist.obs:
        dropped_global = drop_pm * (n * _axes_size(dist, axes))
        if hier:
            obs = obs_counters.hier_exchange_counters(
                intra_frac=pipeline.wire_fraction(n_inner, decompose=decomp),
                inter_frac=pipeline.wire_fraction(n_nodes, decompose=decomp),
                intra_rows=mp * B, inter_rows=n_nodes * IB,
                d_in=d, in_dtype=x.dtype, d_out=ret.shape[-1],
                out_dtype=ret.dtype, counts_elems=E_ns, wire_dtype=wire,
                dropped=dropped_global, shadow_hits=shadow_hits,
                imbalance=imbalance)
        else:
            obs = obs_counters.exchange_counters(
                frac=pipeline.wire_fraction(mp, decompose=n_chunks > 1),
                fwd_rows=mp * B, d_in=d, in_dtype=x.dtype,
                ret_rows=mp * B, d_out=ret.shape[-1], out_dtype=ret.dtype,
                counts_elems=E_ns, wire_dtype=wire,
                dropped=dropped_global,
                shadow_hits=shadow_hits, imbalance=imbalance)
    else:
        obs = ObsCounters.zero()
    metrics = MoEMetrics(
        jnp.zeros(()) if ec
        else jax.lax.pmean(_aux_loss(router, x, g, cfg), axes),
        jax.lax.pmean(router_z_loss(ec_logits if ec else g.logits), axes),
        load,
        drop_pm,
        obs,
    )
    return y, metrics


def _moe_psum(x, router, experts, extra, shadow, l2p, cfg: MoEConfig, act,
              expert_fn, dist: DistConfig, impl: str = "einsum", rng=None):
    """Tokens NOT sharded over the expert axis (decode): every rank gates all
    its tokens, computes only its local experts, partial outputs psum over the
    expert axis.  No all-to-all; communication = one psum of (t, d).

    ``cfg.dispatch == "ragged"`` swaps the capacity buffers for the sorted
    dropless layout: the rank's local experts own one contiguous segment of
    the expert-sorted rows (shifted to offset 0, grouped kernels on variable
    sizes), so the psum mode is dropless too — the dispatch × dist matrix
    has no capacity-only corner left.

    A ``dist.placement`` is honored in full (the ROADMAP's "placement-aware
    psum (decode) shadowing"): gate ids go through the plan's table, owned
    experts are permuted into load-balanced per-rank blocks, and shadowed
    hot experts are *skipped in the psum reduction* — every model-axis rank
    computes them on its own (identical) tokens from the replicated
    ``shadow`` weights, and their contribution is added locally after the
    psum.  There is no wire saving here (the psum payload is (t, d) either
    way); the win is the decode critical path: without shadowing the rank
    owning a hot expert serializes the whole reduction, with it the hot
    compute is replicated and the residual owned load greedy-balanced.
    Bitwise-identical to the unshadowed reduction under the same layout:
    whenever a placement is engaged, per-slot contributions reduce across
    ranks *before* the fixed-order k-sum (dispatch.combine_capacity_slots),
    so no rounding ever observes which rank served a slot — toggling
    ``num_shadow`` or permuting experts cannot move the output by even an
    ulp.  The plain (no-placement) path keeps the cheaper combined (t, d)
    psum — slot-wise reduction costs top_k x the payload, which the tiny
    decode reduction absorbs but the training psum *fallback* (large t)
    should not pay for nothing — so placed vs plain differs by combine
    rounding order (ulp), never semantics.  One further exception: ragged
    dispatch under the "einsum" impl, whose XLA ragged_dot lowering is
    group-structure-sensitive (ulp-level); the tile-aligned pallas/fused
    kernels accumulate group-relative and stay bitwise.

    The planner's ``capacity_scale`` shrink prices a2a bytes; there is no
    wire here, so a shrunk owned buffer would only add drop risk — the
    capacity branch always restores the full per-expert capacity.
    """
    from repro.placement.shadow import (merge_outputs, shadow_only,
                                        shadow_spec, split_buffer)

    ax = dist.expert_axis
    mp = dist.expert_parallelism
    E = cfg.num_experts
    t = x.shape[0]
    place = dist.placement
    if place is not None and place.is_identity and l2p is None:
        place = None
    table = _route_table(place, l2p)

    rank = 0  # row-major rank within the (possibly tuple) expert axis group
    for a in dist.expert_axes:
        rank = rank * dist.mesh.shape[a] + jax.lax.axis_index(a)
    if cfg.router == "expert_choice":
        return _moe_psum_ec(x, router, experts, extra, shadow, table, rank,
                            cfg, act, expert_fn, dist, impl)
    if rng is not None:
        for a_ in dist.token_axes:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(a_))
    g = route_tokens(router, x, cfg, rng=rng)
    expert_ids = g.expert_ids
    if table is not None:
        expert_ids = table[expert_ids]
    # layout-invariant slot-wise reduction only when a placement is engaged;
    # the plain path keeps the k-fold-cheaper combined psum (see docstring)
    slotwise = table is not None or bool(shadow)
    if cfg.dispatch == "ragged":
        E_ns = place.num_owned if place is not None else E
        E_local = E_ns // mp
        n = t * cfg.top_k
        plan = D.make_ragged_plan(expert_ids, E)
        x_sorted = D.dispatch_ragged(x, plan)  # (n, d)
        offs = jnp.cumsum(plan.group_sizes) - plan.group_sizes  # exclusive
        gs_local = jax.lax.dynamic_slice_in_dim(plan.group_sizes,
                                                rank * E_local, E_local)
        lo = offs[rank * E_local]
        i = jnp.arange(n, dtype=jnp.int32)
        mine = (i >= lo) & (i < lo + gs_local.sum())
        dest = jnp.where(mine, i - lo, n).astype(jnp.int32)  # shift to 0
        xs = jnp.zeros((n, x.shape[1]), x.dtype).at[dest].set(x_sorted,
                                                              mode="drop")
        ys = RAGGED_FNS[impl](experts, xs, gs_local, act)
        y_sorted = ys.at[dest].get(mode="fill", fill_value=0)
        if slotwise:
            # per-slot contributions psum BEFORE the fixed-order k-sum:
            # bitwise-invariant to the expert layout (see
            # dispatch.combine_capacity_slots)
            c = jax.lax.psum(
                D.combine_ragged_slots(y_sorted, plan, g.combine_weights), ax)
            psum_elems, psum_dtype = c.size, c.dtype
            if shadow:
                # shadow rows = the sorted tail [num_owned_rows, n), shifted
                # to offset 0 — computed on every rank, excluded from the psum
                lo_sh = offs[E_ns] if E_ns < E else jnp.int32(n)
                dest_sh = jnp.where(i >= lo_sh, i - lo_sh, n).astype(jnp.int32)
                xs_sh = jnp.zeros((n, x.shape[1]), x.dtype).at[dest_sh].set(
                    x_sorted, mode="drop")
                ys_sh = RAGGED_FNS[impl](shadow, xs_sh,
                                         plan.group_sizes[E_ns:], act)
                y_sh = ys_sh.at[dest_sh].get(mode="fill", fill_value=0)
                c = c + D.combine_ragged_slots(y_sh, plan, g.combine_weights)
            y = c.sum(axis=1)
        else:  # plain path: the cheap combined (t, d) psum
            y = jax.lax.psum(
                D.combine_ragged(y_sorted, plan, g.combine_weights), ax)
            psum_elems, psum_dtype = y.size, y.dtype
        plan_load, plan_keep, denom = plan.group_sizes, None, n
    else:
        C = D.expert_capacity(t, E, cfg.top_k, cfg.capacity_factor)
        spec = shadow_spec(place, E, C)
        if spec.main_capacity != C:
            # the planner's capacity shrink prices a2a bytes; there is no
            # wire here, so honoring it would only add decode-time drops
            spec = spec._replace(main_capacity=C)
        E_ns = spec.num_owned
        E_local = E_ns // mp
        if place is not None:
            plan = D.make_capacity_plan(
                expert_ids, E, tuple(int(c) for c in spec.capacities))
        else:
            plan = D.make_capacity_plan(expert_ids, E, C)
        buf = D.dispatch_capacity(x, plan, E)  # (E, width, d)
        buf_main, buf_shadow = split_buffer(buf, spec)
        buf_local = jax.lax.dynamic_slice_in_dim(buf_main, rank * E_local,
                                                 E_local, axis=0)
        out_local = expert_fn(experts, buf_local, act)  # (E_local, Cm, d)
        out_main = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((E_ns, spec.main_capacity, out_local.shape[-1]),
                      out_local.dtype), out_local, rank * E_local, axis=0)
        # shadow slots stay zero in the psum'd buffer; every model-axis rank
        # serves them locally from the replicated weights instead
        out = merge_outputs(out_main, None, spec)
        if slotwise:
            # per-slot contributions reduce across ranks BEFORE the fixed-
            # order k-sum so the result is bitwise-invariant to the expert
            # layout (an in-rank k-sum would FMA-fuse co-located slot pairs
            # into one rounding)
            c = jax.lax.psum(
                D.combine_capacity_slots(out, plan, g.combine_weights), ax)
            psum_elems, psum_dtype = c.size, c.dtype
            if shadow:
                out_sh = expert_fn(shadow, buf_shadow, act)
                c = c + D.combine_capacity_slots(shadow_only(out_sh, spec),
                                                 plan, g.combine_weights)
            y = c.sum(axis=1)
        else:  # plain path: the cheap combined (t, d) psum
            y = jax.lax.psum(D.combine_capacity(out, plan, g.combine_weights),
                             ax)
            psum_elems, psum_dtype = y.size, y.dtype
        plan_load, plan_keep, denom = plan.load, plan.keep, t * cfg.top_k
    for p in extra.values():  # see _moe_a2a
        y = y + dense_ffn(p, x, act)

    axes = tuple(dist.token_axes)
    load, drop = load_metrics(plan_load, plan_keep, denom)
    pm = (lambda v: jax.lax.pmean(v, axes)) if axes else (lambda v: v)
    # pmean the PHYSICAL-order load first, telemetry reads it, then gather to
    # logical order — pmean commutes with the replicated-table gather, so the
    # monitor sees bitwise-identical values
    load_pm = pm(load)
    drop_pm = pm(drop)
    if dist.obs:
        n_ranks = _axes_size(dist, axes)
        imbalance = _imbalance(load_pm[:E_ns], mp, E_local)
        shadow_hits = (load_pm[E_ns:].sum() * (denom * n_ranks)
                       if E_ns < E else jnp.zeros(()))
        obs = obs_counters.reduction_counters(
            payload_elems=psum_elems, payload_dtype=psum_dtype,
            dropped=drop_pm * (denom * n_ranks),
            shadow_hits=shadow_hits, imbalance=imbalance)
    else:
        obs = ObsCounters.zero()
    if table is not None:
        load_pm = load_pm[table]  # logical order
    metrics = MoEMetrics(pm(_aux_loss(router, x, g, cfg)),
                         pm(router_z_loss(g.logits)), load_pm, drop_pm, obs)
    return y, metrics


def _moe_psum_ec(x, router, experts, extra, shadow, table, rank,
                 cfg: MoEConfig, act, expert_fn, dist: DistConfig,
                 impl: str = "einsum"):
    """Expert-choice under the psum (decode) mode.

    Tokens are replicated over the expert axis, so every rank routes the
    *global* token set identically — the (E, C) grid is the dense
    reference's, exactly.  Each rank computes only its owned expert rows of
    the grid (zeros elsewhere), partial grids psum over the expert axis
    (disjoint blocks: the reduction adds exact zeros, so the result is
    bitwise the local grid), shadowed experts are computed on every rank
    outside the reduction, and the combine scatter-adds in logical expert
    order — bitwise layout-invariant by the same argument as the slot-wise
    token-choice combine.
    """
    ax = dist.expert_axis
    mp = dist.expert_parallelism
    E = cfg.num_experts
    t, d = x.shape
    C, token_idx, ti_phys, ec_w, ec_logits = _ec_route(router, x, cfg, table)
    place = dist.placement
    E_ns = place.num_owned if place is not None else E
    E_local = E_ns // mp
    if cfg.dispatch == "ragged":
        n = E * C
        x_sorted = x[ti_phys.reshape(-1)]  # (n, d) physical-expert-major
        i = jnp.arange(n, dtype=jnp.int32)
        lo = rank * E_local * C  # my owned segment (uniform C rows/expert)
        mine = (i >= lo) & (i < lo + E_local * C)
        dest = jnp.where(mine, i - lo, n).astype(jnp.int32)  # shift to 0
        xs = jnp.zeros((n, d), x.dtype).at[dest].set(x_sorted, mode="drop")
        ys = RAGGED_FNS[impl](experts, xs,
                              jnp.full((E_local,), C, jnp.int32), act)
        y_rows = jax.lax.psum(
            ys.at[dest].get(mode="fill", fill_value=0), ax)
        psum_elems, psum_dtype = y_rows.size, y_rows.dtype
        if shadow:
            lo_sh = E_ns * C  # sorted tail = shadow rows, shifted to 0
            dest_sh = jnp.where(i >= lo_sh, i - lo_sh, n).astype(jnp.int32)
            xs_sh = jnp.zeros((n, d), x.dtype).at[dest_sh].set(x_sorted,
                                                               mode="drop")
            ys_sh = RAGGED_FNS[impl](shadow, xs_sh,
                                     jnp.full((E - E_ns,), C, jnp.int32), act)
            y_rows = y_rows + ys_sh.at[dest_sh].get(mode="fill", fill_value=0)
        out_grid = y_rows.reshape(E, C, -1)
    else:
        buf = x[ti_phys]  # (E, C, d)
        buf_local = jax.lax.dynamic_slice_in_dim(buf, rank * E_local,
                                                 E_local, axis=0)
        out_local = expert_fn(experts, buf_local, act)  # (E_local, C, dout)
        out = jax.lax.psum(jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((E_ns, C, out_local.shape[-1]), out_local.dtype),
            out_local, rank * E_local, axis=0), ax)
        psum_elems, psum_dtype = out.size, out.dtype
        if E_ns < E:
            # shadowed experts: every rank, same tokens, outside the psum
            out = jnp.concatenate([out, expert_fn(shadow, buf[E_ns:], act)],
                                  axis=0)
        out_grid = out
    out_log = out_grid if table is None else out_grid[table]
    y = D.combine_ec(out_log, token_idx, ec_w, t)
    for p in extra.values():  # see _moe_a2a
        y = y + dense_ffn(p, x, act)

    axes = tuple(dist.token_axes)
    pm = (lambda v: jax.lax.pmean(v, axes)) if axes else (lambda v: v)
    if dist.obs:
        n_ranks = _axes_size(dist, axes)
        shadow_hits = jnp.float32((E - E_ns) * C * n_ranks)
        obs = obs_counters.reduction_counters(
            payload_elems=psum_elems, payload_dtype=psum_dtype,
            dropped=jnp.zeros(()), shadow_hits=shadow_hits,
            imbalance=jnp.ones(()))
    else:
        obs = ObsCounters.zero()
    metrics = MoEMetrics(jnp.zeros(()), pm(router_z_loss(ec_logits)),
                         _ec_flat_load(E), jnp.zeros(()), obs)
    return y, metrics


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def _check_not_per_layer(place) -> None:
    """This function applies ONE layer; a stacked per-layer plan must be
    split upstream (models/lm.py) into geometry + per-layer ``l2p`` tables."""
    if place is None:
        return
    from repro.placement.plan import PerLayerPlacement
    if isinstance(place, PerLayerPlacement):
        raise TypeError(
            "fmoe_apply applies a single layer; split a PerLayerPlacement "
            "into its geometry + per-layer l2p tables (models.lm does this "
            "for the full stack) instead of passing it here")


def fmoe_apply(params: dict, x: jax.Array, cfg: MoEConfig, *, act: str = "swiglu",
               dist: Optional[DistConfig] = None, impl: str = "einsum",
               rng: Optional[jax.Array] = None, placement=None, l2p=None):
    """Apply the MoE FFN to ``x`` of shape (..., d_model).

    Returns ``(y, MoEMetrics)``.  ``impl`` selects the expert kernels
    ("einsum" | "pallas" | "fused") on every dispatch mode — capacity local,
    ragged local and the distributed paths; ``dist=None`` runs the
    single-worker §4 path, otherwise the §3.2 distributed path (mode picked
    by ``dist``).

    ``dist.placement`` is an ExpertPlacement: ``params`` must already be in
    its physical order (repro.placement.migrate); routing stays in logical
    expert space via the plan's index table.  ``dist`` is the single
    distribution-config channel — for the single-worker path pass
    ``DistConfig.local(placement=plan)`` (mesh=None carrier).  The bare
    ``placement=`` kwarg is deprecated: it warns and forwards onto ``dist``.
    ``l2p`` is *this layer's* logical->physical gate-id table (a traced (E,)
    int32 array) when the plan is per-layer: the layer scan in models/lm.py
    splits a ``PerLayerPlacement`` into the shared static geometry (riding
    on ``dist.placement``) plus the stacked tables it threads here — a
    PerLayerPlacement itself must not reach this function.
    """
    if placement is not None:
        import warnings
        warnings.warn(
            "fmoe_apply(placement=...) is deprecated; pass the plan on the "
            "dist channel instead — DistConfig.local(placement=plan) for "
            "the single-worker path, dist._replace(placement=plan) for a "
            "meshed one", DeprecationWarning, stacklevel=2)
    if dist is not None and dist.router is not None and dist.router != cfg.router:
        # the dist channel can pin the routing variant (e.g. serve-time
        # frozen routing) without touching the model config
        import dataclasses
        cfg = dataclasses.replace(cfg, router=dist.router)
    if dist is not None and dist.mesh is None:
        # DistConfig.local carrier: unwrap to the single-worker path
        if placement is None:
            placement = dist.placement
        dist = None
    expert_fn = EXPERT_FNS[impl]
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    router, experts = params["router"], params["experts"]

    residual_keys = [k for k in ("shared", "dense") if k in params]
    if dist is None:
        _check_not_per_layer(placement)
        y, metrics = _moe_local(xf, router, experts, cfg, act, expert_fn, rng,
                                placement=placement, impl=impl, l2p=l2p)
        for k in residual_keys:
            y = y + dense_ffn(params[k], xf, act)
    else:
        place = dist.placement if dist.placement is not None else placement
        if place is not None:
            _check_not_per_layer(place)
            if place.num_experts != cfg.num_experts:
                raise ValueError(
                    f"placement has {place.num_experts} experts, "
                    f"config has {cfg.num_experts}")
            if place.num_ranks != dist.expert_parallelism:
                raise ValueError(
                    f"placement built for {place.num_ranks} ranks, mesh "
                    f"expert parallelism is {dist.expert_parallelism}")
            if place.num_shadow:
                if dist.tp_axis:
                    raise NotImplementedError(
                        "expert shadowing + expert-internal TP")
                if (place.num_owned % dist.expert_parallelism
                        or place.num_owned == 0):
                    raise ValueError(
                        f"owned experts {place.num_owned} must be a positive "
                        f"multiple of {dist.expert_parallelism}")
            dist = dist._replace(placement=place)
        ragged = cfg.dispatch == "ragged"
        if ragged and dist.tp_axis:
            # the grouped ragged kernels consume flat sorted rows; the
            # capacity path's per-row tp gather/scatter doesn't apply
            raise NotImplementedError(
                "ragged dispatch + expert-internal TP (use capacity)")
        if dist.mode == "a2a":
            local = _moe_a2a_ragged if ragged else _moe_a2a
        else:
            local = _moe_psum
        tok_spec = P(dist.token_axes if dist.token_axes else None, None)

        def espec_for(path_w):
            if dist.tp_axis and dist.mode == "a2a":
                # hidden dim stays sharded (expert-internal TP, §Perf)
                if path_w == "wo":
                    return P(dist.expert_axis, dist.tp_axis, None)
                return P(dist.expert_axis, None, dist.tp_axis)
            return P(dist.expert_axis, None, None)
        espec = {k: espec_for(k) for k in experts}

        if dist.fsdp_axis and not dist.tp_axis:
            # keep the bf16 cast *sharded* so XLA gathers half the bytes
            # (otherwise the convert is hoisted after the f32-master gather)
            from jax.sharding import NamedSharding
            fspec = {k: (P(dist.expert_axis, dist.fsdp_axis, None) if k == "wo"
                         else P(dist.expert_axis, None, dist.fsdp_axis))
                     for k in experts}
            experts = {k: jax.lax.with_sharding_constraint(
                v, NamedSharding(dist.mesh, fspec[k]))
                for k, v in experts.items()}

        # shadowed hot experts: slice off the replicated tail (the broadcast
        # happens at the shard_map boundary via the P(None) in_spec)
        shadow = {}
        if dist.placement is not None and dist.placement.num_shadow:
            E_ns = dist.placement.num_owned
            shadow = {k: v[E_ns:] for k, v in experts.items()}
            experts = {k: v[:E_ns] for k, v in experts.items()}
        sspec = {k: P(None, None, None) for k in shadow}

        if dist.constrain_tokens:
            # shared/dense residual FFNs run INSIDE shard_map on local tokens
            # with replicated weights (§Perf fix — see _moe_a2a)
            extra = {k: params[k] for k in residual_keys}
            residual_keys = []
        else:
            extra = {}
        xspec = {k: jax.tree.map(lambda _: P(None, None), v)
                 for k, v in extra.items()}
        has_l2p = l2p is not None
        has_rng = rng is not None

        def fn(xf_, router_, experts_, extra_, shadow_, *rest):
            # optional trailing operands, in order: l2p table, gate rng (the
            # paths fold the rng with their token-axis indices so every
            # shard explores independently)
            _l2p = rest[0] if has_l2p else None
            _rng = rest[int(has_l2p)] if has_rng else None
            return local(xf_, router_, experts_, extra_, shadow_, _l2p,
                         cfg=cfg, act=act, expert_fn=expert_fn, dist=dist,
                         impl=impl, rng=_rng)

        mspec = MoEMetrics(P(), P(), P(None), P(),
                           ObsCounters(P(), P(), P(), P(), P(), P(), P()))
        in_specs = [tok_spec, jax.tree.map(lambda _: P(None, None), router),
                    espec, xspec, sspec]
        operands = [xf, router, experts, extra, shadow]
        if has_l2p:
            # the per-layer gate-id table rides replicated into the region
            operands.append(jnp.asarray(l2p, jnp.int32))
            in_specs.append(P(None))
        if has_rng:
            operands.append(rng)
            in_specs.append(P(None))
        y, metrics = jax.shard_map(
            fn, mesh=dist.mesh,
            in_specs=tuple(in_specs),
            out_specs=(tok_spec, mspec),
            check_vma=False,
        )(*operands)
        # paper-faithful baseline: residuals outside shard_map (auto-sharded)
        for k in residual_keys:
            y = y + dense_ffn(params[k], xf, act)
    return y.reshape(shape), metrics
