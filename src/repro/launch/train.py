"""Training driver: jitted train_step (with optional microbatch gradient
accumulation), sharding-aware jit wiring, and a CLI for real runs.

Usage (example, CPU-scale):
  PYTHONPATH=src python -m repro.launch.train --arch fastmoe-gpt --steps 100 \
      --batch 8 --seq 256 --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, get_config, reduced
from repro.configs.base import ModelConfig
from repro.core.fmoe import DistConfig
from repro.data import SyntheticLM
from repro.launch.mesh import all_axes, data_axes, make_local_mesh
from repro.launch.sharding import batch_spec, tree_shardings
from repro.models import lm
from repro.obs import events as obs_events
from repro.optim import AdamW, warmup_cosine
from repro.resilience import (CheckpointManager, StepGuard, TrainingAborted,
                              faults)


def moe_dist(cfg: ModelConfig, mesh, num_tokens: int, *,
             opts: Optional[dict] = None) -> Optional[DistConfig]:
    """Pick the expert-parallel mode for this (config, mesh, token count).

    a2a (the paper's §3.2 exchange) when tokens split across every axis
    including the expert axis; psum otherwise (decode-time small batches);
    None when the config has no MoE or the mesh has no expert axis.
    ``opts`` toggles the §Perf beyond-paper optimizations (expert_tp,
    constrain_tokens) and may carry an ExpertPlacement or PerLayerPlacement
    under ``placement``, attached on every expert-parallel mode: the a2a
    paths skip shadowed experts on the wire, and the psum (decode) path
    balances owned experts per rank and serves shadowed ones outside the
    reduction (core/fmoe._moe_psum) — params must be in the plan's physical
    order either way.
    """
    opts = opts or {}
    if cfg.moe is None or "model" not in mesh.axis_names:
        return None
    expert_axis = "model"
    if (opts.get("expert_pod") and "pod" in mesh.axis_names
            and cfg.moe.num_experts
            % (mesh.shape["pod"] * mesh.shape["model"]) == 0):
        # §Perf multi-pod: expert parallelism spans pods (no cross-pod
        # expert-gradient sync; the a2a crosses pods instead)
        expert_axis = ("pod", "model")
    node_ax = None
    if ("node" in mesh.axis_names
            and cfg.moe.num_experts
            % (mesh.shape["node"] * mesh.shape["model"]) == 0):
        # hierarchical mesh (launch/mesh make_local_mesh(node=...)): expert
        # parallelism spans (node, model) node-major, and the ragged a2a
        # runs two-level — aggregate intra-node, slim inter-node exchange
        expert_axis = ("node", "model")
        node_ax = "node"
    ep = 1
    for a in (expert_axis if isinstance(expert_axis, tuple) else (expert_axis,)):
        ep *= mesh.shape[a]
    if cfg.moe.num_experts % ep:
        return None
    total = 1
    for a in mesh.axis_names:
        total *= mesh.shape[a]
    rb = opts.get("ragged_bound") or 0
    ib = int(opts.get("inter_bound") or 0)
    if rb == "auto":
        # adaptive bounds: size the static shards to the LoadMonitor's
        # measured peak peer share (drop-guarded; core/monitor
        # suggest_ragged_bound).  A cold monitor resolves to the dropless
        # default; ReplanHook re-jits through here, so every replan
        # re-calibrates the bounds to the current load EMAs.
        mon = opts.get("load_monitor")
        t_local = num_tokens // total if num_tokens % total == 0 else 0
        rb = 0
        if mon is not None and t_local:
            rb = mon.suggest_ragged_bound(t_local, cfg.moe.top_k, ep)
            if rb >= t_local * cfg.moe.top_k:
                rb = 0  # dropless: keep the canonical 0 spelling
            if node_ax and rb and not ib:
                # slim inter-node shards aggregate n_inner source ranks; the
                # peak is still one rank block's share of the pooled rows
                ib = mon.suggest_ragged_bound(
                    t_local * (ep // mesh.shape["node"]), cfg.moe.top_k, ep)
    extra = dict(
        expert_axis=expert_axis,
        tp_axis="data" if opts.get("expert_tp") and "data" in mesh.axis_names else None,
        constrain_tokens=bool(opts.get("constrain_tokens")),
        fsdp_axis="data" if (opts.get("constrain_tokens")
                             and "data" in mesh.axis_names) else None,
        overlap_chunks=int(opts.get("overlap_chunks") or 0),
        wire_dtype=opts.get("wire_dtype") or None,
        ragged_bound=int(rb),
        node_axis=node_ax,
        inter_bound=ib,
    )
    if num_tokens % total == 0:
        return DistConfig(mesh, all_axes(mesh), placement=opts.get("placement"),
                          **extra)
    d_axes = data_axes(mesh)
    dsize = 1
    for a in d_axes:
        dsize *= mesh.shape[a]
    # psum fallbacks: no a2a, so overlap_chunks / wire_dtype don't apply —
    # but a placement does (decode-time shadowing skips hot experts in the
    # psum reduction and serves them locally; see core/fmoe._moe_psum)
    if num_tokens % dsize == 0:
        return DistConfig(mesh, d_axes, expert_axis=expert_axis, tp_axis=None,
                          constrain_tokens=extra["constrain_tokens"],
                          placement=opts.get("placement"))
    return DistConfig(mesh, (), expert_axis=expert_axis, tp_axis=None,
                      constrain_tokens=extra["constrain_tokens"],
                      placement=opts.get("placement"))


def make_train_step(cfg: ModelConfig, opt: AdamW, *, dist=None,
                    num_microbatches: int = 1, warmup: int = 100,
                    total_steps: int = 10000, impl: str = "einsum"):
    """(params, opt_state, batch, step) -> (params, opt_state, metrics).

    ``impl`` picks the expert kernels (einsum | pallas | fused); "fused"
    runs the one-kernel FFN forward AND the fused dX/dW backward, so the
    step never materializes the (M, H) hidden activation in HBM.
    """

    # exploration routers perturb gate selection with a per-step key derived
    # from the step counter (deterministic, resume-stable); every other
    # router stays rng-free so existing runs are bit-identical
    explore = (cfg.moe is not None
               and cfg.moe.router in ("noisy_topk", "gumbel"))

    def grads_of(params, batch, rng=None):
        return jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch, dist=dist, impl=impl,
                                 rng=rng),
            has_aux=True)(params)

    def train_step(params, opt_state, batch, step):
        rng = (jax.random.fold_in(jax.random.PRNGKey(17), step)
               if explore else None)
        if num_microbatches == 1:
            (loss, aux), grads = grads_of(params, batch, rng)
        else:
            def split(x):
                b = x.shape[0] // num_microbatches
                return x.reshape(num_microbatches, b, *x.shape[1:])
            micro = jax.tree.map(split, batch)
            rngs = (jax.random.split(rng, num_microbatches) if explore
                    else jnp.zeros((num_microbatches,), jnp.uint32))

            def body(acc, xs):
                mb, r = xs
                (l, a), g = grads_of(params, mb, r if explore else None)
                return jax.tree.map(jnp.add, acc, (g, l, a)), None

            zero_g = jax.tree.map(jnp.zeros_like, params)
            n_e = cfg.moe.num_experts if cfg.moe is not None else 1
            aux0 = {"ce": jnp.zeros(()), "aux_loss": jnp.zeros(()),
                    "z_loss": jnp.zeros(()), "drop_frac": jnp.zeros(()),
                    "held_frac": jnp.zeros(()),
                    "load": jnp.zeros((n_e,)),
                    "load_layers": jnp.zeros((cfg.stack_layers, n_e)),
                    # obs counters (repro.obs) emitted by loss_fn's aux
                    "wire_elems": jnp.zeros(()), "wire_bytes": jnp.zeros(()),
                    "wire_bytes_intra": jnp.zeros(()),
                    "wire_bytes_inter": jnp.zeros(()),
                    "dropped": jnp.zeros(()), "shadow_hits": jnp.zeros(()),
                    "imbalance": jnp.zeros(())}
            (grads, loss, aux), _ = jax.lax.scan(
                body, (zero_g, jnp.zeros(()), aux0), (micro, rngs))
            inv = 1.0 / num_microbatches
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss, aux = loss * inv, jax.tree.map(lambda a: a * inv, aux)
        lr_scale = warmup_cosine(step, warmup=warmup, total=total_steps)
        params, opt_state, gnorm = opt.update(grads, opt_state, params,
                                              lr_scale=lr_scale)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   "lr_scale": lr_scale, **aux}

    return train_step


def jit_train_step(cfg: ModelConfig, opt: AdamW, mesh, global_batch: int,
                   seq_len: int, *, num_microbatches: int = 1,
                   opts: Optional[dict] = None, placement=None):
    """Fully sharding-annotated jitted train step for ``mesh``.

    ``placement`` re-jits the step under a migrated expert layout (the
    replan hook swaps it while param/opt shardings stay identical).
    """
    from repro.launch.sharding import option_overrides
    opts = dict(opts or {})
    if placement is not None:
        opts["placement"] = placement
    rng = jax.random.PRNGKey(0)
    rcfg = cfg if opts.get("head_aware") else None
    with option_overrides(opts, mesh):
        params_shape = jax.eval_shape(lambda: lm.init_params(rng, cfg))
        pshard = tree_shardings(params_shape, mesh, cfg=rcfg)
        oshard_shape = jax.eval_shape(opt.init, params_shape)
        oshard = tree_shardings(oshard_shape, mesh, cfg=rcfg)
    bspec = {"tokens": jax.sharding.NamedSharding(mesh, batch_spec(global_batch, mesh))}
    if cfg.frontend == "vision":
        bspec["patches"] = jax.sharding.NamedSharding(mesh, batch_spec(global_batch, mesh, 2))
    if cfg.family == "audio":
        bspec["frames"] = jax.sharding.NamedSharding(mesh, batch_spec(global_batch, mesh, 2))
    dist = moe_dist(cfg, mesh, global_batch * seq_len, opts=opts)
    step_fn = make_train_step(cfg, opt, dist=dist,
                              num_microbatches=num_microbatches,
                              impl=opts.get("impl") or "einsum")
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.jit(
        step_fn,
        in_shardings=(pshard, oshard, bspec, rep),
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1),
    ), pshard, oshard


def init_state(cfg: ModelConfig, opt: AdamW, pshard, oshard, *,
               seed: int = 0):
    """Params and optimizer state placed under ``jit_train_step``'s
    shardings.  The optimizer state is made in place, sharded: unsharded,
    the AdamW moments of a four-chip expert-parallel model would not fit on
    the one device that ``opt.init`` would otherwise fill."""
    params = jax.device_put(lm.init_params(jax.random.PRNGKey(seed), cfg),
                            pshard)
    return params, jax.jit(opt.init, out_shardings=oshard)(params)


# ---------------------------------------------------------------------------
# Periodic replan-and-migrate hook (placement subsystem, paper §6 follow-on)
# ---------------------------------------------------------------------------


class ReplanHook:
    """Closes the load-balance loop: LoadMonitor -> PlacementController ->
    migrate params/opt state -> re-jit the train step under the new layout.

    Call :meth:`observe` every step with the step metrics; when the
    controller decides a better placement pays for its migration, the hook
    permutes the live param/optimizer trees (checkpoint-compatible — see
    repro.placement.migrate.to_logical) and returns a freshly jitted step.

    **Rollback** (ISSUE 8): every accepted replan opens a probation window
    (:class:`repro.resilience.ReplanProbation`).  If the post-replan loss
    or drop fraction regresses against the pre-replan EMA baselines, the
    migration is *inverted* — params/opt state permute back to the prior
    placement, the step re-jits under it, and the regressing plan is
    blacklisted in the controller so the cost model can never propose it
    again.  New replans are deferred while a probation is open (one
    experiment at a time).  Pass ``rollback=False`` to opt out.
    """

    def __init__(self, cfg: ModelConfig, opt: AdamW, mesh, global_batch: int,
                 seq_len: int, *, every: int = 200,
                 num_microbatches: int = 1, opts: Optional[dict] = None,
                 per_layer: bool = False, sink=None, rollback: bool = True,
                 probation: Optional[int] = None,
                 probation_loss_tol: float = 1.05,
                 probation_drop_tol: float = 0.05):
        from repro.core.dispatch import expert_capacity
        from repro.core.monitor import LoadMonitor
        from repro.placement import (PlacementController, identity_placement,
                                     load_calibration)

        self.cfg, self.opt, self.mesh = cfg, opt, mesh
        self.global_batch, self.seq_len = global_batch, seq_len
        self.num_microbatches, self.opts = num_microbatches, opts
        self.per_layer = per_layer
        moe = cfg.moe
        n_dev = 1
        for a in mesh.axis_names:
            n_dev *= mesh.shape[a]
        # a plan only executes if moe_dist threads it into the a2a path for
        # this (config, mesh, shape, opts) combo; otherwise migrating would
        # permute params under a step that never remaps gate ids.  Probe with
        # the SAME opts observe() will re-jit with, and size the controller
        # to the probe's actual expert parallelism (expert_pod may widen it).
        probe = moe_dist(cfg, mesh, global_batch * seq_len,
                         opts={**dict(opts or {}),
                               "placement": identity_placement(
                                   moe.num_experts, 1)})
        self.enabled = (probe is not None and probe.placement is not None
                        and probe.mode == "a2a")
        ranks = probe.expert_parallelism if self.enabled else 1
        # per-gate token count: the flat shard _moe_a2a sees per microbatch
        t_local = max(1, global_batch * seq_len // n_dev // num_microbatches)
        cap = expert_capacity(t_local, moe.num_experts, moe.top_k,
                              moe.capacity_factor)
        L = cfg.stack_layers if per_layer else 0
        self.sink = sink  # optional repro.obs MetricsSink (replan events +
        # the monitor's sampled load snapshots land here)
        # updates arrive pre-sampled (every sync_every steps), so record each
        self.monitor = LoadMonitor(moe.num_experts, num_layers=L, sink=sink,
                                   record_every=1 if sink is not None else 0)
        # price plans with bandwidths measured on THIS machine when the
        # benchmark suite has left results behind (v5e roofline otherwise),
        # and with the bytes the wire actually moves under wire_dtype
        constants = load_calibration()
        wire_bytes = 2 if (opts or {}).get("wire_dtype") == "bf16" else 4
        self.controller = PlacementController(
            self.monitor, ranks, d_model=cfg.d_model,
            d_hidden=moe.d_expert_hidden, capacity=cap,
            capacity_factor=moe.capacity_factor,
            every=every if self.enabled else 0, bytes_per_elem=wire_bytes,
            num_layers=L, constants=constants)
        # fetch load to host only on sampled steps: a per-step device_get
        # would serialize host and device for a decision made every `every`
        self.sync_every = max(1, every // 16)
        from repro.resilience import ReplanProbation
        self.probation = (ReplanProbation(
            window=probation if probation else max(4, min(64, every // 4)),
            loss_tol=probation_loss_tol, drop_tol=probation_drop_tol,
            sink=sink) if rollback else None)
        # host-side loss/drop EMAs: the pre-replan baselines probation
        # judges against (fed by observe()'s loss=/drop= kwargs — the train
        # loop already holds those host floats for the step guard)
        self._loss_ema: Optional[float] = None
        self._drop_ema: Optional[float] = None

    @property
    def placement(self):
        return self.controller.current

    def _switch(self, step: int, old, new, params, opt_state, *,
                span: str = "replan"):
        """Re-jit under ``new`` and permute live state from ``old``'s
        physical order into ``new``'s (shared replan/rollback machinery)."""
        from repro.obs import trace as obs_trace
        from repro.placement import migrate

        with obs_trace.span(span, step=step):
            step_fn, pshard, oshard = jit_train_step(
                self.cfg, self.opt, self.mesh, self.global_batch, self.seq_len,
                num_microbatches=self.num_microbatches, opts=self.opts,
                placement=new)
            with obs_trace.span("migrate", step=step):
                params = jax.device_put(migrate(params, old, new), pshard)
                opt_state = jax.device_put(migrate(opt_state, old, new),
                                           oshard)
        return params, opt_state, step_fn

    def observe(self, step: int, metrics: dict, params, opt_state, *,
                loss: Optional[float] = None, drop: Optional[float] = None):
        """Returns (params, opt_state, new_step_fn | None).

        ``loss``/``drop`` are the step's host-side scalars when the caller
        already has them (the guarded train loop does); otherwise they are
        pulled from ``metrics`` where present.  They feed the probation
        baselines — without them rollback judges on whichever metric it has.
        """
        from repro.core.balance import MoEMetrics

        if (self.per_layer and self.controller.every
                and "load_layers" not in metrics and "load" in metrics):
            # fail loudly: falling back to the summed load would leave the
            # (L, E) EMA at its uniform init and the per-layer controller
            # would silently never replan
            raise ValueError(
                "ReplanHook(per_layer=True) needs metrics['load_layers'] "
                "(the (L, E) stack loss_fn emits); got only 'load'")
        if loss is None and "loss" in metrics:
            loss = float(metrics["loss"])
        if drop is None and "drop_frac" in metrics:
            drop = float(metrics["drop_frac"])
        ema = lambda old, v: v if old is None else 0.9 * old + 0.1 * v
        if loss is not None:
            self._loss_ema = ema(self._loss_ema, loss)
        if drop is not None:
            self._drop_ema = ema(self._drop_ema, drop)
        load_key = "load_layers" if self.per_layer else "load"
        if (load_key in metrics and self.controller.every
                and step % self.sync_every == 0):
            # device_get lands here (and only here) when metrics are device
            # arrays: the monitor EMA samples every sync_every-th step.
            # per-layer mode feeds the stacked (L, E) loads from loss_fn's
            # aux so each layer's skew drives its own plan.
            m = MoEMetrics(0.0, 0.0,
                           jax.device_get(metrics[load_key]),
                           jax.device_get(metrics.get("drop_frac", 0.0)))
            self.monitor.update(m)
        if self.probation is not None and self.probation.active:
            decision = self.probation.observe(step, loss=loss, drop=drop)
            if decision.rollback:
                params, opt_state, step_fn = self._switch(
                    step, decision.new_plan, decision.old_plan, params,
                    opt_state, span="replan_rollback")
                self.controller.rollback(decision.old_plan, decision.new_plan)
                print(f"step {step:5d} replan ROLLBACK: {decision.reason} "
                      f"(plan blacklisted)")
                return params, opt_state, step_fn
            if self.probation.active:  # still on probation: defer replans
                return params, opt_state, None
        old = self.controller.current
        new = self.controller.maybe_replan(step)
        if new is None:
            return params, opt_state, None
        params, opt_state, step_fn = self._switch(step, old, new, params,
                                                  opt_state)
        if self.probation is not None:
            # drop baseline defaults to 0: a replan must not *introduce*
            # drops even if the run never measured any before it
            self.probation.start(
                step, old, new, baseline_loss=self._loss_ema,
                baseline_drop=self._drop_ema if self._drop_ema is not None
                else 0.0)
        if self.sink is not None:
            self.sink.emit({"kind": "replan", "step": step,
                            "num_shadow": int(new.num_shadow),
                            "capacity_scale": float(new.capacity_scale),
                            "imbalance": self.monitor.imbalance})
        return params, opt_state, step_fn


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fastmoe-gpt")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced CPU-scale variant")
    ap.add_argument("--log_every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="",
                    help="DATAxMODEL mesh, e.g. 1x4, or DATAxNODExMODEL, "
                         "e.g. 1x2x4, for the hierarchical two-level ragged "
                         "exchange (requires that many devices; on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--replan_every", type=int, default=0,
                    help="steps between expert-placement replans "
                         "(0 = off; needs --mesh and an MoE arch)")
    ap.add_argument("--per_layer_plans", action="store_true",
                    help="plan expert placement per layer (each layer gets "
                         "its own permutation + shadow set from its own "
                         "measured load; needs --replan_every)")
    ap.add_argument("--overlap_chunks", type=int, default=0,
                    help="§5.2 smart schedule: pipeline the expert all-to-all "
                         "with compute in this many capacity micro-shards "
                         "(0/1 = serial; needs --mesh and an MoE arch)")
    ap.add_argument("--wire_dtype", default="", choices=["", "bf16"],
                    help="cast a2a payloads across the wire (halves bytes)")
    ap.add_argument("--impl", default="einsum",
                    choices=["einsum", "pallas", "fused"],
                    help="expert kernels: einsum (batched XLA GEMMs), pallas "
                         "(two-pass grouped GEMMs), fused (one-kernel FFN "
                         "fwd+bwd — no (M, H) hidden in HBM)")
    ap.add_argument("--dispatch", default="", choices=["", "capacity", "ragged"],
                    help="override the MoE dispatch mode (ragged = dropless "
                         "sorted tokens; with --mesh it runs the ragged "
                         "load-sized all-to-all exchange)")
    ap.add_argument("--router", default="",
                    choices=["", "topk", "noisy_topk", "gumbel",
                             "expert_choice", "frozen"],
                    help="override the MoE routing variant (see "
                         "MoEConfig.router; expert_choice emits exact "
                         "per-expert capacities and a flat load)")
    ap.add_argument("--freeze_router_at", type=int, default=0,
                    help="StableMoE two-stage: at this step the live gate "
                         "stops routing and the distilled lightweight "
                         "router takes over (cfg flips to router='frozen' "
                         "and the step re-jits; requires a distilling "
                         "router — noisy_topk or gumbel — so params carry "
                         "w_frozen)")
    ap.add_argument("--ragged_bound", default="0",
                    help="ragged exchange: rows per peer shard (static "
                         "pad-to-max-per-peer width; 0 = local tokens * "
                         "top_k, which never drops; 'auto' = calibrate from "
                         "the load monitor's EMAs at every replan re-jit — "
                         "needs --replan_every)")
    ap.add_argument("--inter_bound", type=int, default=0,
                    help="hierarchical exchange: rows per slim inter-node "
                         "shard (0 = n_inner * ragged_bound, never drops at "
                         "the aggregation stage; only with a node mesh)")
    ap.add_argument("--ckpt_dir", default="",
                    help="checkpoint root: atomic verified checkpoints land "
                         "in step_<N>/ dirs (state after completing step N, "
                         "always in logical expert order regardless of the "
                         "live placement)")
    ap.add_argument("--save_every", type=int, default=0,
                    help="checkpoint every N completed steps (0 = only the "
                         "final save; needs --ckpt_dir)")
    ap.add_argument("--keep_ckpts", type=int, default=3,
                    help="retention: newest complete checkpoints kept by GC")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint under --ckpt_dir "
                         "that passes verification (corrupt ones are "
                         "skipped) and continue from its step; the data "
                         "stream fast-forwards so the trajectory matches an "
                         "uninterrupted run")
    ap.add_argument("--max_bad_steps", type=int, default=3,
                    help="step guard: tolerated consecutive non-finite "
                         "steps (each is skipped and retried from the last "
                         "good snapshot; exceeding aborts; 0 disables the "
                         "guard and its per-step host sync)")
    ap.add_argument("--snapshot_every", type=int, default=1,
                    help="guard snapshot cadence (1 = copy params/opt state "
                         "after every good step; higher amortizes the copy "
                         "at the cost of replaying more on restore)")
    ap.add_argument("--drop_spike", type=float, default=0.25,
                    help="guard: drop_frac above this for --drop_patience "
                         "consecutive steps forces the dropless ragged "
                         "bound (re-jit with ragged_bound=0)")
    ap.add_argument("--drop_patience", type=int, default=4)
    ap.add_argument("--metrics_out", default="",
                    help="write per-step telemetry records (JSONL): wall "
                         "time, device-side wire/drop/shadow counters, "
                         "HLO-modeled collective bytes, monitor snapshots, "
                         "replan events, and the resilience incident "
                         "timeline — faults, guard skips/restores, "
                         "checkpoint saves, resumes, rollbacks (repro.obs)")
    ap.add_argument("--trace", default="",
                    help="record a profiler trace into this directory of "
                         "steps start+1 .. start+5 (the first step compiles): "
                         "device ops by their fmoe.* scopes and the host "
                         "spans train_step, replan, migrate, as .xplane.pb "
                         "and a Perfetto perfetto_trace.json.gz")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import JsonlSink, StepStats, modeled_collective_bytes
    from repro.obs import trace as obs_trace
    enable_compile_cache()
    sink = JsonlSink(args.metrics_out) if args.metrics_out else None
    if args.trace:
        obs_trace.configure(enabled=True)
    # fault drills: REPRO_FAULTS='[{"point": "train_step", ...}]' arms the
    # registry for this process; every fired fault lands in the sink
    faults.arm_from_env()
    faults.set_sink(sink)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, num_layers=4, d_model=256)
    if args.dispatch and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=args.dispatch))
    if args.router and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, router=args.router))
    if args.freeze_router_at and (
            cfg.moe is None
            or cfg.moe.router not in ("noisy_topk", "gumbel")):
        raise SystemExit("--freeze_router_at needs a distilling router "
                         "(--router noisy_topk or gumbel) so params carry "
                         "w_frozen")
    opt = AdamW(lr=args.lr)

    opts = {"overlap_chunks": args.overlap_chunks,
            "wire_dtype": args.wire_dtype or None,
            "ragged_bound": ("auto" if args.ragged_bound == "auto"
                             else int(args.ragged_bound)),
            "inter_bound": args.inter_bound,
            "impl": args.impl}
    hook = None
    if args.mesh:
        dims = [int(v) for v in args.mesh.split("x")]
        if len(dims) == 3:  # DATAxNODExMODEL: hierarchical two-level mesh
            d, nn, m = dims
            mesh = make_local_mesh(d, m, node=nn)
        else:
            d, m = dims
            mesh = make_local_mesh(d, m)
        step_fn, pshard, oshard = jit_train_step(
            cfg, opt, mesh, args.batch, args.seq,
            num_microbatches=args.microbatches, opts=opts)
        params, opt_state = init_state(cfg, opt, pshard, oshard)
        if args.replan_every and cfg.moe is not None and m > 1:
            hook = ReplanHook(cfg, opt, mesh, args.batch, args.seq,
                              every=args.replan_every,
                              num_microbatches=args.microbatches, opts=opts,
                              per_layer=args.per_layer_plans, sink=sink)
            if not hook.enabled:  # no a2a path here: skip the per-step sync
                print("replan disabled: placement needs the a2a expert path")
                hook = None
            else:
                # ragged_bound=auto: the hook's monitor feeds the bound
                # calibration on every replan re-jit (opts dict is shared
                # with hook.opts, so observe() re-resolves through moe_dist)
                opts["load_monitor"] = hook.monitor
    else:
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt_state = opt.init(params)
        step_fn = jax.jit(make_train_step(cfg, opt,
                                          num_microbatches=args.microbatches,
                                          impl=args.impl),
                          donate_argnums=(0, 1))

    def modeled_of(fn, p, o, b, s):
        # HLO-derived collective bytes for the StepStats modeled-vs-measured
        # comparison; the AOT lowering shares nothing with fn's jit cache, so
        # only pay for it when telemetry asked for it
        try:
            return modeled_collective_bytes(
                fn.lower(p, o, b, jnp.int32(s)).compile())
        except Exception as e:  # missing column must be explainable, not mute
            print(f"warning: modeled collective bytes unavailable: {e}")
            obs_events.emit(sink, obs_events.MODELED_ERROR, step=int(s),
                            error=str(e))
            return {}

    # -- resilience: checkpointing + auto-resume + the step guard ----------
    manager = None
    if args.ckpt_dir:
        manager = CheckpointManager(args.ckpt_dir, save_every=args.save_every,
                                    keep=args.keep_ckpts, sink=sink)
    start_step = 0
    if args.resume and manager is not None:
        # checkpoints are logical-order; the fresh run starts on the
        # identity placement, so no placement kwarg on the restore side
        res = manager.restore_latest({"params": params, "opt": opt_state})
        if res is not None:
            tree, last = res
            start_step = last + 1
            if args.mesh:
                params = jax.device_put(tree["params"], pshard)
                opt_state = jax.device_put(tree["opt"], oshard)
            else:
                params, opt_state = tree["params"], tree["opt"]
            print(f"resumed from step {last} "
                  f"({manager.step_dir(last)}); continuing at {start_step}")
        else:
            print(f"no restorable checkpoint under {args.ckpt_dir}; "
                  f"starting fresh")
    guard = None
    if args.max_bad_steps > 0:
        guard = StepGuard(max_bad_steps=args.max_bad_steps,
                          drop_threshold=args.drop_spike,
                          drop_patience=args.drop_patience,
                          snapshot_every=args.snapshot_every, sink=sink)

    telemetry = sink is not None or obs_trace.enabled()
    # the first step compiles: the profiler window starts after it
    window = (obs_trace.Window(args.trace, start_step + 1) if args.trace
              else None)
    modeled: dict = {}
    data = SyntheticLM(cfg.vocab_size, args.seq)
    batch_iter = data.batches(args.batch)
    for _ in range(start_step):  # deterministic resume: replay the stream
        next(batch_iter)         # position an uninterrupted run would have
    t0 = time.time()
    step = start_step
    if guard is not None:  # seed snapshot: step 0 itself may go non-finite
        guard.commit(start_step - 1, params, opt_state)
    try:
        while step < args.steps:
            if window is not None:
                window.at(step)
            batch = {k: jnp.asarray(v) for k, v in next(batch_iter).items()}
            if (args.freeze_router_at and step >= args.freeze_router_at
                    and cfg.moe is not None and cfg.moe.router != "frozen"):
                # StableMoE stage 2: distillation is over — route through
                # w_frozen from here on.  Pure config flip + re-jit (params
                # already carry the distilled router); gate-id tables stop
                # changing, so later replans are pure load responses.
                cfg = dataclasses.replace(
                    cfg, moe=dataclasses.replace(cfg.moe, router="frozen"))
                if args.mesh:
                    step_fn, pshard, oshard = jit_train_step(
                        cfg, opt, mesh, args.batch, args.seq,
                        num_microbatches=args.microbatches, opts=opts,
                        placement=hook.placement if hook is not None
                        else None)
                    params = jax.device_put(params, pshard)
                    opt_state = jax.device_put(opt_state, oshard)
                    if hook is not None:
                        hook.cfg = cfg  # replan re-jits keep the frozen gate
                else:
                    step_fn = jax.jit(make_train_step(
                        cfg, opt, num_microbatches=args.microbatches,
                        impl=args.impl), donate_argnums=(0, 1))
                obs_events.emit(sink, obs_events.ROUTER_FROZEN, step=step)
                if sink is not None:
                    modeled = modeled_of(step_fn, params, opt_state, batch,
                                         step)
                print(f"step {step:5d} router frozen: gate-id tables are "
                      f"now stable")
            if step == start_step and sink is not None:
                modeled = modeled_of(step_fn, params, opt_state, batch, step)
            while True:  # retry loop, bounded by the guard's max_bad_steps
                ts = time.time()
                with obs_trace.span("train_step", step=step):
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch,
                                                         jnp.int32(step))
                    if telemetry:  # real wall times: don't run ahead
                        jax.block_until_ready(metrics)
                params, opt_state, metrics = faults.apply_step(
                    params, opt_state, metrics, step=step)
                if guard is None:
                    verdict = None
                    break
                loss = float(metrics["loss"])
                gnorm = float(metrics["grad_norm"])
                drop = float(metrics.get("drop_frac", 0.0))
                verdict = guard.check(step, loss=loss, grad_norm=gnorm,
                                      drop=drop)
                if verdict.ok:
                    break
                # non-finite step: the just-written state is poisoned —
                # reinstate the last good snapshot and retry this batch
                params, opt_state = guard.restore()
                if args.mesh:
                    params = jax.device_put(params, pshard)
                    opt_state = jax.device_put(opt_state, oshard)
                print(f"step {step:5d} non-finite ({verdict.reason}); "
                      f"restored step-{guard.snapshot_step} state, retrying")
            if verdict is not None and verdict.fallback_dropless:
                applied = False
                if args.mesh and opts.get("ragged_bound") not in (0, None):
                    opts["ragged_bound"] = 0  # provably dropless shards
                    mon = opts.get("load_monitor")
                    if mon is not None:  # keep auto mode from re-shrinking
                        mon.force_dropless = True
                    step_fn, pshard, oshard = jit_train_step(
                        cfg, opt, mesh, args.batch, args.seq,
                        num_microbatches=args.microbatches, opts=opts,
                        placement=hook.placement if hook is not None
                        else None)
                    applied = True
                    if sink is not None:
                        modeled = modeled_of(step_fn, params, opt_state,
                                             batch, step)
                obs_events.emit(sink, obs_events.DROP_FALLBACK, step=step,
                                applied=applied)
                print(f"step {step:5d} sustained drop spike: "
                      + ("forced dropless ragged bound" if applied else
                         "no bounded ragged exchange active (event only)"))
            if sink is not None:
                counters = {k: float(metrics[k])
                            for k in ("loss", "drop_frac", "wire_elems",
                                      "wire_bytes", "wire_bytes_intra",
                                      "wire_bytes_inter", "dropped",
                                      "shadow_hits", "imbalance")
                            if k in metrics}
                sink.emit(StepStats("train_step", step, time.time() - ts,
                                    counters=counters,
                                    modeled=modeled).record())
            new_fn = None
            if hook is not None:
                params, opt_state, new_fn = hook.observe(
                    step, metrics, params, opt_state,
                    loss=loss if guard is not None else None,
                    drop=drop if guard is not None else None)
                if new_fn is not None:
                    step_fn = new_fn
                    if sink is not None:  # new layout -> new profile
                        modeled = modeled_of(step_fn, params, opt_state,
                                             batch, step)
                    p = hook.placement
                    print(f"step {step:5d} replan: shadow={p.num_shadow} "
                          f"cap_scale={p.capacity_scale:.2f} "
                          f"imbalance={hook.monitor.imbalance:.2f}")
            if guard is not None:
                # post-observe so the snapshot is in the live physical
                # layout; force after a migration for the same reason
                guard.commit(step, params, opt_state,
                             force=new_fn is not None)
            if manager is not None:
                manager.maybe_save(
                    step, {"params": params, "opt": opt_state},
                    placement=hook.placement if hook is not None else None)
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({time.time() - t0:.1f}s)")
            step += 1
    except TrainingAborted as e:
        # persist the last good state so --resume can pick the run back up
        # (snapshot_step < start_step means only the seed exists — nothing
        # was accomplished, and labeling the init as a completed step would
        # skew a later resume's data fast-forward)
        if (manager is not None and guard is not None
                and guard.snapshot is not None
                and guard.snapshot_step >= start_step):
            p_good, o_good = guard.snapshot
            manager.save(guard.snapshot_step,
                         {"params": p_good, "opt": o_good},
                         placement=hook.placement if hook is not None
                         else None)
        print(f"aborted: {e}")
        if sink is not None:
            sink.close()
        raise SystemExit(1)
    finally:
        if window is not None:
            window.close()
    if manager is not None and step > start_step:
        # final save so a completed run is always resumable/extendable
        manager.maybe_save(step - 1, {"params": params, "opt": opt_state},
                           placement=hook.placement if hook is not None
                           else None, force=True)
    print(f"done: {args.steps} steps in {time.time() - t0:.1f}s")
    if sink is not None:
        sink.close()
        print(f"metrics written to {args.metrics_out}")
    if args.trace:
        print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
