"""Serving driver: jitted one-token decode step against a sharded KV/state
cache, plus a simple batched generation loop for the example/CLI.

Decode shapes (decode_32k / long_500k) lower THIS step, not train_step.
long_500k on full-attention archs runs the sliding-window variant: the ring
cache is capped at SWA_CAP and per-layer windows are clamped (DESIGN.md §4).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.configs.base import ModelConfig
from repro.launch.mesh import data_axes
from repro.launch.sharding import batch_spec, cache_specs, tree_shardings
from repro.launch.train import moe_dist
from repro.models import lm

SWA_CAP = 8192  # ring-buffer cap for the long_500k sliding-window variant


def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    """Ring length: full seq when it fits the attention pattern, else the
    sliding window (long_500k)."""
    if cfg.family == "ssm":
        return 1  # pure recurrent state; ring unused
    a = cfg.attention
    if seq_len > 32768:
        w = a.sliding_window if a.sliding_window else SWA_CAP
        return min(seq_len, max(w, 1))
    if a is not None and a.sliding_window:
        return min(seq_len, max(a.sliding_window,
                                1 if not a.global_layers else seq_len))
    return seq_len


def make_serve_step(cfg: ModelConfig, *, dist=None, with_metrics: bool = False,
                    paged: bool = False, layer_loads: bool = False):
    """Build the one-token serve step.  Returns a function with a FIXED
    3-tuple result ``(logits, cache, metrics)`` — ``metrics`` is ``{}`` when
    neither ``with_metrics`` nor ``layer_loads`` asks for telemetry, so call
    sites never branch on arity.

    ``with_metrics`` fills the dict with scalar decode telemetry (drop_frac
    + the repro.obs wire/drop/shadow counters, summed over layers like
    training's loss_fn aux) — same trace, no extra syncs.  ``layer_loads``
    adds ``load_layers`` (the (L, E) per-layer expert-load stack) and
    ``load`` — the online serve-time replan feed the continuous batcher
    pipes into ``LoadMonitor``.  ``paged=True`` takes a fifth argument, the
    (B, nb) per-slot block tables, and decodes through the paged pool
    (lm.init_paged_cache)."""
    L = max(cfg.num_layers, 1)

    def _pack(m, loads):
        md = {}
        if with_metrics:
            md["drop_frac"] = m.drop_frac / L
            if m.obs is not None:
                md.update(wire_elems=m.obs.wire_elems,
                          wire_bytes=m.obs.wire_bytes,
                          wire_bytes_intra=m.obs.wire_bytes_intra,
                          wire_bytes_inter=m.obs.wire_bytes_inter,
                          dropped=m.obs.dropped, shadow_hits=m.obs.shadow_hits,
                          imbalance=m.obs.imbalance / L)
        if layer_loads:
            md["load_layers"] = loads
            md["load"] = m.load / L
        return md

    if paged:
        def serve_step(params, tokens, pos, cache, block_tables):
            res = lm.decode_step(params, cfg, tokens, pos, cache, dist=dist,
                                 block_tables=block_tables,
                                 layer_loads=layer_loads)
            logits, new_cache, m = res[:3]
            return logits, new_cache, _pack(m, res[3] if layer_loads else None)
    else:
        def serve_step(params, tokens, pos, cache):
            res = lm.decode_step(params, cfg, tokens, pos, cache, dist=dist,
                                 layer_loads=layer_loads)
            logits, new_cache, m = res[:3]
            return logits, new_cache, _pack(m, res[3] if layer_loads else None)
    return serve_step


def jit_serve_step(cfg: ModelConfig, mesh, batch: int, seq_len: int, *,
                   opts: dict | None = None, with_metrics: bool = False):
    """Sharding-annotated decode step for the production mesh.

    opts["serve_tp"] keeps weights TP-resident (no FSDP over data) — at
    inference there are no optimizer states, so bf16 weights fit sharded over
    the model axis only and the per-layer weight all-gathers vanish (§Perf).

    opts["placement"] is an ExpertPlacement or PerLayerPlacement whose
    physical order ``params`` must already be in (placement.from_logical):
    decode usually runs the psum expert mode, where a plan load-balances the
    owned experts across ranks and serves shadowed hot experts locally,
    outside the reduction (core/fmoe._moe_psum) — the same load-balance loop
    as training, on the serving path.  Param/cache shardings are unchanged
    (a placement permutes content, not shapes).
    """
    opts = dict(opts or {})
    mp = mesh.shape["model"] if "model" in mesh.axis_names else 1
    if batch < mp and cfg.moe is None:
        # tiny-batch decode (long_500k) on dense archs: weight reads
        # dominate, so maximal (FSDP) weight sharding beats TP-residency and
        # head-aware replication — measured 0.1-0.8x regressions otherwise.
        # MoE archs keep the flags (expert weights are model-sharded either
        # way and head-aware still pays: arctic/deepseek ~4x even at B=1).
        opts.pop("serve_tp", None)
        opts.pop("head_aware", None)
    mode = "serve" if opts.get("serve_tp") else "train"
    clen = cache_len_for(cfg, seq_len)
    cache_shape = jax.eval_shape(
        functools.partial(lm.init_cache, cfg, batch, clen))
    cshard = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        cache_specs(cache_shape, mesh, batch,
                    seq_shard=bool(opts.get("cache_seq"))),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    params_shape = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    rcfg = cfg if opts.get("head_aware") else None
    pshard = tree_shardings(params_shape, mesh, mode, cfg=rcfg)
    tshard = jax.sharding.NamedSharding(mesh, batch_spec(batch, mesh))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    dist = moe_dist(cfg, mesh, batch, opts=opts)
    fn = make_serve_step(cfg, dist=dist, with_metrics=with_metrics)
    return jax.jit(fn, in_shardings=(pshard, tshard, rep, cshard),
                   out_shardings=(None, cshard, None),
                   donate_argnums=(3,)), cache_shape


def decode_dist(cfg: ModelConfig, mesh, batch: int, *,
                opts: dict | None = None):
    """Expert-parallel config for the continuous-batching decode loop,
    pinned to the **psum** mode.

    Placement-engaged psum decode is bitwise layout-invariant (per-slot
    combine before the fixed-order k-sum — README "Decode-time shadowing"),
    which is the property that makes mid-traffic replans safe: the same
    stream decoded under any plan yields identical tokens.  ``moe_dist``
    would pick a2a whenever the slot count happens to divide the mesh, and
    a2a capacity buffers are *not* layout-invariant, so the serving loop
    asks for psum explicitly — at decode's 1-token-per-slot scale the
    exchange would be latency-bound anyway.
    """
    d = moe_dist(cfg, mesh, batch, opts=opts)
    if d is None or d.mode == "psum":
        return d
    tok = tuple(a for a in d.token_axes if a not in d.expert_axes)
    total = 1
    for a in tok:
        total *= mesh.shape[a]
    if total > 1 and batch % total:
        tok = ()
    return d._replace(token_axes=tok)


def jit_paged_serve_step(cfg: ModelConfig, mesh, batch: int, num_blocks: int,
                         block_size: int, *, opts: dict | None = None,
                         with_metrics: bool = False,
                         layer_loads: bool = False):
    """Sharding-annotated paged decode step (continuous batching).

    The pool (lm.init_paged_cache) is shared by every decode slot, so it
    replicates over data axes with only head/latent dims model-sharded
    (cache_specs(paged=True)); block tables are small host-built (B, nb)
    int32 arrays and ride in replicated.  The MoE mode is pinned to psum
    (``decode_dist``) so serve-time replans stay bitwise-invisible.
    Returns ``(jitted_fn, pool_shape)``; the fn is
    ``(params, tokens, pos, pool, tables) -> (logits, pool, metrics)`` with
    the pool donated."""
    opts = dict(opts or {})
    mode = "serve" if opts.get("serve_tp") else "train"
    pool_shape = jax.eval_shape(
        functools.partial(lm.init_paged_cache, cfg, num_blocks, block_size))
    cshard = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        cache_specs(pool_shape, mesh, batch, paged=True),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    params_shape = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    rcfg = cfg if opts.get("head_aware") else None
    pshard = tree_shardings(params_shape, mesh, mode, cfg=rcfg)
    tshard = jax.sharding.NamedSharding(mesh, batch_spec(batch, mesh))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    dist = decode_dist(cfg, mesh, batch, opts=opts)
    fn = make_serve_step(cfg, dist=dist, with_metrics=with_metrics,
                         paged=True, layer_loads=layer_loads)
    return jax.jit(fn, in_shardings=(pshard, tshard, rep, cshard, rep),
                   out_shardings=(None, cshard, None),
                   donate_argnums=(3,)), pool_shape


def generate(params, cfg: ModelConfig, prompt: jax.Array, steps: int, *,
             cache_len: int = 256, temperature: float = 0.0,
             rng=None, use_prefill: bool = True) -> jax.Array:
    """Greedy/temperature sampling loop.

    ``use_prefill=True`` runs ONE full forward pass over the prompt to fill
    the cache (serving fast path); otherwise the prompt is consumed token by
    token (useful as a cross-check — tests assert both paths agree)."""
    B, S = prompt.shape
    cache = lm.init_cache(cfg, B, cache_len)
    step = jax.jit(functools.partial(lm.decode_step, cfg=cfg))

    def sample(logits_last, rng):
        if temperature > 0 and rng is not None:
            rng, k = jax.random.split(rng)
            return jax.random.categorical(
                k, logits_last / temperature)[:, None].astype(jnp.int32), rng
        return jnp.argmax(logits_last, -1)[:, None].astype(jnp.int32), rng

    out = [prompt]
    if use_prefill:
        logits, cache, _ = jax.jit(
            functools.partial(lm.prefill, cfg=cfg))(params, tokens=prompt,
                                                    cache=cache)
        tok, rng = sample(logits[:, -1], rng)
        start = S
    else:
        tok = prompt[:, :1]
        out = [tok]
        for pos in range(S - 1):
            logits, cache, _ = step(params, tokens=prompt[:, pos:pos + 1],
                                    pos=jnp.int32(pos), cache=cache)
            out.append(prompt[:, pos + 1:pos + 2])
        logits, cache, _ = step(params, tokens=prompt[:, S - 1:S],
                                pos=jnp.int32(S - 1), cache=cache)
        tok, rng = sample(logits[:, -1], rng)
        start = S
    out.append(tok)
    for pos in range(start, S + steps - 1):
        logits, cache, _ = step(params, tokens=tok, pos=jnp.int32(pos),
                                cache=cache)
        tok, rng = sample(logits[:, -1], rng)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def plan_for_serving(params, cfg: ModelConfig, prompt: jax.Array,
                     num_ranks: int, *, per_layer: bool = True):
    """Measure per-layer expert load on the prompt and plan a decode layout.

    One forward pass over the prompt yields the (L, E) load stack; the
    planner (train=False: no grad all-reduce to charge for) picks each
    layer's permutation.  Returns ``(plan, params)`` with params migrated
    into the plan's physical order.

    Expect ``num_shadow == 0`` from this path: the decode mode is psum,
    where shadowing saves no wire bytes and replicates weight reads, so the
    cost model correctly declines it — the per-layer *permutation* is what
    pays at decode (balanced owned compute).  The decode-time shadow
    execution in ``core/fmoe._moe_psum`` is there for the other direction:
    a shadowed plan produced by the *training* loop (ReplanHook /
    checkpoint restore) serves unchanged, bit-identically to its
    unshadowed twin, instead of forcing a re-migration at deploy time.
    """
    import numpy as np

    from repro.core.dispatch import expert_capacity
    from repro.placement import (from_logical, load_calibration,
                                 plan_placement, plan_placement_per_layer)

    moe = cfg.moe
    _, _, loads = lm.forward(params, cfg, prompt, layer_loads=True)
    cap = expert_capacity(prompt.shape[0], moe.num_experts, moe.top_k,
                          moe.capacity_factor)
    # train=False: no grad all-reduce to charge; shrink_capacity=False: the
    # decode path is psum — no a2a buffer exists, so a shrunk capacity would
    # only add decode-time drops (and _moe_psum ignores the shrink anyway)
    kw = dict(d_model=cfg.d_model, d_hidden=moe.d_expert_hidden,
              capacity=cap, capacity_factor=moe.capacity_factor,
              train=False, shrink_capacity=False,
              constants=load_calibration())
    if per_layer:
        plan = plan_placement_per_layer(np.asarray(loads), num_ranks, **kw)
    else:
        plan = plan_placement(np.asarray(loads).sum(0), num_ranks, **kw)
    return plan, from_logical(params, plan)


def serve_continuous(params, cfg: ModelConfig, scfg, *, prompt_len: int,
                     gen: int, num_requests: int, sink=None) -> None:
    """Drive the continuous-batching engine (launch/scheduler) over a
    synthetic request stream described by the CLI flags and print the
    serving headline numbers (tokens/sec, per-token p50/p99)."""
    import numpy as np

    from repro.launch.scheduler import ContinuousBatcher
    from repro.launch.serve_api import Request

    rng = np.random.RandomState(1)
    batcher = ContinuousBatcher(params, cfg, scfg, sink=sink)
    t0 = time.time()
    for i in range(num_requests):
        s = max(1, prompt_len - int(rng.randint(0, max(prompt_len // 2, 1))))
        batcher.submit(Request(
            id=i, prompt=rng.randint(0, cfg.vocab_size, s).astype(np.int32),
            max_new_tokens=gen))
    batcher.run()
    dt = time.time() - t0
    done = batcher.completions
    toks = sum(len(c.tokens) for c in done)
    lats = sorted(l for c in done for l in c.latencies[1:]) or [0.0]
    print(f"continuous: {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s) over {batcher.ticks} ticks; "
          f"per-token p50 {lats[len(lats) // 2] * 1e3:.1f}ms "
          f"p99 {lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3:.1f}ms; "
          f"replans={batcher.replans}")


def main() -> None:
    from repro.launch.serve_api import ServeConfig

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode width: the static demo's batch, and the "
                         "slot count when --slots is not given")
    ap.add_argument("--prompt_len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="DATAxMODEL mesh for the sharded decode step (e.g. "
                         "1x4; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--continuous", action="store_true",
                    help="run the continuous-batching serve loop "
                         "(launch/scheduler: per-step admit/retire, paged KV "
                         "cache, online replans) over a synthetic request "
                         "stream instead of decoding one static batch")
    ap.add_argument("--requests", type=int, default=0,
                    help="request count for --continuous (0 = 3x slots)")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode slots (ServeConfig.slots; default --batch)")
    ap.add_argument("--block_size", type=int, default=None,
                    help="paged KV cache block rows (ServeConfig.block_size)")
    ap.add_argument("--max_len", type=int, default=None,
                    help="per-request prompt+gen cap (ServeConfig.max_len; "
                         "default prompt_len + gen)")
    ap.add_argument("--policy", default=None,
                    choices=["continuous", "static"],
                    help="admission policy for --continuous (static = "
                         "admit only at whole-batch boundaries)")
    ap.add_argument("--replan_every", type=int, default=None,
                    help="decode ticks between online placement-replan "
                         "polls (0 = off; needs --mesh and an MoE arch)")
    ap.add_argument("--per_layer_plans", action="store_true",
                    help="measure per-layer expert load on the prompt and "
                         "serve under a per-layer placement (decode-time "
                         "shadowing; needs --mesh and an MoE arch)")
    ap.add_argument("--metrics_out", default="",
                    help="write per-decode-step telemetry (JSONL): latency, "
                         "tokens/sec, device-side wire/drop/shadow counters "
                         "(repro.obs; needs --mesh)")
    ap.add_argument("--trace", default="",
                    help="record a profiler trace into this directory "
                         "(.xplane.pb and a Perfetto perfetto_trace.json.gz): "
                         "with --mesh, decode positions 1 .. 5 and their "
                         "decode_step spans; otherwise the whole run")
    ap.add_argument("--router", default="",
                    choices=["", "topk", "noisy_topk", "gumbel",
                             "expert_choice", "frozen"],
                    help="override the MoE routing variant for serving "
                         "(all routers are deterministic at decode: no rng "
                         "is threaded, so gumbel == topk here)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    scfg = ServeConfig.from_args(args)
    if args.max_len is None:
        scfg.max_len = args.prompt_len + args.gen

    from repro.obs import JsonlSink
    from repro.obs import trace as obs_trace
    sink = JsonlSink(scfg.metrics_out) if scfg.metrics_out else None
    if scfg.trace:
        obs_trace.configure(enabled=True)

    cfg = get_config(scfg.arch)
    if scfg.reduced:
        cfg = reduced(cfg, num_layers=4, d_model=256)
    if args.router and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, router=args.router))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab_size)
    window = None
    if scfg.trace:
        # the mesh decode loop records positions 1.. (position 0 compiles);
        # the other paths have no step loop here and record the whole run
        stepped = args.mesh and not args.continuous
        window = obs_trace.Window(scfg.trace, 1 if stepped else 0)
    if args.continuous:
        n_req = args.requests or 3 * scfg.slots
        if window is not None:
            window.at(0)
        serve_continuous(params, cfg, scfg, prompt_len=args.prompt_len,
                         gen=args.gen, num_requests=n_req, sink=sink)
        if sink is not None:
            sink.close()
            print(f"metrics written to {scfg.metrics_out}")
        if window is not None:
            window.close()
            print(f"trace written to {scfg.trace}")
        return
    if args.mesh:
        from repro.launch.mesh import make_local_mesh
        d, m = (int(v) for v in args.mesh.split("x"))
        mesh = make_local_mesh(d, m)
        opts: dict = {}
        if args.per_layer_plans and cfg.moe is not None and m > 1:
            plan, params = plan_for_serving(params, cfg, prompt, m,
                                            per_layer=True)
            opts["placement"] = plan
            print(f"serving plan: shadow={plan.num_shadow} "
                  f"cap_scale={plan.capacity_scale:.2f}")
        seq_len = args.prompt_len + args.gen
        step, _ = jit_serve_step(cfg, mesh, args.batch, seq_len, opts=opts,
                                 with_metrics=sink is not None)
        cache = lm.init_cache(cfg, args.batch, cache_len_for(cfg, seq_len))
        tok, out = prompt[:, :1], [prompt[:, :1]]
        telemetry = sink is not None or obs_trace.enabled()
        lat: list = []
        t0 = time.time()
        with mesh:
            for pos in range(seq_len - 1):
                if window is not None:
                    window.at(pos)
                ts = time.time()
                with obs_trace.span("decode_step", pos=pos):
                    logits, cache, md = step(params, tok, jnp.int32(pos), cache)
                    if telemetry:  # real per-step latency, not dispatch time
                        jax.block_until_ready(logits)
                lat.append(time.time() - ts)
                if sink is not None:
                    rec = {"kind": "decode_step", "pos": pos,
                           "wall_s": lat[-1],
                           "tokens_per_s": args.batch / max(lat[-1], 1e-9)}
                    rec.update({k: float(v) for k, v in md.items()})
                    sink.emit(rec)
                tok = (prompt[:, pos + 1:pos + 2] if pos + 1 < args.prompt_len
                       else jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32))
                out.append(tok)
        seq = jnp.concatenate(out, axis=1)
        if len(lat) > 1:
            # steady-state decode latency (skip step 0: it pays the compile)
            srt = sorted(lat[1:])
            p50 = srt[len(srt) // 2]
            p99 = srt[min(len(srt) - 1, int(len(srt) * 0.99))]
            print(f"decode: {len(lat)} steps, p50 {p50 * 1e3:.1f}ms "
                  f"p99 {p99 * 1e3:.1f}ms")
    else:
        if window is not None:
            window.at(0)
        t0 = time.time()
        seq = generate(params, cfg, prompt, args.gen)
    dt = time.time() - t0
    print(f"generated {args.batch}x{args.gen} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(seq[0])
    if sink is not None:
        sink.close()
        print(f"metrics written to {args.metrics_out}")
    if window is not None:
        window.close()
        print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
