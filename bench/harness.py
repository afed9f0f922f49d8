"""One run of one cell: set-up, the measured window, the comparison.

Set-up builds the program's own train step (``make_train_step`` under
``jax.jit`` with donated state on one chip; ``jit_train_step`` on a
``make_local_mesh(1, chips)`` mesh otherwise), the benchmark's weights from
the seed, and the cell's pool of batches, then drives that step through its
first three steps.  Those steps are the warm-up and the program's side of
the comparison.  The window drives the same step on the pool for the given
seconds, reading each step's loss back to the host.  After it, the program's
state is freed and the plain reference trains the same three steps.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import math
import os
import shutil
import sys
import time
from typing import Any, NamedTuple, Optional

import numpy as np

from bench import compare, flops, model, reduce, spec, traffic

CORRECT_STEPS = 3


class Program(NamedTuple):
    """The program's step for a cell and where its inputs live."""

    cfg: Any  # repro ModelConfig
    opt: Any  # repro AdamW
    step: Any  # the jitted train step
    pshard: Any  # params sharding
    oshard: Any  # optimizer state sharding
    bshard: Any  # batch sharding
    groups: int  # token groups the MoE routes apart (one per chip)
    mesh: Any  # None on one chip


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader may read."""

    chips: int
    steps: int
    tokens_per_s: float
    flops_per_token: float
    peaks: dict
    aux: dict
    counters: dict
    trace: Optional[reduce.Trace] = None
    window_ns: tuple = (0.0, 0.0)


def say(msg: str) -> None:
    print(msg, flush=True)


def _devices(chips: int, chip_check: bool):
    import jax

    devs = jax.devices()
    if chip_check and devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def _leaf_norms(tree, stacked, scale: float = 1.0) -> dict:
    """Each leaf's norm; a leaf in ``stacked`` (stacked over layers along
    axis 0), one norm per layer."""
    import jax.numpy as jnp

    out = {}
    for p, a in model.flatten(tree).items():
        axes = tuple(range(1 if p in stacked else 0, a.ndim))
        out[p] = jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                  axis=axes)) * scale
    return out


def _host(tree) -> tuple[dict, dict]:
    """({leaf: norm}, {"leaf#layer": norm}) on the host."""
    import jax

    whole, layers = {}, {}
    for k, v in jax.device_get(tree).items():
        v = np.asarray(v, np.float64).reshape(-1)
        whole[k] = float(np.sqrt(np.sum(v * v)))
        layers.update({f"{k}#{i}": float(x) for i, x in enumerate(v)})
    return whole, layers


def _compile_cache() -> str:
    """The program's persistent compilation cache, for every program the
    run compiles (small ones included), at its fixed path in the checkout."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class _CompileCounter:
    def __init__(self):
        self.on = False
        self.counts: dict = {}

    def __call__(self, event: str, *args, **kwargs) -> None:
        if self.on:
            self.counts[event] = self.counts.get(event, 0) + 1


def flops_per_token(cell: spec.Cell) -> int:
    """Model FLOPs per trained token, as the configuration's reference
    counts them at the cell's sequence length."""
    return cell.reference().flops_per_token(cell.config,
                                            cell.traffic["seq_len"])


def build_program(cell: spec.Cell, devs) -> Program:
    """The program's own train step for the cell, as its CLI builds it."""
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from repro.launch.train import jit_train_step, make_train_step, moe_dist
    from repro.launch.mesh import make_local_mesh
    from repro.launch.sharding import batch_spec

    conf, mix = cell.config, cell.traffic
    ref = cell.reference()
    cfg = model.program_config(conf)
    mb = mix["microbatches"]
    mesh = make_local_mesh(1, cell.chips) if cell.chips > 1 else None
    dist = (None if mesh is None else
            moe_dist(cfg, mesh, mix["batch"] * mix["seq_len"] // mb))
    ref.covers(cfg, conf, cell.chips, dist)
    model.check_tree(ref.param_shapes(conf), cfg)
    model.check_optimizer_defaults(conf)
    opt = model.make_optimizer(conf)
    if mesh is None:
        step = jax.jit(make_train_step(cfg, opt, num_microbatches=mb),
                       donate_argnums=(0, 1))
        one = SingleDeviceSharding(devs[0])
        return Program(cfg, opt, step, one, one, one, 1, None)
    step, pshard, oshard = jit_train_step(cfg, opt, mesh, mix["batch"],
                                          mix["seq_len"], num_microbatches=mb)
    bshard = NamedSharding(mesh, batch_spec(mix["batch"], mesh))
    return Program(cfg, opt, step, pshard, oshard, bshard, cell.chips, mesh)


def start(cell: spec.Cell, prog: Program, seed: int):
    """The cell's weights, optimizer state and pool of batches from the seed,
    on the devices the program's step expects."""
    import jax

    conf = cell.config
    key = model.seed_key(seed)
    params = jax.jit(functools.partial(model.init_params,
                                       cell.reference().param_shapes(conf)),
                     out_shardings=prog.pshard)(key)
    opt_state = jax.jit(prog.opt.init, out_shardings=prog.oshard)(params)
    pool_host = traffic.make_pool(cell.traffic, conf["vocab_size"], seed)
    pool = [jax.device_put({"tokens": b}, {"tokens": prog.bshard})
            for b in pool_host]
    jax.block_until_ready((params, opt_state, pool))
    return key, params, opt_state, pool_host, pool


def first_steps(cell: spec.Cell, prog: Program, key, params, opt_state,
                pool):
    """Drive the program's step through its first steps on the pool's first
    batches: (readings for the check, params, opt_state)."""
    import jax
    import jax.numpy as jnp

    ref = cell.reference()
    shapes = ref.param_shapes(cell.config)
    stacked = set(ref.layer_leaves(cell.config))
    grad_norms = jax.jit(functools.partial(_leaf_norms, stacked=stacked,
                                           scale=1.0 / (1 - prog.opt.b1)))
    delta_norms = jax.jit(lambda p, k: _leaf_norms(jax.tree.map(
        jnp.subtract, p, model.init_params(shapes, k)), stacked))
    losses, grad = [], None
    for i in range(CORRECT_STEPS):
        params, opt_state, m = prog.step(params, opt_state, pool[i],
                                         np.int32(i))
        losses.append(float(m["loss"]))
        if i == 0:
            grad, grad_layers = _host(grad_norms(opt_state.mu))
    update, update_layers = _host(delta_norms(params, key))
    return ({"loss": losses, "grad": grad, "update": update,
             "grad_layers": grad_layers, "update_layers": update_layers},
            params, opt_state)


def run_program(cell: spec.Cell, seed: int, seconds: float, trace: bool,
                devs, t0: float, trace_dir: str, readers: dict):
    import jax

    mix = cell.traffic
    parts = {"start_s": time.time() - t0}
    t = time.time()
    program = build_program(cell, devs)
    key, params, opt_state, pool_host, pool = start(cell, program, seed)
    step = program.step
    parts["weights_traffic_s"] = time.time() - t
    t = time.time()
    prog, params, opt_state = first_steps(cell, program, key, params,
                                          opt_state, pool)
    parts["first_steps_s"] = time.time() - t
    setup_s = time.time() - t0
    say("setup " + " ".join(f"{k} {v:.3f}" for k, v in parts.items())
        + f" total {setup_s:.3f}")

    aux_keys = sorted({r.READS["aux"] for r in readers.values()
                       if "aux" in r.READS})
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    if trace:
        if os.path.isdir(trace_dir):
            shutil.rmtree(trace_dir)
        jax.profiler.start_trace(trace_dir)
    times, aux_hist, window_losses = [], [], []
    n_pool = len(pool)
    i = CORRECT_STEPS
    collections = []

    def gc_timer(phase, info, began=[0.0]):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            collections.append(time.perf_counter() - began[0])

    gc.callbacks.append(gc_timer)
    counter.on = True
    w0, at = time.perf_counter(), time.time() - t0
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.next_batch"):
                batch = pool[i % n_pool]
            with jax.profiler.TraceAnnotation("bench.dispatch_step"):
                params, opt_state, m = step(params, opt_state, batch,
                                            np.int32(i))
            with jax.profiler.TraceAnnotation("bench.read_loss"):
                window_losses.append(float(m["loss"]))
            te = time.perf_counter()
            times.append(te - ts)
            aux_hist.append({k: m[k] for k in aux_keys})
            i += 1
            if te - w0 >= seconds:
                break
    counter.on = False
    gc.callbacks.remove(gc_timer)
    jax.monitoring.unregister_event_duration_listener(counter)
    if trace:
        jax.profiler.stop_trace()
    window_s = te - w0
    tokens = len(times) * mix["batch"] * mix["seq_len"]
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(m.get("peak_bytes_in_use", 0) for m in stats)
    say("memory per chip " + json.dumps(
        {k: [m.get(k) for m in stats] for k in
         ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")}))
    slow = np.flatnonzero(np.asarray(times) > 1.5 * np.median(times))
    say("slow steps " + " ".join(
        f"{k}:{1e3 * times[k]:.1f}ms@{at + sum(times[:k]):.1f}s"
        for k in slow[:20]) + f"; {len(collections)} garbage collections, "
        f"longest {1e3 * max(collections, default=0.0):.1f} ms")
    aux = {k: np.asarray(jax.device_get([a[k] for a in aux_hist]), np.float64)
           for k in aux_keys}
    del params, opt_state, pool, m, batch
    return dict(
        prog=prog, setup_s=setup_s, times=times, window_s=window_s,
        tokens_per_s=tokens / window_s, peak=peak, aux=aux,
        counters=counter.counts, window_losses=window_losses,
        pool_host=pool_host, groups=program.groups, mesh=program.mesh)


def run_reference(cell: spec.Cell, seed: int, pool_host, groups: int, mesh,
                  quant=None) -> dict:
    """The plain reference's three steps: losses, first clipped gradient's
    leaf norms, and each leaf's change over the three steps."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    conf, mix = cell.config, cell.traffic
    ref = cell.reference()
    dot = ref.make_dot(quant)
    shapes = ref.param_shapes(conf)
    stacked = set(ref.layer_leaves(conf))
    constrain = lambda a: a
    pshard = None
    if mesh is not None:
        # experts over the chips along each leaf's expert axis, as the
        # reference names it; the rest replicated
        from jax.sharding import Mesh
        emesh = Mesh(np.asarray(mesh.devices).reshape(-1), ("e",))
        experts = ref.expert_leaves(conf)
        pshard = model.unflatten({
            p: NamedSharding(emesh, P(*[None] * experts[p], "e")
                             if p in experts else P())
            for p in model.flatten(shapes)})
        buf = NamedSharding(emesh, P("e"))
        constrain = lambda a: jax.lax.with_sharding_constraint(a, buf)
    o = conf["optimizer"]
    train = ref.make_train_step(conf, mix["microbatches"], groups, dot,
                                constrain)

    def step(p, mu, nu, batch, t, lr):
        p, mu, nu, loss, g = train(p, mu, nu, batch, t, lr)
        return p, mu, nu, loss, _leaf_norms(g, stacked)

    key = model.seed_key(seed)
    init = jax.jit(functools.partial(model.init_params, shapes),
                   out_shardings=pshard)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=pshard)
    with jax.default_matmul_precision("highest"):
        jstep = jax.jit(step, donate_argnums=(0, 1, 2),
                        out_shardings=(pshard, pshard, pshard, None, None))
        params = init(key)
        mu, nu = zeros(params), zeros(params)
        losses, grad = [], None
        for i in range(CORRECT_STEPS):
            lr = o["lr"] * ref.lr_scale(i, o["warmup"], o["total_steps"])
            params, mu, nu, loss, g = jstep(params, mu, nu,
                                            jnp.asarray(pool_host[i]),
                                            np.int32(i + 1), np.float32(lr))
            losses.append(float(loss))
            if i == 0:
                grad, grad_layers = _host(g)
        del mu, nu
        delta = jax.jit(lambda p, k: _leaf_norms(jax.tree.map(
            jnp.subtract, p, model.init_params(shapes, k)), stacked))
        update, update_layers = _host(delta(params, key))
    return {"loss": losses, "grad": grad, "update": update,
            "grad_layers": grad_layers, "update_layers": update_layers}


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t0: float, *, chip_check: bool = True, compile_cache: bool = True,
        bench_dir: str = spec.BENCH_DIR) -> int:
    """Run ``workload`` and print its result line; ``chip_check=False``
    and ``compile_cache=False`` serve the CPU tests, which drive a run on
    the host's devices without the persistent compilation cache."""
    cell = spec.resolve(root, workload, bench_dir)
    import jax

    t_import = time.time()
    devs = _devices(cell.chips, chip_check)
    cache = _compile_cache() if compile_cache else None
    say(f"bench {workload} seed {seed} seconds {seconds} trace {int(trace)}: "
        f"{devs[0].device_kind} x{len(devs)}, compile cache {cache}; "
        f"imports {t_import - t0:.3f} s, devices {time.time() - t_import:.3f} s")
    readers = {m["name"]: cell.metric_reader(m["name"])
               for m in cell.per_layer} if trace else {}
    trace_dir = os.path.join(root, ".bench_trace", workload)
    r = run_program(cell, seed, seconds, trace, devs, t0, trace_dir, readers)
    steps = len(r["times"])
    failed = sum(not math.isfinite(v) for v in r["window_losses"])
    times = np.asarray(r["times"])
    say(f"window {r['window_s']:.3f} s, {steps} steps, "
        f"{r['tokens_per_s']:.1f} tokens/s, peak {r['peak'] / 1e9:.3f} GB; "
        f"step median {1e3 * np.median(times):.3f} ms, slowest "
        f"{1e3 * times.max():.3f} ms at step {int(times.argmax())}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(r["peak"])}
    out = {}
    if trace:
        tr = reduce.load(reduce.find_xplane(trace_dir))
        lo, hi = reduce.span(tr, "bench.window")
        busy = reduce.busy_ns(tr, lo, hi)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        run_data = RunData(
            chips=len(devs), steps=steps,
            tokens_per_s=r["tokens_per_s"],
            flops_per_token=flops_per_token(cell),
            peaks=flops.peaks(devs[0].device_kind,
                              os.path.join(cell.bench_dir, "peaks.json")),
            aux=r["aux"],
            counters=r["counters"], trace=tr, window_ns=(lo, hi))
        metrics = {}
        for m in cell.per_layer:
            v = readers[m["name"]].read(run_data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": reduce.top_ops(tr, lo, hi),
                            "idle_gaps": reduce.idle_gaps(tr, lo, hi)}
    else:
        e2e = {
            "tokens_per_s": r["tokens_per_s"],
            "step_ms_p90": 1e3 * float(np.percentile(r["times"], 90)),
            "setup_s": r["setup_s"],
        }
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    t = time.time()
    ref = run_reference(cell, seed, r["pool_host"], r["groups"], r["mesh"])
    say(f"reference {time.time() - t:.3f} s")
    values = compare.readings(r["prog"], ref)
    say("program " + json.dumps(r["prog"]["loss"]) + " reference "
        + json.dumps(ref["loss"]))
    correct, checks = compare.judge(values, cell.limits)
    correct = correct and failed == 0
    for name, c in checks.items():
        print(f"check {name} {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device, **out, "checks": checks}
    print(json.dumps(result), flush=True)
    return 0
