"""Smoke check: FastMoE training on the TPU at fastmoe-gpt's published width.

    python chip_smoke.py             # one chip: fused and einsum expert kernels
    python chip_smoke.py --chips 4   # four chips: expert parallelism only

One chip trains ``fastmoe-gpt`` (d_model 1024, 96 experts, top-2, expert
hidden 2048, vocab 50304) cut to 1 layer for a few steps through the train
CLI's own ``make_train_step`` + ``jax.jit``, once with the fused Pallas
expert kernels and once with the einsum experts, from the same seed and
batches.  It fails when a loss or grad norm is not finite, when the losses
do not fall, when the two kernels' first-step losses disagree beyond
``LOSS_TOL``, or when the fused step holds no compiled kernel.

``--chips 4`` runs only the expert-parallel phase: 4 layers (24 experts per
chip) on a 1x4 mesh through ``jit_train_step`` (the capacity all-to-all
path), a check from each chip's memory that the experts are spread, and the
first-step loss of the dropless ragged exchange on 1x4 against the same
step on one chip.

Every number printed is a smoke reading, not a benchmark.  The last line is
one JSON object naming the device; it is printed only when every check
passed.  Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
BATCH, SEQ, STEPS = 8, 1024, 5
# first-step loss agreement: fused vs einsum expert kernels on one chip, and
# the 1x4 ragged exchange vs one chip.  Both sides run bf16 compute from the
# same f32 weights.  A CPU rehearsal at reduced width (d_model 256, 8
# experts, same dtypes, 8k tokens) differed by 9.1e-05 and 5.7e-04 (the
# sharded balance loss is a pmean of per-shard terms), so 5e-3 leaves ~10x
# room for rounding on a loss of ~11.
LOSS_TOL = 5e-3


def _say(msg: str) -> None:
    print(msg, flush=True)


def _gb(n: float) -> str:
    return f"{n / 1e9:.2f} GB"


def _batches(vocab: int, seq: int, batch: int, steps: int, seed: int):
    import jax.numpy as jnp
    from repro.data import SyntheticLM

    data = SyntheticLM(vocab, seq, seed=seed)
    return [{"tokens": jnp.asarray(data.sample_batch(batch))}
            for _ in range(steps)]


def _finite(xs) -> bool:
    import math
    return all(math.isfinite(x) for x in xs)


def train_one_chip(cfg, batches, *, impl: str, seed: int, lr: float) -> dict:
    """Train ``len(batches)`` steps on the default device the way the train
    CLI does without a mesh; returns losses, grad norms and smoke timings."""
    import jax
    import jax.numpy as jnp
    from repro.launch.train import make_train_step
    from repro.models import lm
    from repro.optim import AdamW

    opt = AdamW(lr=lr)
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt, impl=impl, warmup=1,
                                      total_steps=len(batches)),
                      donate_argnums=(0, 1))
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, opt_state, batches[0],
                             jnp.int32(0)).compile()
    out = {"compile_s": time.perf_counter() - t0,
           "kernel": "tpu_custom_call" in compiled.as_text(),
           "loss": [], "grad_norm": [], "step_s": []}
    for s, batch in enumerate(batches):
        t0 = time.perf_counter()
        params, opt_state, m = compiled(params, opt_state, batch,
                                        jnp.int32(s))
        jax.block_until_ready(m)
        out["step_s"].append(time.perf_counter() - t0)
        out["loss"].append(float(m["loss"]))
        out["grad_norm"].append(float(m["grad_norm"]))
    return out


def train_expert_parallel(cfg, mesh, batches, *, impl: str, seed: int,
                          lr: float) -> dict:
    """Train on ``mesh`` through the CLI's ``jit_train_step`` (experts
    sharded over the "model" axis).  Also returns, while the state is live,
    each chip's bytes in use and the experts each chip holds."""
    import jax
    import jax.numpy as jnp
    from repro.launch.train import init_state, jit_train_step
    from repro.optim import AdamW

    opt = AdamW(lr=lr)
    batch, seq = batches[0]["tokens"].shape
    step_fn, pshard, oshard = jit_train_step(cfg, opt, mesh, batch, seq,
                                             opts={"impl": impl})
    params, opt_state = init_state(cfg, opt, pshard, oshard, seed=seed)
    out = {"loss": [], "grad_norm": [], "step_s": []}
    for s, b in enumerate(batches):
        t0 = time.perf_counter()
        params, opt_state, m = step_fn(params, opt_state, b, jnp.int32(s))
        jax.block_until_ready(m)
        out["step_s"].append(time.perf_counter() - t0)
        out["loss"].append(float(m["loss"]))
        out["grad_norm"].append(float(m["grad_norm"]))
    wi = params["layers"]["ffn"]["experts"]["wi"]  # (L, E, d, H)
    out["held"] = sorted((sh.device.id, sh.data.shape[1])
                         for sh in wi.addressable_shards)
    out["in_use"] = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                     for d in mesh.devices.flat]
    return out


def one_chip_phase(cfg, args, fails: list) -> None:
    import jax

    dev = jax.devices()[0]
    batches = _batches(cfg.vocab_size, SEQ, BATCH, STEPS, args.seed)
    runs = {}
    for impl in ("fused", "einsum"):
        r = train_one_chip(cfg, batches, impl=impl, seed=args.seed,
                           lr=args.lr)
        runs[impl] = r
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        _say(f"smoke {impl}: compile {r['compile_s']:.1f}s, steps "
             + " ".join(f"{t:.3f}s" for t in r["step_s"])
             + f", peak memory {_gb(peak)}")
        _say(f"smoke {impl}: loss " + " ".join(f"{v:.4f}" for v in r["loss"])
             + "  grad_norm "
             + " ".join(f"{v:.3f}" for v in r["grad_norm"]))
        if not _finite(r["loss"] + r["grad_norm"]):
            fails.append(f"{impl}: non-finite loss or grad norm")
        elif r["loss"][-1] >= r["loss"][0]:
            fails.append(f"{impl}: loss did not fall")
    _say(f"smoke fused step holds a compiled Pallas kernel "
         f"(tpu_custom_call): {runs['fused']['kernel']}")
    if not runs["fused"]["kernel"]:
        fails.append("fused step has no tpu_custom_call: kernels interpreted")
    diff = abs(runs["fused"]["loss"][0] - runs["einsum"]["loss"][0])
    _say(f"smoke first-step loss fused vs einsum: |diff| {diff:.2e} "
         f"(tolerance {LOSS_TOL:.0e})")
    if not diff <= LOSS_TOL:
        fails.append(f"fused vs einsum first-step loss differ by {diff}")


def four_chip_phase(base, args, fails: list) -> None:
    import jax
    from repro.launch.mesh import make_local_mesh

    devs = jax.devices()
    mesh = make_local_mesh(1, 4)
    # 4 layers: 24 experts per chip, with their f32 weights + AdamW moments
    cfg = dataclasses.replace(base, num_layers=4)
    moe = cfg.moe
    expert_state = (cfg.num_layers * moe.num_experts * 2 * cfg.d_model
                    * moe.d_expert_hidden * 4 * 3)
    _say(f"config four chips: layers {base.num_layers} -> 4, "
         f"{moe.num_experts // 4} experts per chip; expert weights + AdamW "
         f"moments {_gb(expert_state)} in all, {_gb(expert_state / 4)} "
         f"per chip")
    batches = _batches(cfg.vocab_size, SEQ, BATCH, 3, args.seed)
    r = train_expert_parallel(cfg, mesh, batches, impl="fused",
                              seed=args.seed, lr=args.lr)
    held, in_use = r["held"], r["in_use"]
    _say("smoke 1x4 capacity a2a (4 layers, fused): steps "
         + " ".join(f"{t:.3f}s" for t in r["step_s"]) + "  loss "
         + " ".join(f"{v:.4f}" for v in r["loss"]) + "  grad_norm "
         + " ".join(f"{v:.3f}" for v in r["grad_norm"]))
    _say("smoke experts held per chip (device id, experts): "
         + ", ".join(f"({i}, {n})" for i, n in held))
    _say("smoke bytes in use per chip: "
         + ", ".join(_gb(b) for b in in_use))
    if not _finite(r["loss"] + r["grad_norm"]):
        fails.append("1x4 capacity a2a: non-finite loss or grad norm")
    if held != [(d.id, moe.num_experts // 4) for d in
                sorted(mesh.devices.flat, key=lambda d: d.id)]:
        fails.append(f"experts not spread one quarter per chip: {held}")
    if not (min(in_use) >= 0.9 * expert_state / 4
            and min(in_use) >= 0.8 * max(in_use)):
        fails.append(f"device memory not spread across chips: {in_use}")

    # dropless ragged exchange: routing is the same on 1x4 and on one chip,
    # so the first-step losses agree up to bf16 rounding
    cfg1 = dataclasses.replace(base, num_layers=1, moe=dataclasses.replace(
        base.moe, dispatch="ragged"))
    one = batches[:1]
    ep = train_expert_parallel(cfg1, mesh, one, impl="fused",
                               seed=args.seed, lr=args.lr)["loss"][0]
    with jax.default_device(devs[0]):
        single = train_one_chip(cfg1, one, impl="fused", seed=args.seed,
                                lr=args.lr)["loss"][0]
    diff = abs(ep - single)
    _say(f"smoke ragged first-step loss (1 layer): 1x4 {ep:.5f}, one chip "
         f"{single:.5f}, |diff| {diff:.2e} (tolerance {LOSS_TOL:.0e})")
    if not diff <= LOSS_TOL:
        fails.append(f"1x4 ragged vs one chip first-step loss differ by "
                     f"{diff}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    _say(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
         f"{cache} ({entries} entries at start)")
    full = get_config("fastmoe-gpt")
    _say(f"config fastmoe-gpt at published width: d_model {full.d_model}, "
         f"{full.moe.num_experts} experts top-{full.moe.top_k}, expert "
         f"hidden {full.moe.d_expert_hidden}, capacity factor "
         f"{full.moe.capacity_factor}, vocab {full.vocab_size}; depth is "
         f"cut: {full.num_layers} layers of f32 params + AdamW moments need "
         f"{_gb(full.param_count() * 12)}, one chip holds "
         f"{_gb((devs[0].memory_stats() or {}).get('bytes_limit', 0))}")
    _say(f"smoke readings only, not a benchmark: batch {BATCH} x seq {SEQ}, "
         f"seed {args.seed}")
    fails: list = []
    if args.chips == 4:
        four_chip_phase(full, args, fails)
    else:
        _say(f"config one chip: layers {full.num_layers} -> 1")
        one_chip_phase(dataclasses.replace(full, num_layers=1), args, fails)
    for f in fails:
        print(f"chip_smoke FAILED: {f}", file=sys.stderr)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
