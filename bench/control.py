"""Readings of the comparison on many seeds in one process: the program's
own first steps, the control and planted faults, each against the plain
reference.  This is how the limits in ``bench/limits/`` were set; the
benchmark's runs never call it.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        --variants program,fp8,half_batch:2

Variants:
  program     the program's step, as a run drives it (the lower reading);
  fp8, int8   the control: the reference itself in the program's place,
              every matmul in float8 e4m3 or in int8 (the precisions below
              the configuration's bfloat16);
  half_batch  fault: the program's step on the first half of each batch;
  no_exchange fault (several chips): the program's step with the expert
              all-to-all left out, each chip's experts fed its own buffer;
  stale       fault: the program's step returning its state unchanged.

Each line of output is one JSON object: workload, seed, variant, readings,
the three losses of both sides, and each leaf's norms (a stacked leaf by
layer, ``leaf#layer``) on both sides.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def planted(variant: str):
    """Break the program's timed path underneath the harness."""
    import jax
    import repro.launch.train as T
    from repro.core import pipeline

    saved = (T.make_train_step, pipeline._plain_all_to_all)
    orig = T.make_train_step

    @functools.wraps(orig)
    def broken(*a, **k):
        step = orig(*a, **k)

        def train_step(params, opt_state, batch, i):
            if variant == "half_batch":
                half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
                return step(params, opt_state, half, i)
            p, o, m = step(params, opt_state, batch, i)
            if variant == "stale":
                return params, opt_state, m
            if variant == "loss_altered":
                return p, o, dict(m, loss=m["loss"] * 1.01)
            return p, o, m
        return train_step

    if variant == "program":
        pass
    elif variant in ("half_batch", "stale", "loss_altered"):
        T.make_train_step = broken
    elif variant == "no_exchange":
        pipeline._plain_all_to_all = lambda x, *a, **k: x
    else:
        raise ValueError(f"unknown variant {variant!r}")
    try:
        yield
    finally:
        T.make_train_step, pipeline._plain_all_to_all = saved


def sweep(root: str, workload: str, seeds, variants, *, chip_check=True,
          compile_cache=True, bench_dir=None, out=sys.stdout):
    """Readings of each variant on each seed.  A variant ``name:n`` runs on
    the first ``n`` seeds only; the reference runs once a seed."""
    from bench import compare, harness, spec

    cell = spec.resolve(root, workload, bench_dir or spec.BENCH_DIR)
    devs = harness._devices(cell.chips, chip_check)
    if compile_cache:
        harness._compile_cache()
    refs, rows = {}, []
    for entry in variants:
        variant, _, n = entry.partition(":")
        prog = None
        for seed in seeds[:int(n)] if n else seeds:
            if variant in ("fp8", "int8"):
                prog = prog or harness.build_program(cell, devs)
                pool = harness.traffic.make_pool(
                    cell.traffic, cell.config["vocab_size"], seed)
                other = harness.run_reference(
                    cell, seed, pool, prog.groups, prog.mesh,
                    quant=cell.reference().QUANT[variant])
            else:
                with planted(variant):
                    prog = prog or harness.build_program(cell, devs)
                    key, params, opt, pool, dev_pool = harness.start(
                        cell, prog, seed)
                    other, params, opt = harness.first_steps(
                        cell, prog, key, params, opt, dev_pool)
                    del params, opt, dev_pool
            if seed not in refs:
                refs[seed] = harness.run_reference(cell, seed, pool,
                                                   prog.groups, prog.mesh)
            ref = refs[seed]
            row = {"workload": workload, "seed": seed, "variant": variant,
                   "readings": compare.readings(other, ref),
                   "loss": other["loss"], "ref_loss": ref["loss"],
                   "leaves": {k: {p: [other[k][p], ref[k][p]]
                                  for p in ref[k]}
                              for k in ("grad_layers", "update_layers")}}
            rows.append(row)
            print(json.dumps(row), file=out, flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,fp8")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    sweep(ROOT, args.workload, [int(s) for s in args.seeds.split(",")],
          args.variants.split(","))
    return 0


if __name__ == "__main__":
    sys.exit(main())
