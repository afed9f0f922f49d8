"""Benchmark of FastMoE training on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips JAX finds: set-up (weights
and traffic from the seed, the program's step compiled or read from the
compile cache at ``<checkout>/.jax_cache``, three first steps), then
``--seconds`` of training steps, then the comparison with the plain
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last the ``checks`` (each number compared
beside its limit, also the last lines of standard error).  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness

    return harness.run(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), T0)


if __name__ == "__main__":
    sys.exit(main())
