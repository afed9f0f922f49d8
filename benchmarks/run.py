"""Benchmark driver — one module per paper figure.  Prints
``name,us_per_call,derived`` CSV and writes JSON results.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig5,fig7]
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: quick shapes + minimum timing reps "
                         "(REPRO_BENCH_SMOKE=1); every registered fig script "
                         "must run end to end or the process fails")
    ap.add_argument("--only", default="")
    ap.add_argument("--out", default="benchmarks/results")
    args = ap.parse_args()
    if args.smoke:
        args.quick = True
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from benchmarks import (fig3_gemm, fig5_single_device, fig6_scaling,
                            fig7_end_to_end, fig8_imbalance, fig9_overlap,
                            fig10_train_step, fig11_serving, tab_capacity)
    suites = {
        "fig3": fig3_gemm.run,
        "fig5": fig5_single_device.run,
        "fig6": fig6_scaling.run,
        "fig7": fig7_end_to_end.run,
        "fig8": fig8_imbalance.run,
        "fig9": fig9_overlap.run,
        "fig10": fig10_train_step.run,
        "fig11": fig11_serving.run,
        "tab_capacity": tab_capacity.run,
    }
    picked = args.only.split(",") if args.only else list(suites)

    os.makedirs(args.out, exist_ok=True)
    # unified telemetry (repro.obs): every fig's rows also land in
    # <out>/metrics.jsonl via benchmarks.common
    from benchmarks import common
    common.set_results_dir(args.out)
    # merge into existing results so `--only fig9` doesn't drop fig8's rows
    # (results.json also feeds repro.placement.calibrate)
    results = {}
    path = os.path.join(args.out, "results.json")
    if os.path.exists(path):
        try:
            with open(path) as f:
                results = json.load(f)
        except (OSError, ValueError):
            results = {}
    print("name,us_per_call,derived")
    for name in picked:
        t0 = time.time()
        results[name] = suites[name](quick=args.quick)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    results["wire_summary"] = _wire_summary(results)
    with open(os.path.join(args.out, "results.json"), "w") as f:
        json.dump(results, f, indent=1)
    common.set_results_dir(None)  # flush + close metrics.jsonl
    print(f"# wrote {args.out}/results.json")
    print(f"# wrote {args.out}/metrics.jsonl")


def _wire_summary(results: dict) -> dict:
    """Collect the measured-vs-modeled wire-byte evidence rows (fig9/fig10)
    into one top-level block (experiments/summarize.py renders it)."""
    out: dict = {}
    for row in results.get("fig9", []):
        for k in ("wire_bytes_serial", "hlo_bytes_serial",
                  "wire_bytes_pipelined", "hlo_bytes_pipelined",
                  "wire_bytes_bf16", "hlo_bytes_bf16"):
            if k in row:
                out.setdefault("fig9", {})[k] = row[k]
        # two-level ragged exchange: flat / dropless / auto-calibrated
        # bounds, with the inter-node (slow-link) share broken out
        h = row.get("hier") or {}
        for k in ("wire_bytes_flat", "hlo_bytes_flat", "wire_bytes_hier",
                  "hlo_bytes_hier", "wire_bytes_auto", "hlo_bytes_auto",
                  "wire_bytes_flat_inter", "wire_bytes_hier_intra",
                  "wire_bytes_hier_inter", "wire_bytes_auto_intra",
                  "wire_bytes_auto_inter"):
            if k in h:
                out.setdefault("fig9_hier", {})[k] = h[k]
    for row in results.get("fig10", []):
        if row.get("distributed") and "wire_bytes" in row:
            key = f"{row['dispatch']}_{row['wire_dtype']}"
            entry = {"wire_bytes": row["wire_bytes"],
                     "hlo_fwd_bytes": row["hlo_fwd_bytes"]}
            if "wire_bytes_inter" in row:
                entry["wire_bytes_intra"] = row["wire_bytes_intra"]
                entry["wire_bytes_inter"] = row["wire_bytes_inter"]
            out.setdefault("fig10", {})[key] = entry
    return out


if __name__ == "__main__":
    main()
