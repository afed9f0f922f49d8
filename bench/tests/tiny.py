"""A tiny cell for the CPU tests: the benchmark copied into a scratch
checkout, plus one configuration, traffic mix and limits of test size."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")

# Readings of the unbroken program at this size on the CPU stay under these
# (loss <= 3.8e-3, grad <= 1.2e-2, update <= 6.9e-3 on the seeds the tests
# use); the fp8 control reads grad >= 2.7e-2 and each planted fault fails
# one of them.
LIMITS = {"loss": 7e-3, "grad": 2e-2, "update": 5e-2}


def make(root: str, *, chips: int = 1, arch: str = "switch-base-128",
         layers: int = 1, top_k: int = 1, extra_metric: str = "") -> str:
    """Write a checkout at ``root`` holding BENCHMARK.json with the one
    workload ``tiny.train``; returns its bench directory."""
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    gpt = arch == "fastmoe-gpt"
    conf = json.load(open(os.path.join(
        BENCH, "configs",
        "fastmoe-gpt.ep4.json" if gpt else "switch-base-128.l1.json")))
    sizes = dict(num_layers=layers, d_model=64, vocab_size=256, num_heads=4,
                 num_kv_heads=4, head_dim=16, num_experts=8, top_k=top_k,
                 d_expert_hidden=128)
    conf.update(name="tiny", **sizes)
    conf["reduced"] = {k: [None, v] for k, v in sizes.items()}
    _write(os.path.join(bench, "configs", "tiny.json"), conf)
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "zipf-markov.128x512.json")))
    mix.update(batch=8, seq_len=32, microbatches=1, pool_batches=4)
    _write(os.path.join(bench, "traffic", "tiny.json"), mix)
    _write(os.path.join(bench, "limits", "tiny.train.json"), LIMITS)
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="the test's stand-in")
    _write(os.path.join(bench, "peaks.json"), peaks)
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tiny.train", "config": "tiny",
                          "traffic": "tiny", "chips": chips, "why": "test"}]
    for m in spec["per_layer"]:
        m["workloads"] = ["tiny.train"]
    if extra_metric:
        spec["per_layer"].append({
            "name": extra_metric, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "tokens_per_s"})
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    return bench


def _write(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def result_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])
