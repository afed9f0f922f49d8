"""Name resolution: every configuration, traffic mix, per-layer metric and
cell limit is a file of its own, found by the name ``BENCHMARK.json`` gives.

- configuration ``<c>``: the ``file`` of its ``configs`` entry (JSON sizes),
  whose ``reference`` key names ``bench/reference/<reference>.py``, the
  plain reference that also owns the model's parameter tree, what it covers
  and its FLOPs per token;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``;
- per-layer metric ``<m>``: ``bench/metrics/<m>.py``;
- cell ``<w>``: its comparison limits in ``bench/limits/<w>.json``.

Adding a cell, a configuration, a mix or a metric is adding files and
entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One resolved workload of ``BENCHMARK.json``."""

    name: str
    chips: int
    config_name: str
    config: dict  # the configuration file's content
    traffic: dict  # the traffic file's content
    limits: dict  # the cell's comparison limits
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1
    reference_path: str
    bench_dir: str
    _reference: Any = field(default=None, repr=False, compare=False)

    def metric_reader(self, name: str):
        return _load_module(os.path.join(self.bench_dir, "metrics",
                                         f"{name}.py"), f"bench_metric_{name}")

    def reference(self):
        """The configuration's reference module, loaded once per cell."""
        if self._reference is None:
            self._reference = _load_module(
                self.reference_path,
                "bench_reference_" + self.config["reference"])
        return self._reference


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(root: str, workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    """Resolve ``workload`` of ``<root>/BENCHMARK.json`` to its files."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    centry = configs[w["config"]]
    config = load_json(os.path.join(root, centry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    limits = load_json(os.path.join(bench_dir, "limits", f"{workload}.json"))
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    ref = os.path.join(bench_dir, "reference", f"{config['reference']}.py")
    if not os.path.isfile(ref):
        raise FileNotFoundError(ref)
    for m in per_layer:
        p = os.path.join(bench_dir, "metrics", f"{m['name']}.py")
        if not os.path.isfile(p):
            raise FileNotFoundError(p)
    return Cell(workload, int(w["chips"]), w["config"], config, traffic,
                limits, e2e, per_layer, ref, bench_dir)
