"""Benchmark utilities: paper-style timing (warm-up + 16 reps, §5.1),
plus the shared metrics sink every fig script's rows land in
(repro.obs.sink — benchmarks/run.py points it at <out>/metrics.jsonl)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# fake host devices exist only on XLA:CPU; children that use them are pinned
# there, so on a machine with a chip none of them grabs or waits on it (the
# parent may hold the chip).  Their rows record this platform.
FAKE_DEVICE_PLATFORM = "cpu"

_RESULTS_DIR: str | None = None
_SINK = None


def set_results_dir(path: str | None) -> None:
    """Route :func:`record` / :func:`emit` telemetry to
    ``<path>/metrics.jsonl`` (None closes the sink)."""
    global _RESULTS_DIR, _SINK
    if _SINK is not None:
        _SINK.close()
        _SINK = None
    _RESULTS_DIR = path


def _sink():
    global _SINK
    if _SINK is None and _RESULTS_DIR is not None:
        from repro.obs import JsonlSink
        os.makedirs(_RESULTS_DIR, exist_ok=True)
        _SINK = JsonlSink(os.path.join(_RESULTS_DIR, "metrics.jsonl"),
                          append=True)
    return _SINK


def record(rec: dict) -> None:
    """Emit one telemetry record to the shared benchmark sink (no-op until
    :func:`set_results_dir` has pointed it somewhere)."""
    s = _sink()
    if s is not None:
        s.emit(rec)


def run_on_fake_devices(script: str, devices: int,
                        timeout: int = 560) -> str:
    """Run a child Python script on ``devices`` fake CPU devices; returns
    its stdout (raises with its stderr when it fails)."""
    env = dict(os.environ)
    env.update(PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS=FAKE_DEVICE_PLATFORM,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return out.stdout


def smoke_mode() -> bool:
    """CI smoke runs (benchmarks/run.py --smoke) only care that every
    registered fig script still executes end to end — timings are noise on
    shared runners, so reps collapse to the minimum."""
    return os.environ.get("REPRO_BENCH_SMOKE", "") == "1"


def timeit(fn, *args, reps: int = 16, warmup: int = 3) -> dict:
    """Median wall time per call in microseconds (paper runs 16 reps)."""
    if smoke_mode():
        reps, warmup = 1, 1
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts = np.asarray(ts)
    return {"us": float(np.median(ts) * 1e6), "std_us": float(ts.std() * 1e6)}


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)
    record({"kind": "bench", "name": name, "us": us, "derived": derived})
