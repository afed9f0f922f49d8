"""The command without a chip, and with nothing but the benchmark's files,
exits non-zero and prints no result; a tiny cell runs end to end on the CPU
with the chip check skipped, traced, through files and entries alone."""
import json
import os
import shutil
import subprocess
import sys
import time

from bench import harness
from bench.tests import tiny

REPO = tiny.REPO


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "switch.train.l1",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert '"metrics"' not in line and '"correct"' not in line


def test_no_tpu_no_result():
    _no_result(_run_cli(REPO))


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    _no_result(_run_cli(str(tmp_path), env))


def test_tiny_cell_traced_end_to_end(tmp_path, capsys):
    bench = tiny.make(str(tmp_path), extra_metric="steps_seen")
    with open(os.path.join(bench, "metrics", "steps_seen.py"), "w") as f:
        f.write('READS = {"host": "steps"}\n\n\n'
                'def read(run):\n    return float(run.steps)\n')
    rc = harness.run(str(tmp_path), "tiny.train", 2**31 + 5, 0.5, True,
                     time.time(), chip_check=False, compile_cache=False,
                     bench_dir=bench)
    out = capsys.readouterr()
    res = tiny.result_line(out.out)
    assert rc == 0 and res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss", "grad", "update"}
    m = res["metrics"]
    assert m["steps_seen"]["value"] == res["attempted"] >= 1
    assert m["compiles_in_window"]["value"] == 0
    assert 0 <= m["drop_share"]["value"] < 100
    assert res["device"]["platform"] == "cpu" and "window_s" in res["device"]
    assert "breakdown" in res
    assert out.err.strip().splitlines()[-1].startswith("check update ")
