"""Four fake devices: the expert-parallel cell is correct, and comes out not
correct with the exchange between chips left out."""
import json
import os
import subprocess
import sys

from bench.tests import tiny

SCRIPT = """
import sys, time
from bench import control, harness
with control.planted(sys.argv[2]):
    harness.run(sys.argv[1], "tiny.train", 3, 0.3, False, time.time(),
                chip_check=False, compile_cache=False,
                bench_dir=sys.argv[1] + "/bench")
"""


def _run(root, variant):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([tiny.REPO,
                                           os.path.join(tiny.REPO, "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, root, variant],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return tiny.result_line(p.stdout)


def test_exchange_left_out_is_not_correct(tmp_path):
    root = str(tmp_path)
    tiny.make(root, chips=4, arch="fastmoe-gpt", layers=2, top_k=2)
    assert _run(root, "program")["correct"] is True
    assert _run(root, "no_exchange")["correct"] is False
