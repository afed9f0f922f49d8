"""A run with the timed path broken underneath comes out not correct: the
state returned unchanged, half of the batch left out, the loss altered where
it is produced."""
import time

import pytest

from bench import control, harness
from bench.tests import tiny


@pytest.mark.parametrize("fault", ["stale", "half_batch", "loss_altered"])
def test_fault_is_not_correct(fault, tmp_path, capsys):
    bench = tiny.make(str(tmp_path))
    with control.planted(fault):
        harness.run(str(tmp_path), "tiny.train", 1, 0.3, False, time.time(),
                    chip_check=False, compile_cache=False, bench_dir=bench)
    res = tiny.result_line(capsys.readouterr().out)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", ["stale", "half_batch", "loss_altered"])
def test_fault_is_not_correct_top2(fault, tmp_path, capsys):
    """The same on one chip with FastMoE's top-2 routing (gpt.train.l1's
    path)."""
    bench = tiny.make(str(tmp_path), arch="fastmoe-gpt", top_k=2)
    with control.planted(fault):
        harness.run(str(tmp_path), "tiny.train", 2, 0.3, False, time.time(),
                    chip_check=False, compile_cache=False, bench_dir=bench)
    res = tiny.result_line(capsys.readouterr().out)
    assert res["correct"] is False, res["checks"]


def test_top2_program_is_correct(tmp_path, capsys):
    bench = tiny.make(str(tmp_path), arch="fastmoe-gpt", top_k=2)
    harness.run(str(tmp_path), "tiny.train", 2, 0.3, False, time.time(),
                chip_check=False, compile_cache=False, bench_dir=bench)
    res = tiny.result_line(capsys.readouterr().out)
    assert res["correct"] is True, res["checks"]
