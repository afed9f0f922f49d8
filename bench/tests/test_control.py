"""The controls, the plain reference computed in float8 or in int8 in the
program's place, come out not correct where the program itself is
correct."""
import pytest

from bench import compare, control
from bench.tests import tiny

SEEDS = [1, 2]


@pytest.mark.parametrize("variant", ["fp8", "int8"])
def test_control_fails_where_the_program_passes(variant, tmp_path):
    bench = tiny.make(str(tmp_path))
    rows = control.sweep(str(tmp_path), "tiny.train", SEEDS,
                         ["program", variant], chip_check=False,
                         compile_cache=False, bench_dir=bench)
    for row in rows:
        ok, _ = compare.judge(row["readings"], tiny.LIMITS)
        assert ok == (row["variant"] == "program"), row


def test_int8_control_fails_on_the_top2_path(tmp_path):
    """gpt.train.l1's control, on one chip with FastMoE's top-2 routing."""
    bench = tiny.make(str(tmp_path), arch="fastmoe-gpt", top_k=2)
    rows = control.sweep(str(tmp_path), "tiny.train", SEEDS,
                         ["program", "int8"], chip_check=False,
                         compile_cache=False, bench_dir=bench)
    for row in rows:
        ok, _ = compare.judge(row["readings"], tiny.LIMITS)
        assert ok == (row["variant"] == "program"), row
