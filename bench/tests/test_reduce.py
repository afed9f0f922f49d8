"""The trace reduction on a trace recorded on the chip (a few steps of
switch.train.l1 at --trace 1, one TPU v5e) and on hand-made intervals."""
import gzip
import os
import shutil

import pytest

from bench import reduce
from bench.metrics import collective_exposed_ms, collective_ms
from bench.metrics import device_idle_share

XPLANE_GZ = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                         "switch.train.l1.xplane.pb.gz")


class _Run:
    def __init__(self, trace, lo, hi, steps=1):
        self.trace, self.window_ns, self.steps = trace, (lo, hi), steps


def test_union_merges_and_clips():
    got = reduce.union([(5, 9), (0, 3), (2, 4), (8, 12), (20, 30)], 1, 25)
    assert got == [[1, 4], [5, 12], [20, 25]]
    assert reduce.length(got) == 15
    assert reduce.union([(0, 1)], 2, 3) == []


def test_collective_filter_and_exposure():
    ops = [("fusion.1", 0, 10), ("all-to-all.3", 5, 15),
           ("all-reduce-start.2", 20, 30), ("convolution.4", 22, 24),
           ("collective-permute-done.1", 40, 42), ("copy.7", 50, 51)]
    tr = reduce.Trace(devices={0: ops, 1: [("fusion.2", 0, 100)]})
    assert [n for n, _, _ in ops if reduce.is_collective(n)] == [
        "all-to-all.3", "all-reduce-start.2", "collective-permute-done.1"]
    assert reduce.kind_ns(tr, 0, 100) == {0: 22, 1: 0}
    # all-to-all 10..15 and all-reduce 20..22, 24..30, permute 40..42
    assert reduce.exposed_ns(tr, 0, 100) == {0: 15, 1: 0}
    run = _Run(tr, 0, 100, steps=2)
    assert collective_ms.read(run) == pytest.approx(22 / 2 / 2 / 1e6)
    assert collective_exposed_ms.read(run) == pytest.approx(15 / 2 / 2 / 1e6)
    busy = reduce.busy_ns(tr, 0, 100)
    assert busy == {0: 15 + 10 + 2 + 1, 1: 100}
    share = device_idle_share.read(run)
    assert share == pytest.approx(100 * (1 - (28 + 100) / 2 / 100))


def test_no_collectives_reads_nothing():
    tr = reduce.Trace(devices={0: [("fusion.1", 0, 10)]})
    assert collective_ms.read(_Run(tr, 0, 10)) is None
    assert collective_exposed_ms.read(_Run(tr, 0, 10)) is None
    assert device_idle_share.read(_Run(reduce.Trace(), 0, 10)) is None


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "switch.train.l1.xplane.pb"
    with gzip.open(XPLANE_GZ) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    tr = reduce.load(str(path))
    lo, hi = reduce.span(tr, "bench.window")
    assert list(tr.devices) == [0] and len(tr.devices[0]) > 1000
    assert tr.devices[0][0][0] == "slice-start"  # named by the HLO's lhs
    ops = tr.devices[0]
    # the device ops lie on the host spans' clock, inside the window
    inside = sum(lo <= s and e <= hi for _, s, e in ops)
    assert inside > 0.9 * len(ops)
    share = device_idle_share.read(_Run(tr, lo, hi))
    assert 0.0 < share < 10.0  # 2.6% in this trace: one host read a step
    names = {n for n, _, _ in tr.host}
    assert {"bench.window", "bench.dispatch_step", "bench.read_loss"} <= names
    top = reduce.top_ops(tr, lo, hi)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    gaps = reduce.idle_gaps(tr, lo, hi)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert not any(reduce.is_collective(n) for n, _, _ in ops)
