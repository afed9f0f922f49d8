"""The reduction of a device trace by the program's named scopes: the HLO
table rules, self time, each new reader on a synthetic run, and a trace of
the scoped program recorded on the chip (a few steps of switch.train.l1 at
--trace 1, one TPU v5e, with the optimized HLO text of its step)."""
import gzip
import os
import shutil

import numpy as np
import pytest

from bench import harness, reduce, scopes
from bench.metrics import (attention_ms, combine_ms, device_idle_share,
                           dispatch_ms, exchange_gb_per_s, exchange_ms,
                           expert_ffn_ms, head_loss_ms, optimizer_ms,
                           router_ms, unscoped_share)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
UNSCOPED_XPLANE_GZ = os.path.join(DATA, "switch.train.l1.xplane.pb.gz")

HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%p, %p), metadata={op_type="mul" op_name="jit(train_step)/fmoe.router/mul" source_file="gate.py" source_line=3}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_type="dot_general" op_name="jit(train_step)/transpose(jvp(fmoe.exchange))/fmoe.experts/dot_general" source_file="fmoe.py" source_line=191}
  %all-to-all.2 = f32[4]{0} all-to-all(%fusion.3), channel_id=1, replica_groups={{0,1}}, dimensions={0}, metadata={op_type="all_to_all" op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/fmoe.exchange/all_to_all"}
  %fusion.4 = f32[4]{0} fusion(%all-to-all.2), kind=kLoop, calls=%fused_computation
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%a)
  ROOT %add.9 = f32[4]{0} add(%fusion.4, %a), metadata={op_type="add" op_name="jit(train_step)/add"}
}
"""


def test_scope_of_takes_the_innermost_through_wrappers():
    assert scopes.scope_of("jit(train_step)/fmoe.optimizer/sub") == \
        "fmoe.optimizer"
    assert scopes.scope_of(
        "jit(train_step)/transpose(jvp())/while/body/closed_call/"
        "fmoe.exchange/fmoe.experts/end,edh->enh/transpose") == "fmoe.experts"
    assert scopes.scope_of("jit(f)/transpose(jvp(fmoe.combine))/mul") == \
        "fmoe.combine"
    assert scopes.scope_of("jit(train_step)/jvp()/while/body/add") is None
    assert scopes.scope_of("jit(f)/fmoe.routers/fmoe.py") is None
    assert scopes.scope_of(None) is None and scopes.scope_of("") is None


def test_hlo_table_rules():
    """Innermost scope, jvp/transpose wrappers, a fusion by its own metadata
    (not its fused root's), no metadata or no scope: unscoped."""
    t = scopes.table_from_hlo(HLO)
    assert t["multiply.1"] == "fmoe.router"  # inside the fused computation
    assert t["fusion.3"] == "fmoe.experts"  # innermost, through the wrappers
    assert t["all-to-all.2"] == "fmoe.exchange"
    assert t["fusion.4"] is None  # no metadata of its own: unscoped
    assert t["copy-start.1"] is None and t["a"] is None
    assert t["add.9"] is None  # metadata without a scope


def test_self_time_while_keeps_only_its_own_time():
    """A while whose body ops run inside it keeps the time they leave
    uncovered; the self times sum to busy_ns, in and across the window."""
    ops = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 40, 90),
           ("copy.1", 95, 100), ("all-to-all.1", 120, 130),
           ("fusion.1", 125, 140)]  # partly overlaps the all-to-all
    got = scopes.self_ns(ops, 0, 200)
    assert got == {"while.1": 25, "fusion.1": 20 + 15, "fusion.2": 50,
                   "copy.1": 5, "all-to-all.1": 5}
    tr = reduce.Trace(devices={0: ops})
    assert sum(got.values()) == reduce.busy_ns(tr, 0, 200)[0] == 120
    clipped = scopes.self_ns(ops, 20, 128)
    assert clipped == {"fusion.1": 10 + 3, "while.1": 15, "fusion.2": 50,
                       "copy.1": 5, "all-to-all.1": 5}
    assert sum(clipped.values()) == reduce.busy_ns(tr, 20, 128)[0]


def _run(devices, table, steps=2, wire=None, window=(0, 1000)):
    run = harness.RunData(
        chips=len(devices), steps=steps, tokens_per_s=1.0,
        flops_per_token=1.0, peaks={},
        aux={} if wire is None else {"wire_bytes": np.asarray(wire)},
        counters={}, trace=reduce.Trace(devices=devices), window_ns=window)
    run.scope_table = table
    return run


TABLE = {"fusion.r": "fmoe.router", "fusion.d": "fmoe.dispatch",
         "all-to-all.x": "fmoe.exchange", "fusion.e": "fmoe.experts",
         "fusion.c": "fmoe.combine", "fusion.a": "fmoe.attention",
         "fusion.h": "fmoe.head_loss", "fusion.o": "fmoe.optimizer",
         "while.1": None, "copy.1": None}


def test_each_reader_on_a_synthetic_run():
    """Two chips, two steps: per-step ms of self time, mean over chips."""
    ms = 1e6  # ns
    dev0 = [("while.1", 0, 60 * ms), ("fusion.r", 1 * ms, 3 * ms),
            ("fusion.d", 3 * ms, 7 * ms), ("all-to-all.x", 7 * ms, 15 * ms),
            ("fusion.e", 15 * ms, 35 * ms), ("fusion.c", 35 * ms, 41 * ms),
            ("fusion.a", 41 * ms, 51 * ms), ("fusion.h", 60 * ms, 70 * ms),
            ("fusion.o", 70 * ms, 90 * ms), ("copy.1", 90 * ms, 92 * ms),
            ("fusion.new", 92 * ms, 94 * ms)]  # not in the table
    dev1 = [(n, s + 100 * ms, e + 100 * ms) for n, s, e in dev0]
    run = _run({0: dev0, 1: dev1}, TABLE, wire=[4e9, 4e9],
               window=(0, 300 * ms))
    per_step = lambda t: t / 2  # the same ops on both chips, two steps
    assert router_ms.read(run) == pytest.approx(per_step(2))
    assert dispatch_ms.read(run) == pytest.approx(per_step(4))
    assert exchange_ms.read(run) == pytest.approx(per_step(8))
    assert expert_ffn_ms.read(run) == pytest.approx(per_step(20))
    assert combine_ms.read(run) == pytest.approx(per_step(6))
    assert attention_ms.read(run) == pytest.approx(per_step(10))
    assert head_loss_ms.read(run) == pytest.approx(per_step(10))
    assert optimizer_ms.read(run) == pytest.approx(per_step(20))
    # unscoped: the while's own 1 + 9 ms, copy 2, the missing op 2
    assert unscoped_share.read(run) == pytest.approx(100 * 14 / 94)
    # 4 GB a step over 4 ms of exchange a step
    assert exchange_gb_per_s.read(run) == pytest.approx(4e9 / 4e-3 / 1e9)
    stages = scopes.stage_ms(run)
    busy = reduce.busy_ns(run.trace, *run.window_ns)
    assert sum(stages.values()) == pytest.approx(
        sum(busy.values()) / 2 / 2 / 1e6)


def test_readers_read_nothing_without_scopes_or_trace():
    """A program without scopes (the table names none), a run without a
    device trace, and an exchange that moved no bytes read nothing."""
    ops = {0: [("fusion.r", 0, 10)]}
    unscoped = _run(ops, {"fusion.r": None})
    no_trace = _run({}, TABLE)
    for reader in (router_ms, dispatch_ms, exchange_ms, expert_ffn_ms,
                   combine_ms, attention_ms, head_loss_ms, optimizer_ms,
                   unscoped_share, exchange_gb_per_s):
        assert reader.read(unscoped) is None
        assert reader.read(no_trace) is None
    local = _run(ops, TABLE, wire=[0.0])
    assert router_ms.read(local) == pytest.approx(10 / 2 / 1e6)
    assert exchange_ms.read(local) == 0.0
    assert exchange_gb_per_s.read(local) is None


def _unzip(src, tmp_path, name):
    path = tmp_path / name
    with gzip.open(src) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(path)


def test_unscoped_program_trace_reads_nothing(tmp_path, monkeypatch):
    """The trace of the program before it had scopes holds its step's
    HloProto: every op is in the table, none is scoped, so the readers,
    finding the trace where the harness records it, read nothing, and the
    readings the benchmark had stay as they were."""
    root = tmp_path / "checkout"
    prof = root / scopes.TRACE_DIR / "switch.train.l1" / "plugins" / \
        "profile" / "run"
    prof.mkdir(parents=True)
    path = _unzip(UNSCOPED_XPLANE_GZ, prof, "host.xplane.pb")
    monkeypatch.setattr(scopes, "ROOT", str(root))
    assert scopes.newest_trace() == path
    table = scopes.table_from_xplane(path)
    tr = reduce.load(path)
    lo, hi = reduce.span(tr, "bench.window")
    assert {n for n, _, _ in tr.devices[0]} <= set(table)
    assert set(table.values()) == {None}
    run = harness.RunData(chips=1, steps=3, tokens_per_s=1.0,
                          flops_per_token=1.0, peaks={}, aux={}, counters={},
                          trace=tr, window_ns=(lo, hi))
    assert router_ms.read(run) is None and unscoped_share.read(run) is None
    assert 0.0 < device_idle_share.read(run) < 10.0


SCOPED_XPLANE_GZ = os.path.join(DATA, "switch.train.l1.scoped.xplane.pb.gz")
SCOPED_HLO_GZ = os.path.join(DATA, "switch.train.l1.scoped.hlo.txt.gz")


def test_recorded_scoped_chip_trace(tmp_path):
    """On the chip's trace of the scoped program: the table read from the
    trace's HloProto equals the one parsed from the step's optimized HLO
    text, every device op is in it, the stages plus the unscoped rest equal
    busy_ns within 0.1%, every stage but the exchange (one chip) reads
    time, and unscoped_share is under 20%."""
    path = _unzip(SCOPED_XPLANE_GZ, tmp_path, "scoped.xplane.pb")
    with gzip.open(SCOPED_HLO_GZ, "rt") as f:
        from_text = scopes.table_from_hlo(f.read())
    table = scopes.table_from_xplane(path)
    tr = reduce.load(path)
    lo, hi = reduce.span(tr, "bench.window")
    on_device = {n for n, _, _ in tr.devices[0]}
    assert on_device <= set(table) and on_device <= set(from_text)
    assert {n: table[n] for n in from_text} == from_text
    steps = sum(n == "bench.dispatch_step" and lo <= s <= hi
                for n, s, _ in tr.host)
    run = harness.RunData(chips=1, steps=steps, tokens_per_s=1.0,
                          flops_per_token=1.0, peaks={}, aux={}, counters={},
                          trace=tr, window_ns=(lo, hi))
    run.scope_table = table
    ms = scopes.stage_ms(run)
    busy = reduce.busy_ns(tr, lo, hi)[0] / steps / 1e6
    assert sum(ms.values()) == pytest.approx(busy, rel=1e-3)
    assert ms[scopes.MISSING] == 0.0 and ms["fmoe.exchange"] == 0.0
    assert all(ms[s] > 0 for s in scopes.SCOPES if s != "fmoe.exchange")
    assert 0.0 < unscoped_share.read(run) < 20.0
    # AdamW over the experts is the optimizer's time (~24 ms a step)
    assert 20.0 < optimizer_ms.read(run) < 30.0
