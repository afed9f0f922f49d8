"""Device time by stage of the train step, read from the program's
``jax.named_scope`` names.

The program opens eight scopes (``fmoe.router`` ... ``fmoe.optimizer``; see
``repro/obs/scopes.py``).  Each HLO instruction carries the name stack it was
traced under as ``metadata={op_name="jit(train_step)/.../fmoe.router/..."}``;
its stage is the innermost of the eight names there, read through the
``jvp(``/``transpose(`` wrappers of the backward pass.  A fusion has its own
metadata, which XLA copies from the fused root.  An instruction with no
metadata, or none of the eight names in it, is unscoped.

The table {instruction name: stage} comes from the compiled step: from its
optimized HLO text (:func:`table_from_hlo`), or from the ``HloProto`` the
profiler writes for every module it saw into the trace's ``/host:metadata``
plane (:func:`table_from_xplane`; the same module, so the same table).  The
trace's device ops are then reduced to self time (:func:`self_ns`) and summed
by stage; the stages plus the unscoped rest equal ``reduce.busy_ns``.

This module mirrors the program's names rather than importing them, so it
runs against a program without scopes and then finds none.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Optional

from bench import reduce

SCOPES = ("fmoe.router", "fmoe.dispatch", "fmoe.exchange", "fmoe.experts",
          "fmoe.combine", "fmoe.attention", "fmoe.head_loss",
          "fmoe.optimizer")
UNSCOPED = "unscoped"  # in the table, no stage
MISSING = "missing"  # on the device, not in the table

_WRAPPER = re.compile(r"[\w\-]+\(|\)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = ".bench_trace"  # where bench/harness.py records a traced run


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost stage named in an ``op_name`` path, or None."""
    if not op_name:
        return None
    for part in reversed(_WRAPPER.sub("", op_name).split("/")):
        if part in SCOPES:
            return part
    return None


def table_from_hlo(text: str) -> dict:
    """{instruction name: stage or None} of an optimized HLO module's text
    (``compiled.as_text()``), each instruction by its own metadata."""
    table = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        meta = line.rfind("metadata={")
        op = _OP_NAME.search(line, meta) if meta >= 0 else None
        table[m.group(1)] = scope_of(op.group(1) if op else None)
    return table


# -- the HloProto inside an .xplane.pb, read from the protobuf wire format --
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry: value
# = 2), .stat_metadata = 5; XEventMetadata.stats = 5; XStat.metadata_id = 1,
# .bytes_value = 6; XStatMetadata.name = 2.  HloProto.hlo_module = 1;
# HloModuleProto.computations = 3; HloComputationProto.instructions = 2;
# HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2.
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf, lo: int, hi: int):
    """(field number, value) of one message in ``buf[lo:hi]``; a
    length-delimited value is its (start, end)."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _sub(buf, span, number: int):
    return [v for f, v in _fields(buf, *span) if f == number]


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _hlo_protos(buf) -> list:
    """Spans of each HloProto in the trace's metadata plane."""
    out = []
    for plane in _sub(buf, (0, len(buf)), 1):
        names = _sub(buf, plane, 2)
        if not names or _text(buf, names[0]) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in _sub(buf, plane, 5):
            for md in _sub(buf, entry, 2):
                ids, nm = _sub(buf, md, 1), _sub(buf, md, 2)
                if ids and nm:
                    stat_names[ids[0]] = _text(buf, nm[0])
        for entry in _sub(buf, plane, 4):
            for md in _sub(buf, entry, 2):
                for stat in _sub(buf, md, 5):
                    sid = _sub(buf, stat, 1)
                    if sid and stat_names.get(sid[0]) == HLO_PROTO_STAT:
                        out.extend(_sub(buf, stat, 6))
    return out


def _module_table(buf, proto) -> dict:
    table = {}
    for module in _sub(buf, proto, 1):
        for comp in _sub(buf, module, 3):
            for instr in _sub(buf, comp, 2):
                name = op = None
                for f, v in _fields(buf, *instr):
                    if f == 1:
                        name = _text(buf, v)
                    elif f == 7:
                        ops = _sub(buf, v, 2)
                        op = _text(buf, ops[0]) if ops else None
                if name is not None:
                    table[name] = scope_of(op)
    return table


def table_from_xplane(path: str) -> Optional[dict]:
    """The stage table of the modules whose HloProto the trace holds (the
    largest module first where two name one instruction), or None."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    tables = sorted((_module_table(buf, p) for p in _hlo_protos(buf)),
                    key=len, reverse=True)
    if not tables:
        return None
    out: dict = {}
    for t in tables:
        for k, v in t.items():
            out.setdefault(k, v)
    return out


# -- self time --

def self_ns(ops, lo: float, hi: float) -> dict:
    """{op name: self time} of one device's "XLA Ops" line within [lo, hi]:
    each instant of the busy time goes to the innermost op running then
    (the latest started), so a ``while`` keeps only the time its body ops
    leave uncovered, and the values sum to ``reduce.busy_ns``."""
    out: dict = {}
    stack: list = []  # [name, end] of the open ops, outermost first
    t = lo

    def advance(until):
        nonlocal t
        while stack and stack[-1][1] <= until:
            name, end = stack.pop()
            if end > t:
                out[name] = out.get(name, 0.0) + (end - t)
                t = end
        if stack and until > t:
            name = stack[-1][0]
            out[name] = out.get(name, 0.0) + (until - t)
        t = max(t, until)

    for name, s, e in sorted(ops, key=lambda op: (op[1], -op[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        advance(s)
        stack.append([name, e])
    advance(hi)
    return out


def stage_ns(tr: reduce.Trace, table: dict, lo: float, hi: float) -> dict:
    """Per device: {stage, UNSCOPED or MISSING: self time} in [lo, hi]."""
    out = {}
    for d, ops in tr.devices.items():
        by = {}
        for name, ns in self_ns(ops, lo, hi).items():
            k = table[name] if name in table else MISSING
            k = UNSCOPED if k is None else k
            by[k] = by.get(k, 0.0) + ns
        out[d] = by
    return out


def newest_trace(root: Optional[str] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` the harness recorded under ``root`` (the
    checkout this module is in)."""
    paths = glob.glob(os.path.join(root or ROOT, TRACE_DIR, "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def stage_ms(run) -> Optional[dict]:
    """{stage, UNSCOPED, MISSING: device self time per step in ms, mean over
    chips} of a traced run, or None where there is no device trace or the
    program names none of the stages; reduced once and kept on the run as
    ``run.stage_ms``.  The table is ``run.scope_table`` where the run
    carries one, else the one in the newest trace the harness recorded in
    this checkout, which is the run's own: readers run after its window,
    before another run can start."""
    if not hasattr(run, "stage_ms"):
        run.stage_ms = _stage_ms(run)
    return run.stage_ms


def _stage_ms(run) -> Optional[dict]:
    tr = run.trace
    if tr is None or not tr.devices or not run.steps:
        return None
    table = getattr(run, "scope_table", None)
    if table is None:
        path = newest_trace()
        table = table_from_xplane(path) if path else None
    if not table or all(v is None for v in table.values()):
        return None
    per = stage_ns(tr, table, *run.window_ns)
    return {k: sum(by.get(k, 0.0) for by in per.values()) / len(per)
            / run.steps / 1e6 for k in SCOPES + (UNSCOPED, MISSING)}


def read_stage(run, stage: str) -> Optional[float]:
    ms = stage_ms(run)
    return None if ms is None else ms[stage]
