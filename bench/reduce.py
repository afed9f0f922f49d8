"""Profiler trace (``.xplane.pb``) -> device intervals and host spans.

Read with ``jax.profiler.ProfileData`` only.  Device operations are the
events of each device plane's "XLA Ops" line (what the TensorCore runs);
collectives also count their in-flight intervals from the "Async XLA Ops"
line.  An event's name is the whole HLO instruction; operations are named
by its left-hand side (``fusion.57``, ``all-to-all.3``).  Host spans are the
benchmark's own ``jax.profiler.TraceAnnotation`` events (``bench.*``) on the
host plane, which share the profiler's clock.  All times are nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PREFIX = "bench."
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "ragged-all-to-all")


@dataclass
class Trace:
    # device id -> [(op name, start, end)], sorted by start
    devices: dict = field(default_factory=dict)
    # device id -> [(op name, start, end)] of the async (in-flight) line
    asyncs: dict = field(default_factory=dict)
    # [(span name, start, end)] of the benchmark's host spans
    host: list = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """``%fusion.57 = (f32[...]) fusion(...)`` -> ``fusion.57``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                into = tr.devices if line.name == OPS_LINE else tr.asyncs
                into[int(m.group(2))] = sorted(
                    ((op_name(e.name), e.start_ns, e.end_ns)
                     for e in line.events), key=lambda op: op[1])
            elif not m:
                tr.host.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events
                               if e.name.startswith(HOST_PREFIX))
    tr.host.sort(key=lambda s: s[1])
    return tr


def span(tr: Trace, name: str) -> tuple:
    """(start, end) of the first host span called ``name``."""
    for n, s, e in tr.host:
        if n == name:
            return s, e
    raise KeyError(f"no host span {name!r} in the trace")


def union(intervals, lo: float, hi: float) -> list:
    """Merge (start, end) pairs, clipped to [lo, hi]."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVES)


def busy_ns(tr: Trace, lo: float, hi: float) -> dict:
    """Per device: time in which some operation ran, within [lo, hi]."""
    return {d: length(union(((s, e) for _, s, e in ops), lo, hi))
            for d, ops in tr.devices.items()}


def _kind(tr: Trace, d, lo: float, hi: float, pred) -> list:
    ops = tr.devices[d] + tr.asyncs.get(d, [])
    return union(((s, e) for n, s, e in ops if pred(n)), lo, hi)


def kind_ns(tr: Trace, lo: float, hi: float, pred=is_collective) -> dict:
    """Per device: time in which an operation matching ``pred`` ran or was
    in flight."""
    return {d: length(_kind(tr, d, lo, hi, pred)) for d in tr.devices}


def exposed_ns(tr: Trace, lo: float, hi: float, pred=is_collective) -> dict:
    """Per device: time in which a ``pred`` operation ran or was in flight
    and no other operation ran."""
    out = {}
    for d, ops in tr.devices.items():
        mine = _kind(tr, d, lo, hi, pred)
        other = union(((s, e) for n, s, e in ops if not pred(n)), lo, hi)
        out[d] = length(mine) - _overlap(mine, other)
    return out


def _overlap(a, b) -> float:
    """Total overlap of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[op name, seconds]]: device time per op name, mean over devices."""
    tot: dict = {}
    for ops in tr.devices.values():
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                tot[name] = tot.get(name, 0.0) + (e - s)
    k = max(len(tr.devices), 1)
    return [[name, t / k / 1e9]
            for name, t in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[host span, seconds]]: the longest gaps in which no operation ran on
    the first device, each named by the benchmark span that overlaps it
    most ("host" where none does)."""
    if not tr.devices:
        return []
    busy = union(((s, e) for _, s, e in tr.devices[min(tr.devices)]), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    spans = [h for h in tr.host if h[0] != "bench.window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, label = 0.0, "host"
        for name, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, name
        out.append([label, (e - s) / 1e9])
    return out
