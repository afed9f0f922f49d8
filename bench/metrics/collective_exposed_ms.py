"""The part of ``collective_ms`` in which no other operation ran on that
chip, per step, mean over chips."""
from bench import reduce

READS = {"ops": list(reduce.COLLECTIVES)}


def read(run):
    if run.trace is None or not run.trace.devices or not run.steps:
        return None
    lo, hi = run.window_ns
    if not any(reduce.kind_ns(run.trace, lo, hi).values()):
        return None
    t = reduce.exposed_ns(run.trace, lo, hi)
    return sum(t.values()) / len(t) / run.steps / 1e6
