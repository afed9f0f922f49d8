"""Device time per step of the collectives (all-to-all, collective-permute,
all-reduce, all-gather, reduce-scatter), mean over chips."""
from bench import reduce

READS = {"ops": list(reduce.COLLECTIVES)}


def read(run):
    if run.trace is None or not run.trace.devices or not run.steps:
        return None
    lo, hi = run.window_ns
    t = reduce.kind_ns(run.trace, lo, hi)
    if not any(t.values()):
        return None
    return sum(t.values()) / len(t) / run.steps / 1e6
