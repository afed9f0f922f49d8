"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips: 1 - (union of device-op intervals) / window."""
from bench import reduce

READS = {"trace": "device_ops"}


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.window_ns
    busy = reduce.busy_ns(run.trace, lo, hi)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (hi - lo))
