"""Achieved rate of the expert exchange: the step's own ``wire_bytes``
counter (bytes per chip of the forward exchange, payload both ways plus the
counts, summed over layers; ``repro.obs.counters``) over ``exchange_ms``.
The time covers the forward exchange, its recompute and the backward, so
the rate reads the forward bytes over all of them."""
import numpy as np

from bench import scopes

READS = {"aux": "wire_bytes", "trace": "fmoe.exchange"}


def read(run):
    wire = run.aux.get(READS["aux"])
    if wire is None or len(wire) == 0 or not np.mean(wire):
        return None
    ms = scopes.read_stage(run, READS["trace"])
    if not ms:
        return None
    return float(np.mean(wire)) / (ms / 1e3) / 1e9
