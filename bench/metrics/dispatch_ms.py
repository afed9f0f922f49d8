"""Device self time per step of the dispatch (``fmoe.dispatch``: the capacity
or ragged plan and the scatter into expert buffers): its forward pass,
backward pass and recompute, mean over chips."""
from bench import scopes

READS = {"trace": "fmoe.dispatch"}


def read(run):
    return scopes.read_stage(run, READS["trace"])
