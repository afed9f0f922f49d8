"""Device self time per step of the expert FFNs (``fmoe.experts``:
``EXPERT_FNS`` / ``RAGGED_FNS``): its forward pass, backward pass and
recompute, mean over chips."""
from bench import scopes

READS = {"trace": "fmoe.experts"}


def read(run):
    return scopes.read_stage(run, READS["trace"])
