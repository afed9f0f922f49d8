"""Device self time per step of the combine (``fmoe.combine``: the gather back
to token order and the weighted sum over the k slots): its forward pass,
backward pass and recompute, mean over chips."""
from bench import scopes

READS = {"trace": "fmoe.combine"}


def read(run):
    return scopes.read_stage(run, READS["trace"])
