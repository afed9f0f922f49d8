"""Device self time per step of the optimizer (``fmoe.optimizer``: AdamW with
its global-norm clip): its forward pass, backward pass and recompute, mean
over chips."""
from bench import scopes

READS = {"trace": "fmoe.optimizer"}


def read(run):
    return scopes.read_stage(run, READS["trace"])
