"""Device self time per step of the output head and the cross-entropy
(``fmoe.head_loss``): its forward pass, backward pass and recompute, mean
over chips."""
from bench import scopes

READS = {"trace": "fmoe.head_loss"}


def read(run):
    return scopes.read_stage(run, READS["trace"])
