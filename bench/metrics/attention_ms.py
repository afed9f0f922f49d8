"""Device self time per step of the token mixer (``fmoe.attention``:
projections, scores, softmax, output projection): its forward pass, backward
pass and recompute, mean over chips."""
from bench import scopes

READS = {"trace": "fmoe.attention"}


def read(run):
    return scopes.read_stage(run, READS["trace"])
