"""Device self time per step of the router (``fmoe.router``: the gate, the
balance and z losses): its forward pass, backward pass and recompute, mean
over chips."""
from bench import scopes

READS = {"trace": "fmoe.router"}


def read(run):
    return scopes.read_stage(run, READS["trace"])
