"""Device self time per step of the expert exchange (``fmoe.exchange``: the
counts and payload all-to-alls of ``core/comm.py`` and ``core/pipeline.py``,
both ways), apart from the tensor-parallel collectives that
``collective_ms`` counts with it: its forward pass, backward pass and
recompute, mean over chips."""
from bench import scopes

READS = {"trace": "fmoe.exchange"}


def read(run):
    return scopes.read_stage(run, READS["trace"])
