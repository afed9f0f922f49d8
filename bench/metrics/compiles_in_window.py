"""Compilations JAX reports (backend compile events, persistent-cache hits
included) between the window's first step and its last."""
READS = {"counter": "/jax/core/compile/backend_compile_duration"}


def read(run):
    return float(run.counters.get(READS["counter"], 0))
