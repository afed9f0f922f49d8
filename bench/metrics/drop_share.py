"""Share of (token, expert) assignments the capacity dispatch dropped: the
mean over the window's steps of the step's own ``drop_frac``."""
READS = {"aux": "drop_frac"}


def read(run):
    vals = run.aux.get(READS["aux"])
    if vals is None or len(vals) == 0:
        return None
    return 100.0 * float(sum(vals)) / len(vals)
