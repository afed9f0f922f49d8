"""Share of the device's busy self time in ops that no stage of the train
step names: ops whose HLO metadata holds none of the program's scopes, and
ops missing from the step's HLO table.  A refactor that drops a scope
shows here."""
from bench import scopes

READS = {"trace": "unscoped"}


def read(run):
    ms = scopes.stage_ms(run)
    if ms is None:
        return None
    busy = sum(ms.values())
    rest = ms[scopes.UNSCOPED] + ms[scopes.MISSING]
    return 100.0 * rest / busy if busy else None
