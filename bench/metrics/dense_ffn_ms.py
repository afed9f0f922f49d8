"""Device self time per step of the FFNs every token runs
(``fmoe.dense_ffn``: the leading dense layers' FFNs and the shared experts):
their forward pass, backward pass and recompute, mean over chips.

``bench/scopes.py`` reduces by the program's eight stage scopes, and these
ops are unscoped there.  This reader builds its own op-to-stage table from
the trace's HloProto with ``fmoe.dense_ffn`` added to the eight, so an op
inside it counts here when it is the innermost of the nine.  A program
without the scope gives no reading."""
from bench import scopes

READS = {"trace": "fmoe.dense_ffn"}
STAGES = scopes.SCOPES + (READS["trace"],)


def _stage(op_name):
    if not op_name:
        return None
    for part in reversed(scopes._WRAPPER.sub("", op_name).split("/")):
        if part in STAGES:
            return part
    return None


def _module_table(buf, proto) -> dict:
    table = {}
    for module in scopes._sub(buf, proto, 1):
        for comp in scopes._sub(buf, module, 3):
            for instr in scopes._sub(buf, comp, 2):
                name = op = None
                for f, v in scopes._fields(buf, *instr):
                    if f == 1:
                        name = scopes._text(buf, v)
                    elif f == 7:
                        ops = scopes._sub(buf, v, 2)
                        op = scopes._text(buf, ops[0]) if ops else None
                if name is not None:
                    table[name] = _stage(op)
    return table


def table_from_xplane(path: str) -> dict:
    """{instruction: stage or None} of the trace's modules, the largest
    module first where two name one instruction."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict = {}
    for t in sorted((_module_table(buf, p) for p in scopes._hlo_protos(buf)),
                    key=len, reverse=True):
        for k, v in t.items():
            out.setdefault(k, v)
    return out


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or not run.steps:
        return None
    path = scopes.newest_trace()
    table = table_from_xplane(path) if path else {}
    if READS["trace"] not in table.values():
        return None
    per = scopes.stage_ns(tr, table, *run.window_ns)
    return (sum(by.get(READS["trace"], 0.0) for by in per.values())
            / len(per) / run.steps / 1e6)
