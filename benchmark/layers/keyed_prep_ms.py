"""Table op: mean milliseconds of the TABLE_ROW_PREP of the window's keyed
FTRL ops (the PREP beside a TABLE_ROW_LAUNCH whose `updater` is `ftrl`, under
one TABLE_PROCESS_ADD or _GET): what the dispatcher does to keys that arrive
as numpy before it can launch, which is the padding to the bucket and the
start of their upload (the range check and the gradient's upload lie beside
it in TABLE_PROCESS_*, not in it). None on a program that keeps no op ring
or served no such op in the window."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    preps = []
    for stage in ("TABLE_PROCESS_ADD", "TABLE_PROCESS_GET"):
        for op in trace.spans(stage):
            inside = {r.stage: r for r in trace.children(op.id)}
            launch, prep = (inside.get("TABLE_ROW_LAUNCH"),
                            inside.get("TABLE_ROW_PREP"))
            if launch is not None and prep is not None \
                    and getattr(launch, "updater", None) == "ftrl":
                preps.append(prep)
    return op_trace.mean_ms(preps)
