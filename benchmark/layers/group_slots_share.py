"""Table op: percent, the id slots the window's group Adds launched (their
TABLE_ROW_LAUNCH records' `n`: whole row groups of the rows named) over the
rows of all tables they name (the `n` of the TABLE_ROW_PREP beside the launch,
under the same TABLE_PROCESS_ADD). 100.1 where the rows of every table share
one launch's row groups; a padding a segment, or a launch that walked its
delta's bucket, would read higher. A program that sends no group op (no
WORKER_GROUP_IDS in the window) gives None."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None or not trace.spans("WORKER_GROUP_IDS"):
        return None
    slots = named = 0
    for add in trace.spans("TABLE_PROCESS_ADD"):
        inside = {r.stage: r.n for r in trace.children(add.id)}
        if "TABLE_ROW_LAUNCH" in inside and inside.get("TABLE_ROW_PREP"):
            slots += inside["TABLE_ROW_LAUNCH"]
            named += inside["TABLE_ROW_PREP"]
    return 100.0 * slots / named if named else None
