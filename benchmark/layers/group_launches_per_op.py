"""Table op: the window's TABLE_ROW_LAUNCH records over its group ops (one
WORKER_GROUP_IDS each). 1.0: a group op is one launch; the number of tables
would say the group fell back to a loop over them. A program that sends no
group op gives None."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    ops = len(trace.spans("WORKER_GROUP_IDS"))
    if not ops:
        return None
    return len(trace.spans("TABLE_ROW_LAUNCH")) / ops
