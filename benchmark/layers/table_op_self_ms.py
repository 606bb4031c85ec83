"""Table op: mean host milliseconds of one table Add or Get less its device
dispatch calls and its blocking device-to-host fetch (TABLE_PROCESS_ADD and
TABLE_PROCESS_GET less their TABLE_ROW_LAUNCH and TABLE_HOST_READ children):
range check, duplicate merge, bucket padding, id upload and bookkeeping."""

from benchmark import op_trace

SOURCE = "program_span"
CHILDREN = ("TABLE_ROW_LAUNCH", "TABLE_HOST_READ")


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    ops = trace.spans("TABLE_PROCESS_ADD") + trace.spans("TABLE_PROCESS_GET")
    if not ops:
        return None
    self_ns = sum(
        op.dur_ns - sum(c.dur_ns for c in trace.children(op.id)
                        if c.stage in CHILDREN)
        for op in ops)
    return self_ns / len(ops) / op_trace.NS_PER_MS
