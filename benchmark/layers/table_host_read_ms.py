"""Table op: mean milliseconds of the blocking device-to-host fetch inside a
served Get (TABLE_HOST_READ under TABLE_PROCESS_GET): the wait for the gather
and the copy of the rows into a fresh host buffer."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    reads = [c for get in trace.spans("TABLE_PROCESS_GET")
             for c in trace.children(get.id) if c.stage == "TABLE_HOST_READ"]
    return op_trace.mean_ms(reads)
