"""Client sync: mean host milliseconds of a group op's WORKER_GROUP_IDS, on
the caller's thread inside the op's WORKER_ROW_IDS: every segment checked
against its own table's end and the bases laid out for the one pass that
fills the launch's id array. A program without the span gives None."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.mean_ms(trace.spans("WORKER_GROUP_IDS"))
