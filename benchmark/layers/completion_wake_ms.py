"""Dispatcher: median milliseconds between the dispatcher's `done` on an
in-process op's completion and the waiting worker thread running again (the
`n` of the WORKER_WAIT records, nanoseconds)."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.median(r.n / op_trace.NS_PER_MS
                           for r in trace.spans("WORKER_WAIT") if r.n)
