"""Dispatcher: median milliseconds a Get or Add waited for the dispatcher
thread over the window, from `Server.send` to the moment its service began
(the program's SERVER_QUEUE_WAIT records; in-process and served ops alike).
The time behind earlier messages of the same drain counts as waiting."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.median(r.dur_ns / op_trace.NS_PER_MS
                           for r in trace.spans("SERVER_QUEUE_WAIT"))
