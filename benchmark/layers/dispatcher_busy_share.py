"""Dispatcher: percent of the window the dispatcher thread was not parked in
its queue's `pop_all` (100 x (1 - DISPATCHER_PARKED seconds / window)). Near
100 the one thread is the serving process's whole capacity; well under it,
something upstream of the queue sets the pace."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None or not trace.spans("DISPATCHER_PARKED"):
        return None
    parked = sum(r.dur_ns for r in trace.spans("DISPATCHER_PARKED")) * 1e-9
    return 100.0 * (1.0 - parked / trace.window_s)
