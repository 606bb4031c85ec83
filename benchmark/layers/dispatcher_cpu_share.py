"""Dispatcher: the dispatcher thread's own CPU seconds over its wall seconds
while it was not parked (the DISPATCHER_DRAIN spans: one drained batch each),
in percent. Near 100 the thread computes all the time it is busy (first
touches of fresh buffers burn CPU); well under 100 it waits while "busy": for
the interpreter lock, a core, or the device."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    drains = trace.spans("DISPATCHER_DRAIN")
    wall = sum(r.dur_ns for r in drains)
    return 100.0 * sum(r.cpu_ns for r in drains) / wall if wall else None
