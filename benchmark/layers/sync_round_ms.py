"""Dispatcher: the server's step time under BSP: mean milliseconds between the
ends of successive rounds of the window (the program's SYNC_ROUND records, one
a table and round, ending at the moment every gated worker's Add of the round
is applied; `n` is the round). None with fewer than two rounds in the window,
or on a program that writes no such record."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    ends = sorted(r.start_ns + r.dur_ns for r in trace.spans("SYNC_ROUND"))
    if len(ends) < 2:
        return None
    return (ends[-1] - ends[0]) / (len(ends) - 1) / op_trace.NS_PER_MS
