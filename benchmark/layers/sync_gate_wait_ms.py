"""Dispatcher: mean milliseconds a request that the round gate deferred waited
behind it, from the deferral to its release (the program's SYNC_GATE_WAIT
records: one a deferred request, `op` the request's own id, `n` the round it
waited for). Under BSP this wait, not the dispatcher's queue, is most of a
Get's time: a round's Gets wait for the round's last Add. None on a program
that writes no such record (the parent of the PR that brought it, or a server
that gates nothing)."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.mean_ms(trace.spans("SYNC_GATE_WAIT"))
