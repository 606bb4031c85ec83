"""Dispatcher: mean milliseconds from the start of the first Add applied in a
round to the end of the last (the duration of the program's SYNC_ROUND
records): how far apart a round's Adds reach the table. Near `sync_round_ms`
the Adds trickle in over the whole step; far under it they arrive together and
the rest of the step is Gets and the wire. None on a program that writes no
such record."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.mean_ms(trace.spans("SYNC_ROUND"))
