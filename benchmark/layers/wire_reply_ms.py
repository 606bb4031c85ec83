"""Wire: mean milliseconds of one reply on the dispatcher thread (WIRE_REPLY:
encode, dedup store and the send into the socket)."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.mean_ms(trace.spans("WIRE_REPLY"))
