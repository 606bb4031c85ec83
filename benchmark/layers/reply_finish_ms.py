"""Wire: mean milliseconds to finish one served Get's reply (REPLY_FINISH: the
wait for the gathered rows' copy to the host, TABLE_HOST_READ, then WIRE_REPLY
with its encode, dedup store and send), on the serving process's finishing
thread, behind the dispatcher, or on the dispatcher where nothing could be
handed over. None on a program that has no such span."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.mean_ms(trace.spans("REPLY_FINISH"))
