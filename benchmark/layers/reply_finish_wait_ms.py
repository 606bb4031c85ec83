"""Wire: mean milliseconds a served Get's reply waited for the finishing
thread, from the dispatcher's hand-over to the start of its REPLY_FINISH (the
program's REPLY_FINISH_WAIT records). Past `reply_finish_ms` the one
finishing thread, not the dispatcher, is the serving process's capacity.
None on a program that hands nothing over."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.mean_ms(trace.spans("REPLY_FINISH_WAIT"))
