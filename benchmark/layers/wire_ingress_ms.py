"""Wire: median milliseconds of a served request from its frame's arrival in
the serving process (`net_recv`, after the payload is read, checked and
copied) to the dispatcher's queue (`dispatch_enqueue`): the mailbox wait and
the serve thread's decode."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.median(q.ingress for q in trace.requests())
