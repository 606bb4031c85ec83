"""Wire: median milliseconds a served request spent in the serving process,
from its frame's arrival (`net_recv`) to `reply_sent`. A worker's median op
time less this is the client's codec, the sockets and the receive thread's
read, check and copy of the frame (the NET_FRAME_* spans of the stage table),
all before the arrival stamp."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    return op_trace.median(q.residence for q in trace.requests())
