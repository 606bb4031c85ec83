"""Wire: median milliseconds from a client's send having returned (`sent`) to the
serving process's arrival stamp (`net_recv`): the tiles `wire_out` and
`frame_in`, the loopback and the receive thread's read, check and copy of the
frame (NET_FRAME_*), with its waits for the interpreter before and inside
them.
None without a served op's timeline (`benchmark/remote_timeline.py`): a
program that records no client half, or a window in which too few ops joined."""

from benchmark import remote_timeline

SOURCE = "program_span"


def read(run):
    return remote_timeline.metric(run, "wire_out_ms")
