"""Wire: median milliseconds from the serving process's `reply_sent` to the
client's receive thread having the reply's header (`reply_header`): the tiles
`send` and `wire_back`. The rest of a large reply's transfer overlaps the
server's NET_SEND and lies in `client_reply_ms`.
None without a served op's timeline (`benchmark/remote_timeline.py`): a
program that records no client half, or a window in which too few ops joined."""

from benchmark import remote_timeline

SOURCE = "program_span"


def read(run):
    return remote_timeline.metric(run, "wire_back_ms")
