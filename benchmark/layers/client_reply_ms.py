"""Client sync: mean milliseconds from the client's receive thread having a reply's
header to the public op's return: the tiles `reply_read` (the frame's read,
check and copy, the mailbox, the pump's wake), `reply_decode`, `wake` and
`ret`.
None without a served op's timeline (`benchmark/remote_timeline.py`): a
program that records no client half, or a window in which too few ops joined."""

from benchmark import remote_timeline

SOURCE = "program_span"


def read(run):
    return remote_timeline.metric(run, "client_reply_ms")
