"""Client sync: mean milliseconds of a served op's `submit` tile, from the proxy's
public call to `RemoteClient._send` back from the transport's send (the
client's CLIENT_SUBMIT record, carried into the serving process's op trace):
argument work, `wire.encode`, the frame and the send, on the worker's own
thread.
None without a served op's timeline (`benchmark/remote_timeline.py`): a
program that records no client half, or a window in which too few ops joined."""

from benchmark import remote_timeline

SOURCE = "program_span"


def read(run):
    return remote_timeline.metric(run, "client_submit_ms")
