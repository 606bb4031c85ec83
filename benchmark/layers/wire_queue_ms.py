"""Wire: the caller-side median op time less the server's mean processing
time, so wire, codec and queue wait together (the split is the tracing
issue's)."""

import statistics

from benchmark import common

SOURCE = "host_clock"


def read(run):
    times = run.result.get("op_ms")
    server = common.load_module("layers", "server_op_ms").read(run)
    if not times or server is None:
        return None
    return statistics.median(times["add"] + times["get"]) - server
