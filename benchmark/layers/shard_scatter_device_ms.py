"""Kernel: device milliseconds of one launch of the row scatter-add on the
chip where it takes longest, on a table sharded over chips (every chip runs
the kernel on its block for every Add; the Add ends when the slowest has),
from the events named `shard_scatter` on every chip's line of the trace."""

from benchmark import shard_trace

SOURCE = "device_trace"


def read(run):
    chips = shard_trace.of(run)
    if chips is None:
        return None
    slowest = shard_trace.slowest(
        shard_trace.launches(chips, shard_trace.SCATTER.search))
    return slowest[2] if slowest else None
