"""Kernel: device milliseconds of one launch of a Get's gather over a shard's
block of the table, on the chip where it takes longest (XLA's gather: on
each chip the custom fusion that picks from the most rows), from every
chip's line of the trace."""

from benchmark import shard_trace

SOURCE = "device_trace"


def read(run):
    chips = shard_trace.of(run)
    if chips is None:
        return None
    slowest = shard_trace.slowest(shard_trace.table_gathers(chips))
    return slowest[2] if slowest else None
