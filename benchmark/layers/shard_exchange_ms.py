"""Interconnect: device milliseconds an op during which table rows were in
flight between chips, on the first chip: for every sharded Add and Get
program of the window, the union of the intervals of its
`collective-permute`s (an Add's segments on their way to their owners, a
Get's ids out and rows back), over the programs. Adds and Gets are told
apart by the program each transfer lies in and printed on a line of their
own (`{"shard_exchange": ...}`); the metric is the mean over both. A Get's
rows cannot leave before their owners have gathered them, so its time
holds the shards' gathers."""

import json

from benchmark import shard_trace

SOURCE = "device_trace"


def read(run):
    found = shard_trace.exchange(run)
    programs = sum(n for n, _ in (found or {}).values())
    if not programs:
        return None
    print(json.dumps({"shard_exchange": {
        kind + "_ms": 1e3 * seconds / n
        for kind, (n, seconds) in found.items()}}), flush=True)
    return 1e3 * sum(s for _, s in found.values()) / programs
