"""Table op: the fullest shard's slots over the mean shard's, a launch, from
the op trace (`max_shard_n` x `shards` / `n` of the TABLE_ROW_LAUNCH records
of a sharded table), averaged over the window's launches: 1.0 where the
ids spread evenly; an Add ends with its fullest shard."""

from benchmark import common

SOURCE = "program_span"


def read(run):
    found = common.load_module(
        "layers", "shard_exchange_bytes_share").sharded_launches(run)
    if not found:
        return None
    return sum(launch.max_shard_n * launch.shards / launch.n
               for launch, _ in found) / len(found)
