"""Table op: percent of the rows the window's ops name that the shards'
launches cover, summed over the shards, from the op trace: `n` of the
TABLE_ROW_LAUNCH records of a sharded table (an Add's row groups that issue
descriptors, a Get's gathered segments) over `n` of the TABLE_ROW_PREP beside
each. 100-115 where each shard walks the rows it owns; 400 on four chips
would mean every shard walks the whole op."""

from benchmark import common

SOURCE = "program_span"


def read(run):
    found = common.load_module(
        "layers", "shard_exchange_bytes_share").sharded_launches(run)
    if not found:
        return None
    return 100.0 * sum(launch.n for launch, _ in found) / sum(
        named for _, named in found)
