"""Kernel: the sharded row scatter-add's share of the HBM roofline of all the
cell's chips. Bytes the window's acknowledged Adds had to move (every row an
Add names read and written, its delta read, at the table's own width, by
kernel_bytes) over the device time of the `shard_scatter` events on the chip
where a launch takes longest, times the chips, over one chip's published
bytes per second: the chips work side by side and the Add ends with the
slowest, so an uneven split of the rows reads as loss. Over 100% fails the
run; so do fewer launches on that chip than the window has Adds."""

from benchmark import kernel_bytes, shard_trace

SOURCE = "device_trace"


def read(run):
    chips = shard_trace.of(run)
    rows = run.result.get("add_rows")
    if chips is None or not rows:
        return None
    slowest = shard_trace.slowest(
        shard_trace.launches(chips, shard_trace.SCATTER.search))
    if slowest is None:
        return None
    events, seconds, _ = slowest
    if events < run.result["adds"]:
        raise ValueError(
            f"the trace holds {events} scatter launches on a chip for "
            f"{run.result['adds']} Adds: part of the work is not in the time")
    moved = kernel_bytes.row_scatter_bytes(rows, run.result["row_cols"])
    return kernel_bytes.share_of_peak(moved, seconds * len(chips),
                                      run.peaks["hbm_bytes_per_s"])
