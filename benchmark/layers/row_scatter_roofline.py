"""Kernel: the row scatter-add's share of the HBM roofline. Bytes the
acknowledged Adds of the window had to move (every row an Add names read and
written, its delta read, at the table's own width, by kernel_bytes) over the
device time of the scatter operation's events in the trace, over the
device's published bytes per second. Bound by bandwidth: the kernel adds one
float per four bytes it moves. The slots of a launch beyond the rows named
(the sentinel pads of a power-of-two bucket) are not counted. Over 100%
fails the run; so does a scatter event whose shapes cannot be read, or
launches with fewer slots than the Adds name rows."""

import re

from benchmark import kernel_bytes

SOURCE = "device_trace"
# the name the trace has for the Pallas scatter-add today; a stable name is
# the tracing issue's first item
OPERATION = r"scatter_add"
# custom-call(s32[<slots>] %ids, f32[<slots>,<lanes>] %deltas, ...
SHAPES = re.compile(r"custom-call\(s32\[(\d+)\][^,]*, f32\[(\d+),(\d+)\]")


def launches(run):
    """[(slots, lanes, events, seconds)] of the scatter-add in the trace."""
    found = []
    for raw, events, seconds in run.trace.ops_matching(OPERATION):
        m = SHAPES.search(raw)
        if not m:
            raise ValueError(
                f"a scatter-add event whose shapes cannot be read: {raw[:200]}")
        found.append((int(m.group(1)), int(m.group(3)), events, seconds))
    return found


def read(run):
    found = launches(run) if run.trace else []
    rows = run.result.get("add_rows")
    if not found or not rows:
        return None
    slots = sum(events * n for n, _, events, _ in found)
    if slots < rows:
        raise ValueError(
            f"the trace holds {slots} scatter slots for {rows} rows that "
            f"acknowledged Adds name: part of the work is not in the time")
    moved = kernel_bytes.row_scatter_bytes(rows, run.result["row_cols"])
    return kernel_bytes.share_of_peak(
        moved, sum(s for *_, s in found), run.peaks["hbm_bytes_per_s"])
