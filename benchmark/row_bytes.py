"""Bytes the row programs of a table wider than one lane tile have to move,
from the rows and columns the ops name alone, and how to find those programs'
events in a trace. Beside kernel_bytes.py (whose scatter count and whose
share of the peak are used as they are), so that no later PR can change the
yardstick.

Counted at the table's own columns (300, not the 384 lanes that hold them):
the lanes that pad a row, and the sentinel slots that fill an id bucket, are
moved and are not useful."""

import re

from benchmark import common, kernel_bytes

# the Pallas scatter-add's event and the shapes in its name, as the `emb128`
# cells' reader finds them: custom-call(s32[<id slots>] %ids,
# f32[<delta rows>,<delta columns>] ...
_narrow = common.load_module("layers", "row_scatter_roofline")
SCATTER_OPERATION, SCATTER_SHAPES = _narrow.OPERATION, _narrow.SHAPES
# XLA's gather as `jit__row_gather` holds it: a fusion whose first operand is
# the program's parameter `data` (the table) and whose second is the ids:
# %fusion = f32[<slots>,<lanes>] fusion(f32[<rows>,<lanes>] %data.1, s32[<slots>] ...
GATHER_EVENT = re.compile(
    r"= f32\[(\d+),(\d+)\]\S* fusion\(f32\[\d+,\d+\]\S* %data[\w.]*, "
    r"s32\[\d+\]")

row_scatter_bytes = kernel_bytes.row_scatter_bytes
share_of_peak = kernel_bytes.share_of_peak


def row_gather_bytes(rows, cols, itemsize=4):
    """Gather of ``rows`` rows of ``cols`` columns out of a table in HBM:
    each row is read once and written once into the result."""
    return 2 * rows * cols * itemsize


def scatter_launches(trace):
    """[(delta rows, events, seconds)] of the scatter-add in the trace; a
    scatter event whose shapes cannot be read fails the run."""
    found = []
    for raw, events, seconds in trace.ops_matching(SCATTER_OPERATION):
        m = SCATTER_SHAPES.search(raw)
        if not m:
            raise ValueError("a scatter-add event whose shapes cannot be "
                             f"read: {raw[:200]}")
        found.append((int(m.group(2)), events, seconds))
    return found


def gather_launches(trace):
    """[(slots, events, seconds)] of the row gather in the trace."""
    found = []
    for raw, (events, seconds) in trace.raw_ops.items():
        m = GATHER_EVENT.search(raw)
        if m:
            found.append((int(m.group(1)), events, seconds))
    return found


def roofline(launches, rows, moved, peak_bytes_per_s, what):
    """Percent of the HBM roofline: ``moved`` bytes over the device time of
    ``launches`` ([(slots, events, seconds)]). Fewer slots than the ops
    name rows means part of the work is not in the time, and fails the run;
    so does a share over 100%."""
    slots = sum(n * events for n, events, _ in launches)
    if slots < rows:
        raise ValueError(
            f"the trace holds {slots} {what} slots for {rows} rows that "
            f"acknowledged ops name: part of the work is not in the time")
    return share_of_peak(moved, sum(s for *_, s in launches),
                         peak_bytes_per_s)


def device_ms(launches):
    events = sum(n for _, n, _ in launches)
    return 1e3 * sum(s for *_, s in launches) / events if events else None
