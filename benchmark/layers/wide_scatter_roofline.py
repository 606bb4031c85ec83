"""Kernel: the row scatter-add's share of the HBM roofline on a table wider
than one lane tile. Bytes the window's acknowledged Adds had to move (every
row an Add names read and written, its delta read, at the table's own
columns: 3 x rows x 300 x 4 B, by row_bytes) over the device time of the
scatter operation's events in the trace, over the device's published bytes
per second. Bound by bandwidth. Padding lanes and sentinel slots are moved
and not counted. Over 100% fails the run; so do fewer slots than rows."""

from benchmark import row_bytes

SOURCE = "device_trace"


def read(run):
    found = row_bytes.scatter_launches(run.trace) if run.trace else []
    rows = run.result.get("add_rows")
    if not found or not rows:
        return None
    moved = row_bytes.row_scatter_bytes(rows, run.result["row_cols"])
    return row_bytes.roofline(found, rows, moved,
                              run.peaks["hbm_bytes_per_s"], "scatter")
