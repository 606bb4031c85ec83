"""Kernel: the row gather's share of the HBM roofline on a table wider than
one lane tile. Bytes the window's acknowledged Gets had to move (every row a
Get names read and written into the result, at the table's own columns:
2 x rows x 300 x 4 B, by row_bytes) over the device time of the gather's
events (`jit__row_gather`'s fusion over the table) in the trace, over the
device's published bytes per second. Padding lanes and sentinel slots are
moved and not counted. Over 100% fails the run; so do fewer slots than
rows."""

from benchmark import row_bytes

SOURCE = "device_trace"


def read(run):
    found = row_bytes.gather_launches(run.trace) if run.trace else []
    rows = run.result.get("get_rows")
    if not found or not rows:
        return None
    moved = row_bytes.row_gather_bytes(rows, run.result["row_cols"])
    return row_bytes.roofline(found, rows, moved,
                              run.peaks["hbm_bytes_per_s"], "gather")
