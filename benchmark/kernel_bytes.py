"""Bytes a row kernel has to move, from its shapes alone. Kept with the
benchmark so that no later PR can change the yardstick."""


def row_scatter_bytes(rows, cols, itemsize=4):
    """Scatter-add of ``rows`` rows of ``cols`` columns into a table in HBM:
    each table row is read and written once and its delta row is read once.
    ``rows`` counts the rows the ops name, never the slots of the launch:
    the sentinel rows that fill a power-of-two bucket, and the lanes that
    pad a narrow table, are moved and are not useful."""
    return 3 * rows * cols * itemsize


def share_of_peak(total_bytes, seconds, peak_bytes_per_s):
    """Percent of the peak; over 100 the bytes are counted too high or the
    time leaves out part of the work, and the run fails."""
    share = 100.0 * total_bytes / seconds / peak_bytes_per_s
    if share > 100.0:
        raise ValueError(
            f"{total_bytes} bytes in {seconds} s is {share:.1f}% of the "
            f"peak {peak_bytes_per_s} B/s: more than the chip can move")
    return share
