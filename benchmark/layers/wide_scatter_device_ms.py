"""Kernel: device milliseconds of one row scatter-add launch on a table wider
than one lane tile, from the trace."""

from benchmark import row_bytes

SOURCE = "device_trace"


def read(run):
    if not run.trace:
        return None
    return row_bytes.device_ms(row_bytes.scatter_launches(run.trace))
