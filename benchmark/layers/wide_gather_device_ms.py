"""Kernel: device milliseconds of one row gather launch on a table wider than
one lane tile, from the trace."""

from benchmark import row_bytes

SOURCE = "device_trace"


def read(run):
    if not run.trace:
        return None
    return row_bytes.device_ms(row_bytes.gather_launches(run.trace))
