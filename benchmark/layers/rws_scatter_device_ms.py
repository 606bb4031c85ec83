"""Kernel: device milliseconds of one launch of the row scatter-add under a
row-state updater (the kernel takes the scaled gradient);
`row_scatter_device_ms`' reader under this cell's name."""

from benchmark import common

SOURCE = "device_trace"


def read(run):
    return common.load_module("layers", "row_scatter_device_ms").read(run)
