"""Kernel: the row scatter-add's share of the HBM roofline under a row-state
updater (3 x 512 B a row named: the row read and written, the scaled
gradient read); `row_scatter_roofline`'s reader and bytes under this cell's
name."""

from benchmark import common

SOURCE = "device_trace"


def read(run):
    return common.load_module("layers", "row_scatter_roofline").read(run)
