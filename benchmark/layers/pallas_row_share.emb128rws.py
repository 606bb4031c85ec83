"""Table op: percent of the window's row Add launches that the Pallas row
kernel served; `pallas_row_share`'s count, for the cell whose Adds are
optimizer steps (100 expected; 0 says the updater left the kernel)."""

from benchmark import common

SOURCE = "program_span"


def read(run):
    return common.load_module("layers", "pallas_row_share").read(run)
