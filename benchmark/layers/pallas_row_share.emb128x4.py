"""Table op: percent of the window's row Add launches that the Pallas row
kernel served; `pallas_row_share`'s count, for the cell of the table sharded
over four chips."""

from benchmark import common

SOURCE = "program_span"


def read(run):
    return common.load_module("layers", "pallas_row_share").read(run)
