"""Updater: percent of the window's keyed FTRL Add launches whose rows of
128 the Pallas row kernel wrote back (the others took XLA's two scatters of
single floats); `pallas_row_share`'s count, for the cell of the keyed FTRL
table (100 expected on one chip; 0 on a program whose Adds are XLA's)."""

from benchmark import common

SOURCE = "program_span"


def read(run):
    return common.load_module("layers", "pallas_row_share").read(run)
