"""Updater jit: backend compiles JAX reported inside the window, expected 0;
`window_compiles.rows`' count, for the cell whose Adds are optimizer
steps."""

from benchmark import common

SOURCE = "program_counter"


def read(run):
    return common.load_module("layers", "window_compiles.rows").read(run)
