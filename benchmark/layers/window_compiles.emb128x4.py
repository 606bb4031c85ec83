"""Updater jit: backend compiles JAX reported inside the window, expected 0;
`window_compiles.rows`' count, for the cell of the table sharded over four
chips (a pooled op whose fullest shard crossed a step of the capacity rule
would compile here)."""

from benchmark import common

SOURCE = "program_counter"


def read(run):
    return common.load_module("layers", "window_compiles.rows").read(run)
