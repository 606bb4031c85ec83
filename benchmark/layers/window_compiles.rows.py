"""Updater jit: backend compiles JAX reported inside the window; expected 0,
since set-up warms every shape the cell's traffic uses."""

SOURCE = "program_counter"


def read(run):
    return len(run.compiles_in_window)
