"""Updater: device milliseconds of a stateful Add outside the row kernel,
under a row-state updater: the reduce of the gradient's squares, the read
and write of the named rows' state, the scaling of the gradient
(`benchmark/rws_trace.py`: every operation of the `jit__row_state_add`
programs in the window but the `scatter_add` kernel, a mean an Add)."""

from benchmark import rws_trace

SOURCE = "device_trace"


def read(run):
    found = rws_trace.state_step(run)
    return 1e3 * found[1] / found[0] if found else None
