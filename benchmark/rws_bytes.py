"""Bytes the state step of a row-wise optimizer has to move, from the rows
an Add names alone. Beside kernel_bytes.py (whose share of the peak is used
as it is), so that no later PR can change the yardstick."""

from benchmark import kernel_bytes

share_of_peak = kernel_bytes.share_of_peak


def state_step_bytes(rows, cols, itemsize=4, state_itemsize=4):
    """The part of a row-wise AdaGrad Add in front of the row scatter-add,
    for ``rows`` rows named: each gradient row is read once (for its mean
    square; scaling it is the scatter-add's delta and is not counted
    again), and each row's one value of state is read and written. The
    scaled gradient that is written for the kernel, the sort and the copies
    a compiler puts in are moved and are not useful."""
    return rows * (cols * itemsize + 2 * state_itemsize)
