"""Bytes the keyed FTRL ops have to move, from the keys an op names alone.
Beside kernel_bytes.py (whose share of the peak is used as it is), so that
no later PR can change the yardstick."""

from benchmark import kernel_bytes

share_of_peak = kernel_bytes.share_of_peak

ADD_BYTES_A_KEY = 20   # read z, n and the gradient; write z and n
GET_BYTES_A_KEY = 12   # read z and n; write the weight


def add_bytes(keys, itemsize=4):
    """A keyed Add of ``keys`` keys: each key's ``z`` and ``n`` read and
    written and its gradient read, single floats. The sort of the keys, the
    tiles a gather or a scatter of single floats really touches and the
    slots aimed at the scratch key are moved and are not useful."""
    return keys * ADD_BYTES_A_KEY * itemsize // 4


def get_bytes(keys, itemsize=4):
    """A keyed Get of ``keys`` keys: each key's ``z`` and ``n`` read and
    its weight written. The fill of the bucket's tail is not useful."""
    return keys * GET_BYTES_A_KEY * itemsize // 4
