"""Device: percent of the traced window in which no operation ran on the
device; `device_idle_share.rows`' arithmetic, for the cell of the wide
tables."""

from benchmark import common

SOURCE = "device_trace"


def read(run):
    return common.load_module("layers", "device_idle_share.rows").read(run)
