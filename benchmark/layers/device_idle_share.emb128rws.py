"""Device: percent of the traced window in which no operation ran on the
chip; `device_idle_share.rows`' reader, for the cell whose Adds are
optimizer steps."""

from benchmark import common

SOURCE = "device_trace"


def read(run):
    return common.load_module("layers", "device_idle_share.rows").read(run)
