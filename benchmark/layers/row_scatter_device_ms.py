"""Kernel: device milliseconds of one row scatter-add launch, from the
trace (where the dispatcher fuses Adds, launches differ in size)."""

from benchmark import common

SOURCE = "device_trace"


def read(run):
    if not run.trace:
        return None
    found = common.load_module("layers", "row_scatter_roofline").launches(run)
    events = sum(n for _, _, n, _ in found)
    return 1e3 * sum(s for *_, s in found) / events if events else None
