"""Device: percent of the traced window in which no operation ran on the
device (device 0 where there are several)."""

SOURCE = "device_trace"


def read(run):
    return 100.0 * run.trace.idle_share if run.trace else None
