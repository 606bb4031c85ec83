"""Device: mean milliseconds over the window's row launches from the start of
TABLE_ROW_LAUNCH to the start of the first device program it owns, at the
lower bound of the device clock's correction (understated by at most
`trace_clock_slack_us`)."""

from benchmark import op_timeline

SOURCE = "device_trace"


def read(run):
    return op_timeline.metric(run, "launch_to_device_ms")
