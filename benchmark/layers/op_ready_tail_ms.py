"""Device: mean milliseconds over the window's in-process ops from the end of
the op's last device program to the caller having its result (`bench.op.*`
ends), at the lower bound of the device clock's correction (overstated by at
most `trace_clock_slack_us`)."""

from benchmark import op_timeline

SOURCE = "device_trace"


def read(run):
    return op_timeline.metric(run, "op_ready_tail_ms")
