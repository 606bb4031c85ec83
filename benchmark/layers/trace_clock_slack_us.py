"""Device: microseconds between the two causal bounds of the correction to the
trace's device clock (no program begins before its launch; none ends after a
host call that needed its result returned): how far `launch_to_device_ms`
and the two tails can be wrong."""

from benchmark import op_timeline

SOURCE = "device_trace"


def read(run):
    return op_timeline.metric(run, "trace_clock_slack_us")
