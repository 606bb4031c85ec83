"""Device: mean milliseconds over the window's in-process ops of the caller's
latency (`bench.op.*`) less the op's device interval (its first owned
program's start to its last one's end): durations only, so the device clock's
offset does not enter."""

from benchmark import op_timeline

SOURCE = "device_trace"


def read(run):
    return op_timeline.metric(run, "op_around_device_ms")
