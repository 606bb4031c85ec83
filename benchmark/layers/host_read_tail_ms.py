"""Table op: mean milliseconds over the window's served Gets from the end of the
gather's device program to the end of the TABLE_HOST_READ that fetched its
rows, at the lower bound of the device clock's correction."""

from benchmark import op_timeline

SOURCE = "device_trace"


def read(run):
    return op_timeline.metric(run, "host_read_tail_ms")
