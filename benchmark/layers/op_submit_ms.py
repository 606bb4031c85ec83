"""Client sync: mean milliseconds of WORKER_SUBMIT over the window: an in-process
op on the caller's thread, from the public call (`add_device_async`,
`get_device_async`, ...) to `Server.send` having returned."""

from benchmark import op_timeline

SOURCE = "program_span"


def read(run):
    return op_timeline.metric(run, "op_submit_ms")
