"""Client sync: percent of the window's in-process device-path ops on a table
on one device that launched on the ids their proxy had kept from its last op,
from the op trace: of the WORKER_ROW_IDS records (one an op, on the caller's
thread at submit), the share whose `bytes` is 0: nothing went up, the op
named the rows of the op before it (a trainer's push after its pull) and took
that op's id array as it lay on the device. 50 where every second op names
its predecessor's rows; 0 on a program that keeps nothing (every record
carries the bytes it sent up). A window without the span (a mesh, served
ops, a program from before the span) gives None."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    sent = [record.bytes for record in trace.spans("WORKER_ROW_IDS")]
    if not sent:
        return None
    return 100.0 * sum(1 for nbytes in sent if not nbytes) / len(sent)
