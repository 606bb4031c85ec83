"""Dispatcher: mean milliseconds of one pass of the gate's drain that released
at least one deferred request (the program's SYNC_RELEASE sections; `n` is the
requests released, each served inside the section one after the other on the
dispatcher thread). Prints the mean `n` on a line of its own. None on a
program that writes no such record."""

import json

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    passes = trace.spans("SYNC_RELEASE")
    if not passes:
        return None
    print(json.dumps({"sync_release": {
        "passes": len(passes),
        "mean_released": sum(r.n for r in passes) / len(passes)}}),
        flush=True)
    return op_trace.mean_ms(passes)
