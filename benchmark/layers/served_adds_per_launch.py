"""Table op: the window's served Adds (the `n` of its SERVER_PROCESS_ADD_MSG
records: 1 for an Add served alone, the requests of a fused apply) over the
TABLE_ROW_LAUNCH records under a TABLE_PROCESS_ADD. 1.0 on a table whose
Adds do not merge (an FTRL step is not linear: one launch a request, by
construction), above 1 where the dispatcher fuses queued Adds; the number
that batching of keyed Adds that share no key would move. None on a program
that keeps no op ring or launched no row Add in the window."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    adds = {r.id for r in trace.spans("TABLE_PROCESS_ADD")}
    launches = sum(1 for r in trace.spans("TABLE_ROW_LAUNCH")
                   if r.parent in adds)
    served = sum(r.n for r in trace.spans("SERVER_PROCESS_ADD_MSG"))
    return served / launches if launches and served else None
