"""Wire: percent of the window's served Gets (a TABLE_PROCESS_GET whose op has
a `reply_sent`) whose reply the dispatcher handed to the finishing thread (the
op has a REPLY_FINISH_WAIT record): 100 where every keyed Get is fetched and
sent behind the dispatcher, 0 on a program whose dispatcher finishes its own
replies, None where no Get is served over the wire."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    served = ({r.op for r in trace.spans("TABLE_PROCESS_GET")}
              & {r.op for r in trace.spans("reply_sent")})
    if not served:
        return None
    behind = served & {r.op for r in trace.spans("REPLY_FINISH_WAIT")}
    return 100.0 * len(behind) / len(served)
