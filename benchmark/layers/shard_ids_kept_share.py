"""Table op: percent of the window's routed row ops (a table sharded over
chips: the ops whose TABLE_ROW_LAUNCH says `shards`) that launched on the id
array the row plan had kept from the routed op before them, from the op
trace: of the TABLE_ROW_PREP records beside those launches, the share whose
`bytes` is 0: nothing was filled, counted by shard or sent up, the op named
the rows of the op before it (a trainer's Get after its Add) and took that
op's ids as they lay on the first chip. 50 where every second op names its
predecessor's rows. A program whose TABLE_ROW_PREP records say no `bytes`
(the parent of the PR that brought the field: every one reads 0) gives None,
and so does a window without a routed op; a window whose every routed op hit
would read None too, and a benchmark's never is: its first op misses."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    sent = [prep.bytes
            for launch in trace.spans("TABLE_ROW_LAUNCH")
            if getattr(launch, "shards", 0)
            for prep in trace.children(launch.parent)
            if prep.stage == "TABLE_ROW_PREP"]
    if not any(sent):
        return None
    return 100.0 * sum(1 for nbytes in sent if not nbytes) / len(sent)
