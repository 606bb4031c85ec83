"""Interconnect: percent of the bytes of the rows the window's ops name that
crossed chips, from the op trace: `exchange_bytes` of the TABLE_ROW_LAUNCH
records of a sharded table (the segments sent between chips, by their
shapes) over the rows named (`n` of the TABLE_ROW_PREP beside each launch)
at the table's own width. About 75 where three quarters of an op's rows
live on other chips than the one that holds the delta and takes the Get;
300 would be a broadcast. A program whose launch records carry no `shards`
gives None."""

from benchmark import op_trace

SOURCE = "program_span"


def sharded_launches(run):
    """[(launch record, rows its op named)] of the window's row launches on
    a table sharded over chips."""
    trace = op_trace.of(run)
    if trace is None:
        return []
    out = []
    for launch in trace.spans("TABLE_ROW_LAUNCH"):
        if not getattr(launch, "shards", 0):
            continue
        named = [r.n for r in trace.children(launch.parent)
                 if r.stage == "TABLE_ROW_PREP"]
        if named:
            out.append((launch, named[0]))
    return out


def read(run):
    found = sharded_launches(run)
    if not found:
        return None
    row_bytes = run.result["row_cols"] * 4
    return 100.0 * sum(launch.exchange_bytes for launch, _ in found) / (
        sum(named for _, named in found) * row_bytes)
