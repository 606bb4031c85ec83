"""Table op: percent, the key slots the window's keyed FTRL ops launched
(their TABLE_ROW_LAUNCH records' `n`, the records whose `updater` is `ftrl`)
over the keys they name (the `n` of the TABLE_ROW_PREP beside the launch,
under the same TABLE_PROCESS_ADD or _GET). 100.x where the programs follow
the keys named (rounded up to a thirty-second of the bucket), 114 where they
walk a bucket of 131,072 for 115,000 keys. None on a program that launches
no such op."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    slots = named = 0
    for stage in ("TABLE_PROCESS_ADD", "TABLE_PROCESS_GET"):
        for op in trace.spans(stage):
            inside = {r.stage: r for r in trace.children(op.id)}
            launch, prep = (inside.get("TABLE_ROW_LAUNCH"),
                            inside.get("TABLE_ROW_PREP"))
            if launch is None or prep is None or not prep.n \
                    or getattr(launch, "updater", None) != "ftrl":
                continue
            slots += launch.n
            named += prep.n
    return 100.0 * slots / named if named else None
