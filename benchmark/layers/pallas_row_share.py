"""Table op: percent of the window's row Add launches that the Pallas row
kernel served (the others took XLA's scatter), from the `path` the program
writes on every TABLE_ROW_LAUNCH record: the launches inside a
TABLE_PROCESS_ADD. A program whose records name no path gives None."""

from benchmark import op_trace

SOURCE = "program_span"


def read(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    paths = [getattr(launch, "path", "")
             for add in trace.spans("TABLE_PROCESS_ADD")
             for launch in trace.children(add.id)
             if launch.stage == "TABLE_ROW_LAUNCH"]
    if not paths or not all(paths):
        return None
    return 100.0 * paths.count("pallas") / len(paths)
