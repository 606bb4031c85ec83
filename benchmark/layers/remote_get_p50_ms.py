"""Wire: median milliseconds of one Get from a worker process's call to its
reply. It swings too far from run to run to carry a bound in the cell with
remote workers (4-6% between the quartiles), so it is recorded here."""

import statistics

SOURCE = "host_clock"


def read(run):
    samples = run.result.get("op_ms", {}).get("get")
    return statistics.median(samples) if samples else None
