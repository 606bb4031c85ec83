"""Wire: median milliseconds of one Add from a worker process's call to its
reply. In the cell with remote workers it spread 4.9-6.0% between the
quartiles, too far for the largest bound a metric may carry (0.1 asks for
under 5%), so it is recorded here."""

import statistics

SOURCE = "host_clock"


def read(run):
    samples = run.result.get("op_ms", {}).get("add")
    return statistics.median(samples) if samples else None
