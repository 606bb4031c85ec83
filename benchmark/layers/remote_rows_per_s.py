"""Dispatcher: rows per second of acknowledged Gets and Adds, all worker
processes together, over the traced window. With a standing queue this is
the capacity of the serving process. It spread 5.3-6.5% between the
quartiles over untraced windows of 20 s, too far for the largest bound a
metric may carry, so it is recorded here."""

SOURCE = "host_clock"


def read(run):
    rows, elapsed = run.result.get("rows"), run.result.get("elapsed_s")
    return rows / elapsed if rows and elapsed else None
