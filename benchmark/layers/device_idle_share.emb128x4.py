"""Device: percent of the traced window in which no operation ran on a chip,
the mean over the cell's four chips: `trace_reduce.reduce(chips=4)` gives
`busy_s` as the mean of the chips' busy seconds (`device_idle_share.rows`
reads the first chip alone)."""

SOURCE = "device_trace"


def read(run):
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
