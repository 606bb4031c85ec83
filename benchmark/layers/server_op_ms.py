"""Dispatcher: mean host milliseconds the server spent processing one Add or
Get message over the window (the program's SERVER_PROCESS_ADD_MSG and
SERVER_PROCESS_GET_MSG monitors)."""

SOURCE = "program_span"
MONITORS = ("SERVER_PROCESS_ADD_MSG", "SERVER_PROCESS_GET_MSG")


def read(run):
    seen = [run.monitors[m] for m in MONITORS if m in run.monitors]
    count = sum(m["count"] for m in seen)
    if not count:
        return None
    return sum(m["elapse_ms"] for m in seen) / count
