"""Dispatcher: percent, of the window's acknowledged Adds as the worker
processes counted them, those whose reply carried the table's Add ordinal and
whose ordinal the reference's rule (a) accepts (inside 1..N and no other
Add's). 100 where every Add was stamped and applied exactly once: the
reading that says the order check had something to check. None where the
driver counted none (a program whose replies carry no ordinal reads 0, not
None: its Adds were acknowledged)."""

SOURCE = "program_counter"


def read(run):
    acked = run.result.get("adds_acked")
    if not acked:
        return None
    return 100.0 * run.result.get("adds_ordered", 0) / acked
