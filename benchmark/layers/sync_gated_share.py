"""Dispatcher: percent of the window's served worker requests that the round
gate had deferred first (the program's always-on counters SYNC_DEFERRED_ADD
and SYNC_DEFERRED_GET over SYNC_SERVED_ADD and SYNC_SERVED_GET, their deltas
over the window as the driver took them). 0 = nothing waited at a gate: the
cell is not gated. None on a program without the counters."""

SOURCE = "program_counter"


def read(run):
    deltas = run.result.get("sync_counters") or {}
    served = deltas.get("SYNC_SERVED_ADD", 0) + deltas.get("SYNC_SERVED_GET", 0)
    if not served:
        return None
    deferred = (deltas.get("SYNC_DEFERRED_ADD", 0)
                + deltas.get("SYNC_DEFERRED_GET", 0))
    return 100.0 * deferred / served
