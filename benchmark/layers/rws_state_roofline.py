"""Updater: the state step's share of the HBM roofline. Bytes the stateful
Adds of the traced programs had to move in front of the row kernel (each
named row's gradient read once, its one float32 of state read and written:
`benchmark/rws_bytes.py`) over the device time `rws_state_device_ms` is the
mean of, over the device's published bytes per second. Bound by bandwidth
in principle (two flops a byte); what XLA's gather and scatter of single
floats reach is the finding. Over 100% fails the run."""

from benchmark import rws_bytes, rws_trace

SOURCE = "device_trace"


def read(run):
    found = rws_trace.state_step(run)
    adds = run.result.get("adds")
    if not found or not adds:
        return None
    programs, seconds = found
    # the rows an Add names, for the programs that lie wholly in the window
    rows = programs * run.result["add_rows"] // adds
    return rws_bytes.share_of_peak(
        rws_bytes.state_step_bytes(rows, run.result["row_cols"]), seconds,
        run.peaks["hbm_bytes_per_s"])
