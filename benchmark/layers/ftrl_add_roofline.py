"""Updater: the keyed FTRL Add's share of the HBM roofline. Bytes the Adds
of the traced programs had to move (each named key's z and n read and
written, its gradient read, 20 B: `benchmark/ftrl_bytes.py`) over the device
time `ftrl_add_device_ms` is the mean of, over the device's published bytes
per second. Bound by bandwidth in principle; what XLA's gather and scatter of
single floats over a 3.5 GB array reach is the finding, and the number a
later kernel is held to. Over 100% fails the run."""

from benchmark import ftrl_bytes, ftrl_trace

SOURCE = "device_trace"


def read(run):
    found = ftrl_trace.programs(run, "add")
    keys = found and ftrl_trace.keys_of(run, "add", found[0])
    if not keys:
        return None
    return ftrl_bytes.share_of_peak(ftrl_bytes.add_bytes(keys), found[1],
                                    run.peaks["hbm_bytes_per_s"])
