"""Updater: the keyed FTRL Get's share of the HBM roofline. Bytes the Gets
of the traced programs had to move (each named key's z and n read, its
weight written, 12 B: `benchmark/ftrl_bytes.py`) over the device time
`ftrl_get_device_ms` is the mean of, over the device's published bytes per
second. Over 100% fails the run."""

from benchmark import ftrl_bytes, ftrl_trace

SOURCE = "device_trace"


def read(run):
    found = ftrl_trace.programs(run, "get")
    keys = found and ftrl_trace.keys_of(run, "get", found[0])
    if not keys:
        return None
    return ftrl_bytes.share_of_peak(ftrl_bytes.get_bytes(keys), found[1],
                                    run.peaks["hbm_bytes_per_s"])
