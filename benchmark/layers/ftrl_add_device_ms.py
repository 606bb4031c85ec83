"""Updater: device milliseconds of one keyed FTRL Add, the whole program
(`benchmark/ftrl_trace.py`: the `jit__ftrl_keyed_add` modules that lie in the
window, on the trace's `XLA Modules` line; a mean an Add). None on a
program without the module."""

from benchmark import ftrl_trace

SOURCE = "device_trace"


def read(run):
    return ftrl_trace.device_ms(run, "add")
