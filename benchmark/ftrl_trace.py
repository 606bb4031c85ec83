"""A traced run of the keyed FTRL table: how its two device programs
(`multiverso_tpu/tables/ftrl_table.py`) appear in a trace, and the device
time of each.

Compiled for a described v5e, the programs are modules of their own:

    jit__ftrl_keyed_add(...) on line `XLA Modules`: one event an Add
        (the sort of the keys with their gradients, the gathers of z and n
        as rows of 128, the step, XLA's two scatters of single floats)
    jit__ftrl_keyed_get(...) on line `XLA Modules`: one event a Get
        (the two gathers, the closed form, the fill of the bucket's tail)

A program without those modules (no such table, or the parent of the PR
that brought it) has no such event, and the readers return None."""

import re

from benchmark import rws_trace, shard_trace, trace_reduce

MODULE = {"add": re.compile(r"ftrl_keyed_add"),
          "get": re.compile(r"ftrl_keyed_get")}


def programs(run, op):
    """(programs of ``op`` -- ``add`` or ``get`` -- that lie wholly in the
    window, their device seconds on the first chip); None where the run was
    not traced or ran no such program."""
    if not run.trace:
        return None
    cache = run.__dict__.setdefault("_ftrl_programs", {})
    if op in cache:
        return cache[op]
    lo = hi = None
    found = []
    for plane in rws_trace._raw(run)["planes"]:
        device = trace_reduce.DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            if device and int(device.group(1)) == 0:
                if line["name"] == shard_trace.MODULES_LINE:
                    found += [(s, s + d) for name, s, d in line["events"]
                              if MODULE[op].search(name)]
            elif not device:
                for name, s, d in line["events"]:
                    if name == trace_reduce.WINDOW_SPAN:
                        lo, hi = s, s + d
    if lo is not None:
        found = [m for m in found if m[0] >= lo and m[1] <= hi]
    cache[op] = (len(found), sum(b - a for a, b in found) * 1e-9) \
        if found else None
    return cache[op]


def device_ms(run, op):
    """Mean device milliseconds of one program of ``op``."""
    found = programs(run, op)
    return 1e3 * found[1] / found[0] if found else None


def keys_of(run, op, count):
    """The keys ``count`` programs of ``op`` name, at the window's mean."""
    ops = run.result.get(op + "s")
    named = run.result.get(op + "_rows")
    return count * named // ops if ops and named else None
