"""A traced run of a table under a row-state updater: how the program of
its stateful Add (`MatrixServer._make_row_state_add`) appears in a trace,
and the device time of what runs in it beside the row kernel.

Compiled for a described v5e (the names are the HLO instructions'):

    jit__row_state_add(...) on line `XLA Modules`: one event an Add
    %_scatter_add_call.N = ... custom-call(s32[<slots>] ..., f32[<rows>,<cols>] ..., f32[<table rows>,<lanes>] ...)
        the Pallas row kernel, the event `row_scatter_roofline`'s reader finds
    every other event on `XLA Ops` inside the module's interval: the state
        step (the reduce of g^2, XLA's gather of the named rows' state, the
        sort and scatter that write it back, the scaling of the gradient,
        copies between them)

A program without that module (one whose Adds are not stateful, or the
parent of the PR that brought it) has no such event, and the readers return
None."""

import os
import re

from benchmark import common, shard_trace, trace_reduce

MODULE = re.compile(r"row_state_add")
MODULES_LINE = shard_trace.MODULES_LINE
KERNEL = re.compile(r"scatter_add")


def _raw(run):
    if not hasattr(run, "_rws_raw"):
        run._rws_raw = trace_reduce.load_xplane(trace_reduce.find_xplane(
            os.path.join(common.BENCH_DIR, ".trace", run.cell["name"])))
    return run._rws_raw


def state_step(run):
    """(stateful Add programs in the window, device seconds of their
    operations other than the row kernel, on the first chip); None where
    the run was not traced or ran no such program. A program counts if it
    lies wholly in the window; time is the union of its operations'
    intervals, so nested or overlapping events are counted once."""
    if not run.trace:
        return None
    if hasattr(run, "_rws_state_step"):
        return run._rws_state_step
    lo = hi = None
    modules, ops = [], []
    for plane in _raw(run)["planes"]:
        device = trace_reduce.DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            if device and int(device.group(1)) == 0:
                if line["name"] == MODULES_LINE:
                    modules += [(s, s + d) for name, s, d in line["events"]
                                if MODULE.search(name)]
                elif line["name"] == trace_reduce.OPS_LINE:
                    ops += [(s, s + d) for name, s, d in line["events"]
                            if not KERNEL.search(
                                trace_reduce.short_name(name))]
            elif not device:
                for name, s, d in line["events"]:
                    if name == trace_reduce.WINDOW_SPAN:
                        lo, hi = s, s + d
    if lo is not None:
        modules = [m for m in modules if m[0] >= lo and m[1] <= hi]
    run._rws_state_step = None
    if modules:
        ops.sort()
        modules.sort()
        busy, k = 0, 0
        for a, b in modules:
            end = a
            while k < len(ops) and ops[k][0] < b:
                s, e = max(ops[k][0], end), min(ops[k][1], b)
                if ops[k][1] > a and e > s:
                    busy += e - s
                    end = e
                k += 1
        run._rws_state_step = (len(modules), busy * 1e-9)
    return run._rws_state_step
