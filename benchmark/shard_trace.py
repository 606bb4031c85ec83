"""A traced run of a table sharded over chips, chip by chip: device time by
operation on every chip of the cell, which `trace_reduce.Reduction` gives for
the first alone. Nothing of the arithmetic is new: the trace is loaded by
`trace_reduce.load_xplane` and each chip's operations are reduced by
`trace_reduce.reduce` over the trace with the other chips' planes left out,
so window, clipping and names are the ones every other reader has.

    chips = shard_trace.of(run)       # [Reduction of chip 0, of chip 1, ...]
    shard_trace.launches(chips, rx)   # per chip [events, seconds] of a name
    shard_trace.exchange(run)         # seconds rows were in flight, by op kind

How the row programs of a sharded table (`multiverso_tpu/ops/sharded_rows`)
appear in a trace, compiled for a described v5e 2x2 (the names are the HLO
instructions'; `jax.named_scope` reaches them only through the Pallas call,
which takes the innermost scope's name):

    %shard_scatter = ... custom-call(s32[<slots>] ..., s32[1] ..., f32[<slots>,<cols>] ..., f32[<block rows>,<lanes>] ...)
    %fusion = f32[<slots>,<lanes>] fusion(f32[<block rows>,<lanes>] %param, s32[<padded slots>] ...), kind=kCustom
    %collective-permute-start / -done: segments on their way between chips (line `Async XLA Ops` holds each transfer
        from its start to its end, on the first chip; `XLA Ops` the instants they are issued and the waits for them)
    jit_sharded_row_add(...), jit_sharded_row_get(...) on line `XLA Modules`: one event a program a chip

A program without them (the parent of the PR that brought the routed
programs) has no such event, and the readers return None."""

import os
import re

from benchmark import common, trace_reduce

SCATTER = re.compile(r"^%shard_scatter[\w.]* = ")
# XLA's gather: a custom fusion over (rows to pick from, ids)
GATHER = re.compile(
    r"= f32\[(\d+),(\d+)\]\S* fusion\(f32\[(\d+),\d+\]\S* %[\w.\-]+, "
    r"s32\[\d+\]\S* %[\w.\-]+\), kind=kCustom")
EXCHANGE = re.compile(r"^%collective-permute-(start|done)[\w.]* = ")
MODULES_LINE, ASYNC_LINE = "XLA Modules", "Async XLA Ops"
MODULE_KIND = re.compile(r"sharded_row_(add|get)")


def of(run):
    """One Reduction a chip of the cell, from the run's trace; None where
    the run was not traced. Loaded once a run."""
    if not run.trace:
        return None
    if not hasattr(run, "_shard_trace"):
        run._shard_raw = trace_reduce.load_xplane(trace_reduce.find_xplane(
            os.path.join(common.BENCH_DIR, ".trace", run.cell["name"])))
        run._shard_trace = by_chip(run._shard_raw, run.chips)
    return run._shard_trace


def by_chip(trace, chips):
    """[Reduction] of chips 0 .. chips-1 of a trace in plain form, each over
    that chip's plane and the host's; None for a chip that ran nothing."""
    out = []
    for chip in range(chips):
        planes = []
        for plane in trace["planes"]:
            device = trace_reduce.DEVICE_PLANE.match(plane["name"])
            if device is None or int(device.group(1)) == chip:
                planes.append(plane)
        try:
            out.append(trace_reduce.reduce({"planes": planes}, 1))
        except ValueError:
            out.append(None)
    return out


def launches(chips, matches):
    """For each chip [events, seconds] of the operations whose raw name
    ``matches`` (a function of the name), None where the chip ran none."""
    out = []
    for chip in chips:
        found = [slot for name, slot in (chip.raw_ops.items() if chip else ())
                 if matches(name)]
        out.append([sum(n for n, _ in found), sum(s for _, s in found)]
                   if found else None)
    return out


def slowest(per_chip):
    """(events, seconds, ms a launch) of the chip whose launches took
    longest each; None where no chip ran one."""
    ran = [(seconds / events, events, seconds)
           for events, seconds in filter(None, per_chip) if events]
    if not ran:
        return None
    each, events, seconds = max(ran)
    return events, seconds, 1e3 * each


def table_gathers(chips):
    """Per chip [events, seconds] of the gather over the table's block: of
    the gather fusions a chip ran, the one that picks from the most rows
    (the others pick from an op's rows: the delta's, a Get's)."""
    out = []
    for chip in chips:
        picked_from = {name: int(m.group(3)) for name in
                       (chip.raw_ops if chip else ())
                       if (m := GATHER.search(name))}
        most = max(picked_from.values(), default=None)
        out.extend(launches([chip], lambda name: picked_from.get(name, 0)
                            == most) if picked_from else [None])
    return out


def exchange(run):
    """`exchange_in` of the run's trace; None where the run was not traced."""
    return exchange_in(run._shard_raw) if of(run) is not None else None


def exchange_in(trace):
    """{"add": [programs, seconds], "get": [...]} on the first chip of a
    trace in plain form: for every sharded Add and Get program of the
    window (`XLA Modules`), the time during which one of its
    collective-permutes was in flight (the union of their intervals on
    `Async XLA Ops`; where the trace has no such line, of the waits for
    them on `XLA Ops`). Empty where the chip ran no such program."""
    window, lines = None, {}
    for plane in trace["planes"]:
        device = trace_reduce.DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            if device and int(device.group(1)) == 0:
                lines.setdefault(line["name"], []).extend(line["events"])
            elif not device:
                for name, start, dur in line["events"]:
                    if name == trace_reduce.WINDOW_SPAN:
                        window = (start, start + dur)
    flights = [[s, s + d] for n, s, d in lines.get(ASYNC_LINE, ())
               if EXCHANGE.search(n)] or [
        [s, s + d] for n, s, d in lines.get(trace_reduce.OPS_LINE, ())
        if EXCHANGE.search(n)]
    flights = trace_reduce._union(flights)
    out = {}
    for name, start, dur in lines.get(MODULES_LINE, ()):
        kind = MODULE_KIND.search(name)
        if not kind or (window and not (window[0] <= start
                                        and start + dur <= window[1])):
            continue
        slot = out.setdefault(kind.group(1), [0, 0.0])
        slot[0] += 1
        slot[1] += 1e-9 * sum(b - a for a, b in trace_reduce._clip(
            flights, start, start + dur))
    return out
