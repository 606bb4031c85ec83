"""An op's timeline across the host and the device on one clock: every row
launch of the traced window joined to the device programs it started, the
device clock's offset bounded by causality, and an in-process op's latency
tiled from the caller's call to the caller having its result.

    timeline = op_timeline.of(run)     # None: not traced, or the program
                                       # leaves no WORKER_SUBMIT record
    op_timeline.metric(run, name)      # one number of it, for layers/<name>.py

`of` is pulled once a run and prints one JSON line, `{"op_timeline": ...}`.
Three clocks meet here and two steps put them on one:

1. **Ring clock to the trace's host clock.** The op trace (`op_trace.of`) is
   on `perf_counter_ns`; every section of it is also a `TraceAnnotation`,
   which the profiler stamps with its own host clock. The window's
   `TABLE_ROW_LAUNCH` ring records are paired, in order, with the trace's
   events of that name (the counts must agree) and the median difference is
   the step; the residuals' spread is printed (two clock reads apart).
2. **The device clock's correction `d`, bounded and not assumed.** A launch
   owns the executions on the first chip's `XLA Modules` line that begin,
   corrected, between its start and the next launch's start (one dispatcher
   thread, one in-order device queue), but none that begins after the
   launch's own waiter has returned: that program is nobody's and is
   counted (`unowned_programs`). The reader looks for the correction
   nearest 0, in steps of `STEP_NS` up to `SEARCH_NS` either way, under
   which every launch owns a program and the two bounds below leave room;
   a launch that owns none under any of them fails the run. Under that
   ownership no program begins before the launch that dispatched it, so
   `d >= max(launch start - first program's start)`: the lower bound. No
   program ends after a host call that needed its result returned, so
   `d <= min(return - last program's end)` over the waiters: the caller's
   `bench.op.*` span that holds the launch, and the `TABLE_HOST_READ` that
   fetched a served Get's rows. Both are printed (`device_offset_us`
   `lower`, `upper`, `slack`).
3. **Tighter where the trace allows, and stretch by stretch.** Where the
   trace holds the runtime's own two host events a program (`ENQUEUED`: the
   program handed to the device; `DONE`: the host told it has ended), no
   program begins before the first or ends after the second, which narrows
   both bounds (on several chips the runtime has one of each a chip: a
   program's first hand-over and last "done"); a trace without exactly
   one of each a program and chip is read without them. And the profiler
   re-bases the device clock about once a second (steps of 20-280 us on
   the v5e's host), so the bounds are taken for each stretch of about a
   second of the window (`by_second`) and a launch is read at its own
   stretch's lower bound. **Every
   device-relative number is computed there**: the launch nearest its
   program reads `launch_to_device` 0 (with the runtime's events: the
   program nearest its hand-over), so `launch_to_device` is understated
   and the two tails (`ready_tail`, `host_read_tail`) overstated by at most
   the stretch's slack (upper less lower bound; the metric
   `trace_clock_slack_us` is the widest stretch's). With the runtime's
   events an op also gets three numbers that need no device clock at all
   (`without_device_clock_ms`: see `_tile`).

The tiling of an in-process op whose `bench.op.*` sample lies in the window
(its `WORKER_SUBMIT` is the one inside the sample; that record's `op` gives
the rest): `submit` (the call to the message stamped into the dispatcher's
queue: `SERVER_QUEUE_WAIT`'s start, tens of microseconds before `Server.send`
returns; `submit_after_enqueue` says how many) | `queue_wait`
(`SERVER_QUEUE_WAIT`) | `service` (service begins to the op's first
`TABLE_ROW_LAUNCH`: dispatch, `TABLE_ROW_PREP`, bookkeeping) |
`launch_to_device` | `device` (first owned program's start to the last
one's end) | `ready_tail` (to the caller having its result); `turnaround`
is one op's return to the next one's call. The six tile the op by
construction, so their means add up to the mean latency. The first chip's
idle intervals are cut at these boundaries and summed by the segment they
fall in (`other`: outside every op).

    python benchmark/op_timeline.py --record <cell> <seed> <ms> <out.json.gz>

runs the cell traced (on the chip) and writes the first `<ms>` of what the
reader was handed, cut between two ops: `benchmark/fixtures/` keeps one.
"""

import bisect
import gzip
import json
import os
import statistics
import sys
from types import SimpleNamespace

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import op_trace, rws_trace, trace_reduce  # noqa: E402
from benchmark.op_trace import NS_PER_MS  # noqa: E402
from benchmark.shard_trace import MODULES_LINE  # noqa: E402
from benchmark.trace_reduce import OPS_LINE, WINDOW_SPAN  # noqa: E402

LAUNCH = "TABLE_ROW_LAUNCH"
SEARCH_NS, STEP_NS = 2_000_000, 20_000
PARTS = ("submit", "queue_wait", "service", "launch_to_device", "device",
         "ready_tail")

# the runtime's own host events, one a program where the trace has them:
# the program handed to the device, the host told it is done
ENQUEUED, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"
_NONE = np.iinfo(np.int64).max

_record = None   # (milliseconds, path): set by --record alone


# -- the trace's plain form ------------------------------------------------------

def _device(plane):
    """The chip a plane of the plain form is, None for a host plane."""
    chip = trace_reduce.DEVICE_PLANE.match(plane["name"])
    return int(chip.group(1)) if chip else None


def _lines(raw, chip):
    """{line name: events sorted by start} of one chip's plane."""
    out = {}
    for plane in raw["planes"]:
        if _device(plane) == chip:
            for line in plane["lines"]:
                out.setdefault(line["name"], []).extend(line["events"])
    return {name: sorted(events, key=lambda e: e[1])
            for name, events in out.items()}


def _host_events(raw, names):
    """{name: sorted start instants} of the host events of `names`, and the
    window span's (start, end) where the trace has one."""
    starts, window = {name: [] for name in names}, None
    for plane in raw["planes"]:
        if _device(plane) is not None:
            continue
        for line in plane["lines"]:
            for event, start, dur in line["events"]:
                if event in starts:
                    starts[event].append(start)
                elif event == WINDOW_SPAN:
                    window = (start, start + dur)
    return {name: sorted(found) for name, found in starts.items()}, window


def _idle(ops, lo, hi):
    """[start, end) intervals of [lo, hi) that no operation covers."""
    gaps, at = [], lo
    for _, start, dur in ops:
        if start > at:
            gaps.append((at, min(start, hi)))
        at = max(at, start + dur)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


# -- the join --------------------------------------------------------------------

class Join:
    """Launches (host instants, ns) joined to module executions (device
    instants, ns, as the trace gives them). `returns[i]` is the host instant
    launch i's result was in a caller's hands (negative: nobody waited).
    A launch owns the programs that begin, corrected, from its start to the
    next launch's start, but none that begins after its own waiter has
    returned: that one is nobody's (`unowned`, with the programs before the
    first launch; `owned` marks the rest of the programs given).
    `first[i]` / `last[i]` index launch i's programs in `starts` / `ends`
    (the owned programs'), `lower` and `upper` bound the correction to
    device times (`upper` None where nobody waited), `found` is the
    correction of the search that settled the ownership."""

    def __init__(self, launches, modules, returns):
        self.launches = np.asarray(launches, np.int64)
        returns = np.asarray(returns, np.int64)
        modules = sorted(modules, key=lambda e: e[1])
        starts = np.array([s for _, s, _ in modules], np.int64)
        ends = np.array([s + d for _, s, d in modules], np.int64)
        n = len(self.launches)
        if not n:
            raise RuntimeError(f"op_timeline: no {LAUNCH} in the window")
        waited = returns >= 0
        corrections = [c for step in range(0, SEARCH_NS + 1, STEP_NS)
                       for c in ((step, -step) if step else (0,))]
        # an ownership is consistent when the two bounds it implies leave
        # room for a correction; where none is (a device clock that drifts
        # over the window), the nearest under which every launch owns a
        # program is kept and the slack reads negative
        for consistent in (True, False):
            for d in corrections:
                owner = np.searchsorted(self.launches, starts + d,
                                        side="right") - 1
                owned = (owner >= 0) & ~(waited[owner]
                                         & (starts + d > returns[owner]))
                if not np.bincount(owner[owned], minlength=n).all():
                    continue
                self.owned, self.starts, self.ends = (owned, starts[owned],
                                                      ends[owned])
                self._own(owner[owned], returns, waited)
                if not consistent or self.upper is None \
                        or self.lower <= self.upper:
                    self.found = d
                    return
        owner = np.searchsorted(self.launches, starts, side="right") - 1
        bare = np.flatnonzero(np.bincount(owner[owner >= 0], minlength=n) == 0)
        raise RuntimeError(
            f"op_timeline: {len(bare)} of {n} launches own no device program "
            f"under any clock correction within {SEARCH_NS / 1e3:.0f} us "
            f"(the first: launch {bare[0]} at {self.launches[bare[0]]} ns); "
            f"{len(modules)} programs")

    def _own(self, owner, returns, waited):
        n = len(self.launches)
        self.unowned = int((~self.owned).sum())
        self.first = np.searchsorted(owner, np.arange(n), side="left")
        self.last = np.searchsorted(owner, np.arange(n), side="right") - 1
        # per launch: the least correction its own program allows, and the
        # largest its waiter does (none: no bound)
        self.begun = self.starts[self.first]   # each launch's first program
        self.low = self.launches - self.begun
        self.high = np.where(waited, returns - self.ends[self.last], _NONE)
        self.lower = int(self.low.max())
        self.upper = int(self.high.min()) if waited.any() else None

    def tighten(self, enqueued, done):
        """The runtime's own events, one a program (host instants at which
        it was handed to the device and at which the host was told it was
        done): no program begins before the first or ends after the
        second."""
        self.enqueued, self.done = enqueued[self.owned], done[self.owned]
        enqueued, done = self.enqueued, self.done
        low = np.maximum.reduceat(enqueued - self.starts, self.first)
        high = np.minimum.reduceat(done - self.ends, self.first)
        self.low = np.maximum(self.low, low)
        self.high = np.minimum(self.high, high)

    def correct(self, t0, t1):
        """Settle the correction stretch by stretch of the window [t0, t1),
        each about a second long (the profiler re-bases the device clock
        about once a second, in steps of tens to hundreds of microseconds):
        each launch takes the lower bound of its stretch. Returns [(lower,
        upper or None)] a stretch, None for one without a launch."""
        stretches = max(1, round((t1 - t0) * 1e-9))
        mine = np.minimum((self.launches - t0) * stretches // (t1 - t0),
                          stretches - 1)
        bounds = []
        self.d = np.zeros(len(self.launches), np.int64)
        for s in range(stretches):
            here = mine == s
            if not here.any():
                bounds.append(None)
                continue
            high = int(self.high[here].min())
            bounds.append((int(self.low[here].max()),
                           None if high == _NONE else high))
            self.d[here] = bounds[-1][0]
        return bounds

    def device_start(self, i):
        return int(self.begun[i] + self.d[i])

    def device_end(self, i):
        return int(self.ends[self.last[i]] + self.d[i])

    def correction_at(self, device_instant):
        """The correction of the launch that owns, or last preceded, a
        device instant."""
        i = np.searchsorted(self.begun, device_instant, side="right") - 1
        return int(self.d[max(i, 0)])


# -- the timeline ----------------------------------------------------------------

def _fold(values, scale=NS_PER_MS):
    values = [v / scale for v in values]
    if not values:
        return None
    return {"mean": statistics.fmean(values),
            "median": statistics.median(values)}


def _one_a_program(starts, count, chips, pick):
    """The host events of one name (sorted `starts`), one a program, where
    the trace has exactly `count` of them a chip (else None): on several
    chips a program's `chips` events are `pick`ed from (`np.min`: the first
    hand-over; `np.max`: the last "done")."""
    if len(starts) != count * chips:
        return None
    return pick(np.array(starts, np.int64).reshape(count, chips), axis=1)


def _joined(raw, trace, samples):
    """The two clock steps and the join, from plain data: what `timeline`
    reports on and `cut` cuts by."""
    records = sorted(trace.spans(LAUNCH), key=lambda r: r.start_ns)
    host, window = _host_events(raw, (LAUNCH, ENQUEUED, DONE))
    events = host[LAUNCH]
    if len(events) != len(records):
        raise RuntimeError(
            f"op_timeline: the ring has {len(records)} {LAUNCH} records in "
            f"the window and the trace {len(events)} events of that name")
    launches = np.array([r.start_ns for r in records], np.int64)
    steps = np.array(events, np.int64) - launches
    to_trace = int(np.floor(np.median(steps)))   # ring clock -> trace's host clock

    lines = _lines(raw, 0)
    modules = lines.get(MODULES_LINE, [])
    if not modules:
        raise RuntimeError("op_timeline: the first chip's plane has no "
                           f"{MODULES_LINE!r} line")
    # everything from here on is on the ring's clock: an instant t of the
    # trace is t - to_trace there, a device instant t - to_trace + d

    # who waited for each launch's result
    ops = sorted((a, b, name.rsplit(".", 1)[1])
                 for name, spans in samples.items() for a, b in spans
                 if a >= trace.t0_ns and b <= trace.t1_ns)
    returns = np.full(len(records), -1, np.int64)
    held = np.searchsorted([a for a, _, _ in ops], launches,
                           side="right") - 1
    for i, k in enumerate(held):
        if k >= 0 and launches[i] < ops[k][1]:
            returns[i] = ops[k][1]
    reads = {}
    for read in trace.spans("TABLE_HOST_READ"):
        reads.setdefault(read.parent, read)
    fetched = {}    # launch index -> the TABLE_HOST_READ behind it
    for i, r in enumerate(records):
        read = reads.get(r.parent) if r.parent else None
        if read is not None and read.start_ns >= r.start_ns:
            fetched[i] = read
            end = read.start_ns + read.dur_ns
            returns[i] = end if returns[i] < 0 else min(returns[i], end)
    join = Join(launches, [(n, s - to_trace, d) for n, s, d in modules],
                returns)
    return SimpleNamespace(
        records=records, launches=launches, window=window, lines=lines,
        modules=modules, to_trace=to_trace, residual=steps - to_trace,
        ops=ops, returns=returns, fetched=fetched, join=join, host=host)


def timeline(raw, trace, samples, chips=1):
    """The `{"op_timeline": ...}` line's content from plain data: `raw` the
    trace in `trace_reduce`'s plain form, `trace` the window's op trace (an
    `op_trace.Trace`), `samples` `{"bench.op.add": [(a_ns, b_ns), ...],
    ...}` on the ring's clock (empty where the callers are other
    processes)."""
    joined = _joined(raw, trace, samples)
    join, records, launches = joined.join, joined.records, joined.launches
    modules, to_trace, residual = (joined.modules, joined.to_trace,
                                   joined.residual)
    fetched, returns = joined.fetched, joined.returns
    q1, q3 = np.percentile(residual, [25, 75])
    lower, upper = join.lower, join.upper   # from the program's spans alone
    enqueued = _one_a_program(joined.host[ENQUEUED], len(modules), chips,
                              np.min)
    done = _one_a_program(joined.host[DONE], len(modules), chips, np.max)
    runtime = enqueued is not None and done is not None
    if runtime:
        join.tighten(enqueued - to_trace, done - to_trace)
    by_second = join.correct(trace.t0_ns, trace.t1_ns)
    slacks = [hi - lo for lo, hi in filter(None, by_second) if hi is not None]

    kinds = [trace._kind(r).lstrip(".") or "other" for r in records]
    to_device = [join.device_start(i) - int(launches[i])
                 for i in range(len(records))]
    out = {
        "launches": len(records), "programs": len(modules),
        "unowned_programs": join.unowned,
        "ring_to_trace_ns": to_trace,
        "ring_to_trace_residual_us": {
            "iqr": float(q3 - q1) / 1e3,
            "max": float(np.abs(residual).max()) / 1e3},
        "device_offset_us": {
            "lower": lower / 1e3,
            "upper": None if upper is None else upper / 1e3,
            "slack": None if upper is None else (upper - lower) / 1e3,
            "ownership_settled_at": join.found / 1e3,
            "runtime_events": [ENQUEUED, DONE] if runtime else None,
            "by_second": [b and {
                "lower": b[0] / 1e3,
                "upper": None if b[1] is None else b[1] / 1e3}
                for b in by_second]},
        "at": "each second's lower bound: launch_to_device is understated "
              "and ready_tail and host_read_tail are overstated by at most "
              "that second's slack (upper less lower)",
        "launch_to_device_ms": dict(
            _fold(to_device), **{
                kind: _fold(v for v, k in zip(to_device, kinds) if k == kind)
                for kind in sorted(set(kinds))}),
    }

    segments = []   # (start, end, part) on the ring's clock, for the idle time
    tiled = _tile(trace, joined.ops, records, join, segments, runtime)
    if tiled:
        out.update(tiled)
    else:           # no in-process op: a launch's own stretch, a Get's fetch
        for i in range(len(records)):
            begun, ended = join.device_start(i), join.device_end(i)
            segments += [(int(launches[i]), begun, "launch_to_device"),
                         (begun, ended, "device")]
            if i in fetched:
                segments.append((ended, int(returns[i]), "host_read_tail"))
    if fetched:
        out["host_read_tail_ms"] = dict(_fold(
            fetched[i].start_ns + fetched[i].dur_ns - join.device_end(i)
            for i in fetched), count=len(fetched))

    lo, hi = joined.window or (min(s for _, s, _ in modules),
                               max(s + n for _, s, n in modules))
    idle = []
    for a, b in _idle(joined.lines.get(OPS_LINE, []), lo, hi):
        d = join.correction_at(a - to_trace)
        idle.append((a - to_trace + d, b - to_trace + d))
    by_part = _share(idle, segments)
    out["idle_s"] = {"total": sum(b - a for a, b in idle) * 1e-9,
                     "by_segment": {k: v * 1e-9 for k, v in by_part.items()}}
    if chips > 1:
        out["shard_end_skew_us"] = _end_skew(raw, chips, joined.lines)

    submits = trace.spans("WORKER_SUBMIT")
    out["metrics"] = {
        "op_submit_ms": (sum(r.dur_ns for r in submits) / len(submits)
                         / NS_PER_MS if submits else None),
        "op_around_device_ms": tiled and tiled["around_device_ms"]["mean"],
        "launch_to_device_ms": out["launch_to_device_ms"]["mean"],
        "op_ready_tail_ms": tiled and tiled["ready_tail_ms"]["mean"],
        "host_read_tail_ms": (out["host_read_tail_ms"]["mean"]
                              if fetched else None),
        "trace_clock_slack_us": max(slacks) / 1e3 if slacks else None,
    }
    return out


def _tile(trace, ops, records, join, segments, runtime):
    """The tiling of the window's in-process ops, Add and Get apart, and the
    turnaround between them; appends every segment to `segments`. None
    where the window holds no such op. Where the program under the samples
    submits nothing (a bare jitted loop), an op's launches are those that
    begin inside its sample and the host parts are one, `before_launch`.
    With the runtime's own events (`runtime`) an op also gets three numbers
    that need no device clock: `launch_to_enqueue` (the launch's start to
    the runtime handing its first program to the device),
    `enqueue_to_done_less_device` (from there to the runtime being told
    the last one is done, less the device interval: what the runtime and
    the hardware spend around the program) and `done_to_ready` (to the
    caller having its result); the three and the device interval add up to
    `launch_to_device + device + ready_tail`."""
    waits, launches_of = {}, {}      # op -> its queue waits, its launches
    for r in trace.spans("SERVER_QUEUE_WAIT"):
        waits.setdefault(r.op, []).append(r)
    for i, r in enumerate(records):
        launches_of.setdefault(r.op, []).append(i)
    submits = sorted(trace.spans("WORKER_SUBMIT"), key=lambda r: r.start_ns)
    starts = [r.start_ns for r in submits]
    names = PARTS if submits else ("before_launch",) + PARTS[3:]
    extra = ("launch_to_enqueue", "enqueue_to_done_less_device",
             "done_to_ready") if runtime else ()
    launched = [r.start_ns for r in records]
    parts = {}           # kind -> part -> [ns]
    around, tails, after, turnaround = [], [], [], []
    for k, (a, b, kind) in enumerate(ops):
        if submits:
            at = bisect.bisect_left(starts, a)
            if at == len(submits) or starts[at] >= b:
                raise RuntimeError(f"op_timeline: no WORKER_SUBMIT inside "
                                   f"the bench.op.{kind} sample at {a} ns")
            submit = submits[at]
            waited = waits.get(submit.op, ())
            mine = launches_of.get(submit.op, ())
            if len(waited) != 1:
                raise RuntimeError(
                    f"op_timeline: op {submit.op} (bench.op.{kind} at {a} "
                    f"ns) left {len(waited)} SERVER_QUEUE_WAIT records")
            host = [a, waited[0].start_ns,
                    waited[0].start_ns + waited[0].dur_ns]
            after.append(submit.start_ns + submit.dur_ns - host[1])
        else:
            mine = range(bisect.bisect_left(launched, a),
                         bisect.bisect_left(launched, b))
            host = [a]
        if not mine:
            raise RuntimeError(f"op_timeline: the bench.op.{kind} sample at "
                               f"{a} ns holds no {LAUNCH}")
        first, last = mine[0], mine[-1]
        begun, ended = join.device_start(first), join.device_end(last)
        edges = host + [launched[first], begun, ended, b]
        slot = parts.setdefault(
            kind, {p: [] for p in names + extra + ("latency",)})
        slot["latency"].append(b - a)
        for part, lo, hi in zip(names, edges, edges[1:]):
            slot[part].append(hi - lo)
            segments.append((lo, hi, part))
        if runtime:
            handed = int(join.enqueued[join.first[first]])
            told = int(join.done[join.last[last]])
            slot["launch_to_enqueue"].append(handed - launched[first])
            slot["enqueue_to_done_less_device"].append(
                told - handed - (ended - begun))
            slot["done_to_ready"].append(b - told)
        around.append(b - a - (ended - begun))
        tails.append(b - ended)
        if k + 1 < len(ops):
            turnaround.append(ops[k + 1][0] - b)
            segments.append((b, ops[k + 1][0], "turnaround"))
    if not parts:
        return None
    out = {"ops": {}}
    for kind, slot in sorted(parts.items()):
        means = {p: statistics.fmean(slot[p]) / NS_PER_MS for p in names}
        out["ops"][kind] = {
            "count": len(slot["latency"]),
            "latency_ms": _fold(slot["latency"]),
            "mean_ms": means,
            "sum_of_means_ms": sum(means.values()),
            "median_ms": {p: statistics.median(slot[p]) / NS_PER_MS
                          for p in names}}
        if runtime:
            out["ops"][kind]["without_device_clock_ms"] = {
                p: _fold(slot[p]) for p in extra}
    out["turnaround_ms"] = _fold(turnaround)
    out["around_device_ms"] = _fold(around)
    out["ready_tail_ms"] = _fold(tails)
    out["submit_after_enqueue_ms"] = _fold(after)
    return out


def _share(idle, segments):
    """Nanoseconds of the idle intervals by the segment they fall in;
    `other` is what no segment covers. Segments are taken in time order and
    each begins no earlier than the one before it ended."""
    out = {}
    segments = sorted(s for s in segments if s[1] > s[0])
    k, covered = 0, 0
    for a, b in sorted(idle):
        while k < len(segments) and segments[k][1] <= a:
            k += 1
        j, at = k, a
        while j < len(segments) and segments[j][0] < b:
            lo, hi = max(segments[j][0], at), min(segments[j][1], b)
            if hi > lo:
                out[segments[j][2]] = out.get(segments[j][2], 0) + hi - lo
                covered += hi - lo
                at = hi
            j += 1
    out["other"] = sum(b - a for a, b in idle) - covered
    return out


def _end_skew(raw, chips, first_chip):
    """By how much the other chips' program ends trail the first chip's,
    execution by execution (each chip's clock as the trace gives it): a
    tail that is really a slow shard reads as one. None where the chips
    ran different numbers of programs."""
    ends = [np.array([s + d for _, s, d in
                      _lines(raw, chip).get(MODULES_LINE, [])], np.int64)
            for chip in range(1, chips)]
    mine = np.array([s + d for _, s, d in first_chip[MODULES_LINE]], np.int64)
    if any(len(e) != len(mine) for e in ends):
        return None
    trail = np.max([e - mine for e in ends], axis=0)
    return {"mean": float(trail.mean()) / 1e3,
            "median": float(np.median(trail)) / 1e3,
            "max": float(trail.max()) / 1e3}


# -- one run ---------------------------------------------------------------------

def of(run):
    """The timeline of `run`'s traced window, pulled once a run; None where
    the run was not traced or the program leaves neither a WORKER_SUBMIT
    record nor a cause on its queue waits (the parent of the PR that
    brought them)."""
    if not hasattr(run, "_op_timeline"):
        run._op_timeline = _pull(run)
    return run._op_timeline


def metric(run, name):
    found = of(run)
    return found and found["metrics"][name]


def _pull(run):
    if not run.trace:
        return None
    trace = op_trace.of(run)
    if trace is None or not (trace.spans("WORKER_SUBMIT") or any(
            r.parent for r in trace.spans("SERVER_QUEUE_WAIT"))):
        return None
    samples = {name: [(round(a * 1e9), round(b * 1e9)) for a, b in spans]
               for name, spans in run.spans.samples.items()
               if name.startswith("bench.op.")}
    raw = rws_trace._raw(run)
    if _record:
        _write_cut(raw, trace, samples, *_record)
    found = timeline(raw, trace, samples, run.chips)
    found["reduction_idle_s"] = run.trace.window_s - run.trace.busy_by_device[0]
    print(json.dumps({"op_timeline": found}), flush=True)
    return found


# -- a recorded cut ----------------------------------------------------------------

def cut(raw, trace, samples, milliseconds):
    """The first `milliseconds` of what `timeline` is handed, ended where
    the first op that would straddle the cut begins (nothing is in flight
    there): the host spans and the runtime's two events a program up to
    there, the first chip's operations and programs up to the end of the
    last program a kept launch owns (the device's clock may run a
    millisecond off the host's), the ring records and the samples, all
    rebased to the window's start on each clock."""
    joined = _joined(raw, trace, samples)
    window, join = joined.window, joined.join
    limit = trace.t0_ns + int(milliseconds * 1e6)
    end = min(a for a, b, _ in joined.ops if b > limit)
    kept = int(np.searchsorted(joined.launches, end)) - 1
    device_end = int(join.ends[join.last[kept]]) + joined.to_trace
    hi = window[0] + end - trace.t0_ns
    planes = []
    for plane in raw["planes"]:
        chip = _device(plane)
        if chip not in (None, 0):
            continue
        lines = []
        for line in plane["lines"]:
            if chip == 0 and line["name"] in (OPS_LINE, MODULES_LINE):
                # whole events, as the device's clock has them
                events = [[n, s - window[0], d] for n, s, d in line["events"]
                          if s + d <= device_end]
            elif chip == 0:
                continue
            else:
                events = [[n, max(s, window[0]) - window[0],
                           min(s + d, hi) - max(s, window[0])]
                          for n, s, d in line["events"]
                          if (trace_reduce.HOST_SPAN.match(n)
                              or n in (ENQUEUED, DONE))
                          and s < hi and s + d > window[0]]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes,
            "ring": [[*r[:4], r.start_ns - trace.t0_ns, *r[5:]]
                     for r in trace.records
                     if r.start_ns + r.dur_ns <= end],
            "samples": {name: [[a - trace.t0_ns, b - trace.t0_ns]
                               for a, b in spans
                               if a >= trace.t0_ns and b <= end]
                        for name, spans in samples.items()},
            "window_ns": [0, end - trace.t0_ns]}


def load_cut(path):
    """(raw, trace, samples) of a file `cut` wrote."""
    from multiverso_tpu.dashboard import OpRecord
    with gzip.open(path, "rt") as f:
        kept = json.load(f)
    trace = op_trace.Trace([OpRecord._make(r) for r in kept["ring"]],
                           *kept["window_ns"])
    return ({"planes": kept["planes"]}, trace,
            {name: [tuple(s) for s in spans]
             for name, spans in kept["samples"].items()})


def _write_cut(raw, trace, samples, milliseconds, path):
    with gzip.open(path, "wt") as f:
        json.dump(cut(raw, trace, samples, milliseconds), f)


if __name__ == "__main__":
    from benchmark import op_timeline, run as bench_run
    if len(sys.argv) != 6 or sys.argv[1] != "--record":
        sys.exit(__doc__)
    op_timeline._record = (float(sys.argv[4]), sys.argv[5])
    sys.exit(bench_run.main(["--workload", sys.argv[2], "--seed", sys.argv[3],
                             "--trace", "1"]))
