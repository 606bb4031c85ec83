"""An op's timeline across host and device (`benchmark/op_timeline.py`): the
join of row launches to device programs, the two clock steps, the causal
bounds of the device clock's correction, the tiling of an in-process op and
the idle time by segment, on synthetic traces in `trace_reduce`'s plain form
whose every number is known, against an oracle that shares no code with the
reader, and on a cut recorded on the chip. Then the program's half: the
records an in-process Add and Get leave (`WORKER_SUBMIT`, the cause of a
`SERVER_QUEUE_WAIT`).

Times of the in-program tests come from a CPU run: they check order, cause
and counts, never how fast anything is."""

import bisect
import json
import os
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu import dashboard
from multiverso_tpu.dashboard import Dashboard, OpRecord, span
from multiverso_tpu.runtime.message import Message, MsgType
from multiverso_tpu.runtime.server import _ExecWaiter
from multiverso_tpu.runtime.zoo import Zoo

from benchmark import common, op_timeline, op_trace

PARTS = op_timeline.PARTS
FIXTURE = os.path.join(common.BENCH_DIR, "fixtures",
                       "op_timeline_bulk_rows_40ms")


# -- a synthetic window --------------------------------------------------------

class Synthetic:
    """A closed loop of Add then Get, `pairs` times, every segment of every
    op drawn from `rng` in odd nanoseconds; the ring on one clock, the
    trace's host events `to_trace` ns later, the device's events `skew` ns
    off the trace's host clock (negative: they appear early)."""

    LEAD, END = 3_000_017, 2_500_003   # window before the first / after the last op
    # the proportions of an in-process op on the chip's host, ns
    RANGES = {"submit": (100_000, 200_000), "queue_wait": (80_000, 150_000),
              "service": (300_000, 500_000),
              "launch_to_device": (50_000, 300_000),
              "device": (400_000, 1_800_000),
              "ready_tail": (200_000, 600_000),
              "turnaround": (20_000, 100_000)}

    def __init__(self, pairs=6, to_trace=987_654_321, skew=0, seed=0,
                 programs=(1, 2), jitter=0, runtime=False, step=None,
                 ids_at_submit=False):
        """`step`: (instant on the ring's clock, ns added to the skew from
        there on): the profiler re-basing the device clock. `runtime`: the
        runtime's own enqueue and completion events around each program.
        `ids_at_submit`: the records of a program whose callers send their
        ids up at submit: a WORKER_ROW_IDS inside every WORKER_SUBMIT, and
        launches that say `caller` (every Get's ids landed, no Add's)."""
        rng = np.random.default_rng(seed)
        self.to_trace, self.skew, self.step = to_trace, skew, step
        self.runtime, self.ids_at_submit = runtime, ids_at_submit
        self.ring, self.samples, self.ops = [], {}, []
        self.host, self.modules, self.device_ops = [], [], []
        self._ids = iter(range(1, 1 << 20))
        t = 10_000_000_000
        self.t0 = t
        t += self.LEAD
        for k in range(2 * pairs):
            kind = "get" if k % 2 else "add"
            part = {p: int(rng.integers(lo, hi)) | 1
                    for p, (lo, hi) in self.RANGES.items()}
            t = self._op(t, k + 100, kind, part, programs[k % 2], rng, jitter)
            self.ops.append((kind, part))
        self.t1 = t - self.ops[-1][1]["turnaround"] + self.END
        self.host.append(["bench.window", self.t0 + 7 + to_trace,
                          self.t1 - self.t0 - 9])

    def _record(self, stage, start, dur, op, parent=0, n=0, span_id=None):
        span_id = next(self._ids) if span_id is None else span_id
        self.ring.append(OpRecord(len(self.ring), span_id, parent, stage,
                                  start, dur, 0, op, n))
        return span_id

    def _op(self, a, op, kind, part, programs, rng, jitter):
        KIND = kind.upper()
        enq = a + part["submit"]
        began = enq + part["queue_wait"]
        launch = began + part["service"]
        start = launch + part["launch_to_device"]
        end = start + part["device"]
        b = end + part["ready_tail"]
        submit = self._record("WORKER_SUBMIT", a + 1_001, enq - a - 1_001 + 2_003,
                              op, n=100_000)
        if self.ids_at_submit:
            self._record("WORKER_ROW_IDS", a + 2_001, (enq - a) // 2, 0,
                         submit, n=100_000)
        self._record("SERVER_QUEUE_WAIT", enq, began - enq, op, submit,
                     span_id=0)
        dispatch = self._record("SERVER_DISPATCH_MSG", began + 11,
                                launch - began + 150_000, op)
        process = self._record(f"SERVER_PROCESS_{KIND}_MSG", began + 21,
                               launch - began + 140_000, op, dispatch)
        table = self._record(f"TABLE_PROCESS_{KIND}", began + 31,
                             launch - began + 130_000, op, process)
        self._record("TABLE_ROW_PREP", began + 41, launch - began - 51, op,
                     table)
        self._record("TABLE_ROW_LAUNCH", launch, 110_001, op, table,
                     n=100_096)
        if self.ids_at_submit:
            self.ring[-1] = self.ring[-1]._replace(
                ids_from="caller", ids_ready=int(kind == "get"))
        self._record("WORKER_WAIT", enq + 3_001, launch + 120_000 - enq, op)
        wobble = int(rng.integers(-jitter, jitter + 1)) if jitter else 0
        self.host.append(["TABLE_ROW_LAUNCH",
                          launch + self.to_trace + wobble, 110_001])
        self.host.append([f"bench.op.{kind}", a + self.to_trace, b - a])
        # `programs` module executions, a gap between them, operations
        # inside each with a gap of their own
        cuts = np.linspace(start, end, 2 * programs).astype(np.int64)
        for lo, hi in zip(cuts[0::2], cuts[1::2]):
            lo, hi = int(lo), int(hi)
            at = lo + self.to_trace + self.skew_at(lo)
            self.modules.append([f"jit_row_{kind}(1)", at, hi - lo])
            if self.runtime:
                # handed to the device 1/4 of launch-to-start before it
                # begins; the host is told 1/3 of the tail after it ends
                self.host.append([
                    op_timeline.ENQUEUED,
                    lo - part["launch_to_device"] // 4 + self.to_trace,
                    30_001])
                self.host.append([
                    op_timeline.DONE,
                    hi + part["ready_tail"] // 3 + self.to_trace, 40_001])
            third = (hi - lo) // 3
            self.device_ops.append(["%fusion = f32[8,128]{1,0} fusion()", at,
                                    third])
            self.device_ops.append(["%copy = f32[8,128]{1,0} copy()",
                                    at + 2 * third, hi - lo - 2 * third])
        self.samples.setdefault(f"bench.op.{kind}", []).append((a, b))
        return b + part["turnaround"]

    def skew_at(self, instant):
        stepped = self.step and instant >= self.step[0]
        return self.skew + (self.step[1] if stepped else 0)

    @property
    def raw(self):
        return {"planes": [
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": self.modules},
                {"name": "XLA Ops", "events": self.device_ops},
                {"name": "Steps", "events": [["0", 0, 5]]}]},
            {"name": "/host:CPU", "lines": [
                {"name": "python", "events": self.host}]}]}

    @property
    def trace(self):
        return op_trace.Trace(self.ring, self.t0, self.t1)

    def timeline(self, **kwargs):
        return op_timeline.timeline(self.raw, self.trace, self.samples,
                                    **kwargs)

    @property
    def least_launch_to_device(self):
        return min(part["launch_to_device"] for _, part in self.ops)


# -- an oracle that shares no code with the reader ---------------------------------

def oracle(raw, trace, samples):
    """Ownership by a scan over the launches for each program, under the
    first of the launch-to-program differences (tried as the correction,
    nearest 0 first) that gives every launch a program and leaves the two
    bounds room; the tiling from the records; the idle time segment by
    segment over the elementary intervals between all boundary points,
    each classified by its midpoint."""
    launches = sorted(r.start_ns for r in trace.spans("TABLE_ROW_LAUNCH"))
    events = sorted(s for p in raw["planes"] if "device" not in p["name"]
                    for line in p["lines"] for n, s, _ in line["events"]
                    if n == "TABLE_ROW_LAUNCH")
    diffs = sorted(e - l for e, l in zip(events, launches))
    to_trace = (diffs[(len(diffs) - 1) // 2] + diffs[len(diffs) // 2]) // 2
    chip = [p for p in raw["planes"] if p["name"] == "/device:TPU:0"][0]
    modules = sorted((s - to_trace, s - to_trace + d) for line in chip["lines"]
                     if line["name"] == "XLA Modules"
                     for _, s, d in line["events"])
    busy = sorted((s - to_trace, s - to_trace + d) for line in chip["lines"]
                  if line["name"] == "XLA Ops" for _, s, d in line["events"])

    def owners(d):
        out = [[] for _ in launches]
        for m in modules:
            mine = [i for i, l in enumerate(launches) if l <= m[0] + d]
            if mine:
                out[mine[-1]].append(m)
        return out

    ops = sorted((a, b, name.rsplit(".", 1)[1])
                 for name, spans in samples.items() for a, b in spans)

    def bounds(owned):
        return (max(l - ms[0][0] for l, ms in zip(launches, owned)),
                min(b - owned[i][-1][1] for a, b, _ in ops
                    for i, l in enumerate(launches) if a <= l < b))

    candidates = sorted({l - m[0] for l in launches for m in modules
                         if abs(l - m[0]) < 2_000_000}, key=abs)
    owned = next(o for o in map(owners, [0] + candidates)
                 if all(o) and bounds(o)[0] <= bounds(o)[1])
    lower, upper = spans_alone = bounds(owned)
    # the runtime's own events, one a program, tighten the lower bound the
    # numbers are read at (one stretch: the oracle's windows are short)
    assert trace.t1_ns - trace.t0_ns < 1_500_000_000
    told = {name: sorted(s - to_trace for p in raw["planes"]
                         if "device" not in p["name"] for line in p["lines"]
                         for n, s, _ in line["events"] if n == name)
            for name in (op_timeline.ENQUEUED, op_timeline.DONE)}
    if all(len(v) == len(modules) for v in told.values()):
        lower = max(lower, max(e - m[0] for e, m in zip(
            told[op_timeline.ENQUEUED], modules)))
    tiles, segments = {}, []
    for k, (a, b, kind) in enumerate(ops):
        submit, = [r for r in trace.spans("WORKER_SUBMIT")
                   if a <= r.start_ns < b]
        wait, = [r for r in trace.spans("SERVER_QUEUE_WAIT")
                 if r.op == submit.op]
        mine = [i for i, l in enumerate(launches) if a <= l < b]
        edges = [a, wait.start_ns, wait.start_ns + wait.dur_ns,
                 launches[mine[0]], owned[mine[0]][0][0] + lower,
                 owned[mine[-1]][-1][1] + lower, b]
        for part, lo, hi in zip(PARTS, edges, edges[1:]):
            tiles.setdefault(kind, {}).setdefault(part, []).append(hi - lo)
            segments.append((lo, hi, part))
        if k + 1 < len(ops):
            segments.append((b, ops[k + 1][0], "turnaround"))
    window, = [(s - to_trace, s - to_trace + d) for p in raw["planes"]
               if "device" not in p["name"] for line in p["lines"]
               for n, s, d in line["events"] if n == "bench.window"]
    # the device's clock, corrected, against the host's segments
    busy = [(s + lower, e + lower) for s, e in busy]
    lo, hi = window[0] + lower, window[1] + lower
    points = sorted({lo, hi, *[t for iv in busy for t in iv],
                     *[t for s in segments for t in s[:2]]})
    points = [t for t in points if lo <= t <= hi]
    starts = [s for s, _ in busy]
    idle = {}
    for p, q in zip(points, points[1:]):
        mid = (p + q) / 2
        k = bisect.bisect_right(starts, mid) - 1
        if k >= 0 and any(s <= mid < e for s, e in busy[max(0, k - 2):k + 1]):
            continue
        part = [name for s, e, name in segments if s <= mid < e]
        name = part[0] if part else "other"
        idle[name] = idle.get(name, 0) + q - p
    return {"to_trace": to_trace, "lower": spans_alone[0],
            "upper": spans_alone[1], "read_at": lower, "tiles": tiles,
            "idle": idle}


def _ns(microseconds):
    return round(microseconds * 1e3)


def _same_as_oracle(found, expected):
    assert found["ring_to_trace_ns"] == expected["to_trace"]
    assert _ns(found["device_offset_us"]["lower"]) == expected["lower"]
    assert _ns(found["device_offset_us"]["upper"]) == expected["upper"]
    stretch, = found["device_offset_us"]["by_second"]
    assert _ns(stretch["lower"]) == expected["read_at"]
    for kind, tiles in expected["tiles"].items():
        for part in PARTS:
            assert found["ops"][kind]["mean_ms"][part] == pytest.approx(
                np.mean(tiles[part]) / 1e6, abs=1e-9), (kind, part)
    assert {k: round(v * 1e9) for k, v in
            found["idle_s"]["by_segment"].items() if round(v * 1e9)} == \
        {k: v for k, v in expected["idle"].items() if v}


# -- the reader on synthetic traces --------------------------------------------

def test_every_segment_is_recovered_to_the_nanosecond_and_the_means_tile():
    made = Synthetic(pairs=6)
    found = made.timeline()
    least = made.least_launch_to_device
    assert found["launches"] == found["programs"] / 1.5 == 12
    assert found["unowned_programs"] == 0
    assert found["ring_to_trace_ns"] == made.to_trace
    assert found["ring_to_trace_residual_us"] == {"iqr": 0.0, "max": 0.0}
    # with no skew the correction's lower bound is minus the window's least
    # launch-to-start: the launch nearest its program reads 0
    assert _ns(found["device_offset_us"]["lower"]) == -least
    for kind in ("add", "get"):
        mine = [part for k, part in made.ops if k == kind]
        got = found["ops"][kind]
        assert got["count"] == 6
        want = {p: np.mean([part[p] for part in mine]) for p in PARTS}
        want["launch_to_device"] -= least
        want["ready_tail"] += least
        for p in PARTS:
            assert got["mean_ms"][p] * 1e6 == pytest.approx(want[p], abs=1e-3)
        latency = np.mean([sum(part[p] for p in PARTS) for part in mine])
        assert got["latency_ms"]["mean"] * 1e6 == pytest.approx(latency,
                                                                abs=1e-3)
        assert got["sum_of_means_ms"] == pytest.approx(
            got["latency_ms"]["mean"], abs=1e-9)
    turnaround = [part["turnaround"] for _, part in made.ops[:-1]]
    assert found["turnaround_ms"]["mean"] * 1e6 == pytest.approx(
        np.mean(turnaround), abs=1e-3)
    around = [sum(part[p] for p in PARTS) - part["device"]
              for _, part in made.ops]
    assert found["metrics"]["op_around_device_ms"] * 1e6 == pytest.approx(
        np.mean(around), abs=1e-3)
    assert found["metrics"]["launch_to_device_ms"] * 1e6 == pytest.approx(
        np.mean([part["launch_to_device"] for _, part in made.ops]) - least,
        abs=1e-3)
    assert found["metrics"]["op_ready_tail_ms"] * 1e6 == pytest.approx(
        np.mean([part["ready_tail"] for _, part in made.ops]) + least,
        abs=1e-3)
    assert found["metrics"]["host_read_tail_ms"] is None
    _same_as_oracle(found, oracle(made.raw, made.trace, made.samples))


@pytest.mark.parametrize("skew", [-450_000, 450_000, -1_300_000])
def test_an_injected_device_offset_is_recovered_as_the_lower_bound(skew):
    made = Synthetic(pairs=5, skew=skew, seed=3)
    found = made.timeline()
    least = made.least_launch_to_device
    offset = found["device_offset_us"]
    assert _ns(offset["lower"]) == -skew - least
    tails = [part["ready_tail"] for _, part in made.ops]
    assert _ns(offset["upper"]) == -skew + min(tails)
    assert _ns(offset["slack"]) == least + min(tails)
    assert found["metrics"]["trace_clock_slack_us"] == offset["slack"]
    assert offset["by_second"] == [{"lower": offset["lower"],
                                    "upper": offset["upper"]}]
    assert offset["runtime_events"] is None
    assert found["unowned_programs"] == 0
    # what the reader reports does not depend on the skew it removed
    plain = Synthetic(pairs=5, skew=0, seed=3).timeline()
    assert found["ops"] == plain["ops"]
    assert found["idle_s"]["by_segment"] == plain["idle_s"]["by_segment"]
    _same_as_oracle(found, oracle(made.raw, made.trace, made.samples))


def test_the_runtimes_own_events_tighten_the_bounds_and_split_without_a_clock():
    made = Synthetic(pairs=5, skew=-700_000, seed=4, runtime=True,
                     programs=(1, 1))
    found = made.timeline()
    loose = Synthetic(pairs=5, skew=-700_000, seed=4,
                      programs=(1, 1)).timeline()
    offset = found["device_offset_us"]
    assert offset["runtime_events"] == [op_timeline.ENQUEUED,
                                        op_timeline.DONE]
    # the bounds from the program's own spans are printed as they were
    for key in ("lower", "upper", "slack"):
        assert offset[key] == loose["device_offset_us"][key]
    handed = [p["launch_to_device"] // 4 for _, p in made.ops]
    told = [p["ready_tail"] // 3 for _, p in made.ops]
    stretch, = offset["by_second"]
    assert _ns(stretch["lower"]) == 700_000 - min(handed)
    assert _ns(stretch["upper"]) == 700_000 + min(told)
    assert _ns(found["metrics"]["trace_clock_slack_us"]) \
        == min(handed) + min(told) < _ns(offset["slack"])
    for kind in ("add", "get"):
        mine = [p for k, p in made.ops if k == kind]
        got = found["ops"][kind]
        # at the tightened lower bound the device is min(handed) late
        assert got["mean_ms"]["launch_to_device"] * 1e6 == pytest.approx(
            np.mean([p["launch_to_device"] for p in mine]) - min(handed),
            abs=1e-3)
        plain = got["without_device_clock_ms"]
        assert plain["launch_to_enqueue"]["mean"] * 1e6 == pytest.approx(
            np.mean([p["launch_to_device"] - p["launch_to_device"] // 4
                     for p in mine]), abs=1e-3)
        assert plain["enqueue_to_done_less_device"]["mean"] * 1e6 == \
            pytest.approx(np.mean([p["launch_to_device"] // 4
                                   + p["ready_tail"] // 3 for p in mine]),
                          abs=1e-3)
        assert plain["done_to_ready"]["mean"] * 1e6 == pytest.approx(
            np.mean([p["ready_tail"] - p["ready_tail"] // 3 for p in mine]),
            abs=1e-3)
        assert sum(v["mean"] for v in plain.values()) == pytest.approx(
            got["mean_ms"]["launch_to_device"]
            + got["mean_ms"]["ready_tail"], abs=1e-9)
        assert got["sum_of_means_ms"] == pytest.approx(
            got["latency_ms"]["mean"], abs=1e-9)
    _same_as_oracle(found, oracle(made.raw, made.trace, made.samples))
    # one event short of one a program: the reader does without them
    del made.host[[e[0] for e in made.host].index(op_timeline.DONE)]
    assert made.timeline()["device_offset_us"]["runtime_events"] is None


def test_a_device_clock_that_steps_is_corrected_stretch_by_stretch():
    """The profiler re-bases the device clock about once a second: each
    stretch of the window has its own bounds and its launches are read at
    their own stretch's lower bound."""
    plain = Synthetic(pairs=420, seed=6, programs=(1, 1))
    middle = (plain.t0 + plain.t1) // 2
    assert round((plain.t1 - plain.t0) * 1e-9) == 2
    made = Synthetic(pairs=420, seed=6, programs=(1, 1), skew=-300_000,
                     step=(middle, -250_000))
    found = made.timeline()
    first, second = found["device_offset_us"]["by_second"]
    launches = sorted(r.start_ns for r in made.ring
                      if r.stage == "TABLE_ROW_LAUNCH")
    least = [min(p["launch_to_device"] for (_, p), at
                 in zip(made.ops, launches) if (at >= middle) == late)
             for late in (False, True)]
    assert _ns(first["lower"]) == 300_000 - least[0]
    assert _ns(second["lower"]) == 550_000 - least[1]
    assert _ns(found["device_offset_us"]["lower"]) == 550_000 - least[1]
    for kind in ("add", "get"):
        want = np.mean([p["launch_to_device"] - least[at >= middle]
                        for (k, p), at in zip(made.ops, launches)
                        if k == kind])
        assert found["ops"][kind]["mean_ms"]["launch_to_device"] * 1e6 == \
            pytest.approx(want, abs=1e-3)
    idle = found["idle_s"]
    assert sum(idle["by_segment"].values()) == pytest.approx(idle["total"],
                                                             abs=1e-12)
    # a wrong correction would push idle time across the segments' edges
    # (the one op that straddles the step may: a microsecond of room)
    assert idle["by_segment"]["device"] == pytest.approx(
        plain.timeline()["idle_s"]["by_segment"]["device"], abs=1e-6)


def test_the_ring_clock_step_is_the_median_and_its_spread_is_printed():
    made = Synthetic(pairs=8, jitter=4_000, seed=5)
    found = made.timeline()
    assert abs(found["ring_to_trace_ns"] - made.to_trace) <= 4_000
    spread = found["ring_to_trace_residual_us"]
    assert 0 < spread["iqr"] <= spread["max"] * 2 and spread["max"] <= 8.0


def test_a_launch_with_no_program_fails_the_run():
    made = Synthetic(pairs=3)
    del made.modules[4:6]      # the third op's two programs
    with pytest.raises(RuntimeError, match="own no device program"):
        made.timeline()
    made = Synthetic(pairs=3)
    del made.host[2]           # a launch the trace does not have
    with pytest.raises(RuntimeError, match="records in the window"):
        made.timeline()


def test_an_unowned_program_is_counted():
    made = Synthetic(pairs=3)
    first = min(s for _, s, _ in made.modules)
    made.modules.append(["jit_stray(7)", first - 2_400_000, 50_000])
    made.device_ops.append(["%stray = f32[8]{0} fusion()",
                            first - 2_400_000, 50_000])
    found = made.timeline()
    assert found["unowned_programs"] == 1
    assert found["programs"] == 10 and found["launches"] == 6
    # nor does a launch own what begins after its own waiter has returned
    # (a served Get's rows are fetched; a stray program follows): the
    # bounds and the tails are as without it
    plain = Synthetic(pairs=3).timeline()
    made = Synthetic(pairs=3)
    a, b = made.samples["bench.op.get"][1]
    made.modules.append(["jit_stray(7)", b + 3_001 + made.to_trace, 271])
    made.device_ops.append(["%copy.1 = f32[8]{0} copy()",
                            b + 3_001 + made.to_trace, 271])
    found = made.timeline()
    assert found["unowned_programs"] == 1 and found["programs"] == 10
    assert found["device_offset_us"] == plain["device_offset_us"]
    assert found["ops"] == plain["ops"]


@pytest.mark.parametrize("seed", range(4))
def test_idle_time_by_segment_adds_up_to_the_idle_time(seed):
    made = Synthetic(pairs=4 + seed, seed=10 + seed,
                     skew=int(-200_000 * seed))
    found = made.timeline()
    idle = found["idle_s"]
    assert sum(idle["by_segment"].values()) == pytest.approx(idle["total"],
                                                             abs=1e-12)
    # every part of an op but its device interval is wholly idle, and the
    # device interval idles in the gaps its programs and operations leave
    least = made.least_launch_to_device
    for part in PARTS + ("turnaround",):
        want = sum(p[part] for _, p in made.ops)
        if part == "turnaround":
            want -= made.ops[-1][1][part]
        elif part == "launch_to_device":
            want -= least * len(made.ops)
        elif part == "ready_tail":
            want += least * len(made.ops)
        elif part == "device":
            continue
        assert idle["by_segment"][part] * 1e9 == pytest.approx(want, abs=1)
    assert 0 < idle["by_segment"]["device"] < sum(
        p["device"] for _, p in made.ops) * 1e-9
    # the same total the device's idle share is taken from
    from benchmark import trace_reduce
    reduced = trace_reduce.reduce(made.raw)
    assert idle["total"] == pytest.approx(
        reduced.window_s - reduced.busy_by_device[0], abs=1e-12)
    _same_as_oracle(found, oracle(made.raw, made.trace, made.samples))


def test_a_served_get_reads_its_tail_from_the_fetch_and_no_tiling():
    """The remote cell's shape: no in-process op, a TABLE_HOST_READ behind
    every Get's launch, nothing behind an Add's."""
    made = Synthetic(pairs=4, seed=7, programs=(1, 1))
    for i, r in enumerate(list(made.ring)):
        if r.stage == "TABLE_ROW_LAUNCH" and i and \
                made.ring[i - 2].stage == "TABLE_PROCESS_GET":
            made.ring.append(r._replace(
                seq=len(made.ring), id=900_000 + i, stage="TABLE_HOST_READ",
                start_ns=r.start_ns + r.dur_ns + 5,
                dur_ns=1_200_001, n=524_288))
    made.ring = [r._replace(parent=5_000_000) if r.stage == "SERVER_QUEUE_WAIT"
                 else r for r in made.ring if r.stage != "WORKER_SUBMIT"]
    found = op_timeline.timeline(made.raw, made.trace, {})
    assert "ops" not in found and found["launches"] == 8
    least = made.least_launch_to_device
    gets = [part for kind, part in made.ops if kind == "get"]
    tails = [110_001 + 5 + 1_200_001
             - (part["launch_to_device"] + part["device"]) + least
             for part in gets]
    assert found["host_read_tail_ms"]["count"] == 4
    assert found["metrics"]["host_read_tail_ms"] * 1e6 == pytest.approx(
        np.mean(tails), abs=1e-3)
    assert _ns(found["device_offset_us"]["upper"]) == min(tails) - least
    for name in ("op_submit_ms", "op_around_device_ms", "op_ready_tail_ms"):
        assert found["metrics"][name] is None
    assert found["metrics"]["launch_to_device_ms"] * 1e6 == pytest.approx(
        np.mean([p["launch_to_device"] for _, p in made.ops]) - least,
        abs=1e-3)
    idle = found["idle_s"]
    assert set(idle["by_segment"]) == {"launch_to_device", "device",
                                       "host_read_tail", "other"}
    assert sum(idle["by_segment"].values()) == pytest.approx(idle["total"],
                                                             abs=1e-12)


def test_a_bare_loop_has_one_host_part_before_its_launch():
    """`benchmark/tests/bare_loop.py`'s shape: a launch section and a
    bench.op span an op, nothing submitted, nothing queued."""
    made = Synthetic(pairs=4, seed=9, skew=-300_000, programs=(1, 1))
    made.ring = [r._replace(op=0, parent=0) for r in made.ring
                 if r.stage == "TABLE_ROW_LAUNCH"]
    found = made.timeline()
    least = made.least_launch_to_device
    names = ("before_launch", "launch_to_device", "device", "ready_tail")
    for kind in ("add", "get"):
        mine = [part for k, part in made.ops if k == kind]
        got = found["ops"][kind]
        assert tuple(got["mean_ms"]) == names
        want = {"before_launch": np.mean([
                    p["submit"] + p["queue_wait"] + p["service"]
                    for p in mine]),
                "launch_to_device": np.mean(
                    [p["launch_to_device"] for p in mine]) - least,
                "device": np.mean([p["device"] for p in mine]),
                "ready_tail": np.mean([p["ready_tail"] for p in mine]) + least}
        for name in names:
            assert got["mean_ms"][name] * 1e6 == pytest.approx(want[name],
                                                               abs=1e-3)
        assert got["sum_of_means_ms"] == pytest.approx(
            got["latency_ms"]["mean"], abs=1e-9)
    assert found["metrics"]["op_submit_ms"] is None
    assert _ns(found["device_offset_us"]["lower"]) == 300_000 - least
    assert set(found["launch_to_device_ms"]) == {"mean", "median", "other"}


def test_other_chips_program_ends_are_laid_against_the_first_chips():
    made = Synthetic(pairs=3)
    raw = made.raw
    for chip, late in ((1, 7_000), (2, 31_000), (3, -2_000)):
        raw["planes"].append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Modules", "events": [
                [n, s, d + late] for n, s, d in made.modules]}]})
    found = op_timeline.timeline(raw, made.trace, made.samples, chips=4)
    assert found["shard_end_skew_us"] == {"mean": 31.0, "median": 31.0,
                                          "max": 31.0}
    del raw["planes"][-1]["lines"][0]["events"][0]
    assert op_timeline.timeline(raw, made.trace, made.samples,
                                chips=4)["shard_end_skew_us"] is None
    # the runtime's events come one a chip: a program's first hand-over
    # and its last "done" bound the first chip's program
    one = Synthetic(pairs=3, runtime=True, programs=(1, 1), skew=-500_000)
    four = Synthetic(pairs=3, runtime=True, programs=(1, 1), skew=-500_000)
    for name, start, dur in list(four.host):
        late = {op_timeline.ENQUEUED: 1, op_timeline.DONE: -1}.get(name)
        if late:
            four.host += [[name, start + late * k * 9_001, dur]
                          for k in (1, 2, 3)]
    got = op_timeline.timeline(four.raw, four.trace, four.samples, chips=4)
    assert got["device_offset_us"]["runtime_events"]
    assert got["device_offset_us"]["by_second"] \
        == one.timeline()["device_offset_us"]["by_second"]
    assert got["ops"] == one.timeline()["ops"]


# -- the reader as a run hands it over -----------------------------------------

def _run(made, **fields):
    from benchmark import trace_reduce
    spans = SimpleNamespace(samples={
        name: [(a * 1e-9, b * 1e-9) for a, b in pairs]
        for name, pairs in made.samples.items()})
    run = SimpleNamespace(trace=trace_reduce.reduce(made.raw), chips=1,
                          spans=spans, window=(made.t0 * 1e-9, made.t1 * 1e-9),
                          cell={"name": "synthetic"},
                          _op_trace=made.trace, _rws_raw=made.raw)
    for name, value in fields.items():
        setattr(run, name, value)
    return run


def test_of_prints_one_line_once_and_the_layer_files_read_it(capsys):
    run = _run(Synthetic(pairs=3, skew=-450_000))
    found = op_timeline.of(run)
    assert op_timeline.of(run) is found
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"op_timeline"')]
    assert len(lines) == 1
    line = lines[0]["op_timeline"]
    assert line["reduction_idle_s"] == pytest.approx(line["idle_s"]["total"],
                                                     rel=1e-9)
    declared = {m["name"]: m for m in
                common.load_json("BENCHMARK.json")["per_layer"]}
    for name, value in found["metrics"].items():
        assert name in declared
        assert common.load_module("layers", name).read(run) == value
    assert found["metrics"]["host_read_tail_ms"] is None
    assert found["metrics"]["op_submit_ms"] > 0


def test_a_program_without_the_new_records_reads_none():
    """The parent of the PR: no WORKER_SUBMIT, queue waits without a cause;
    and an untraced run."""
    made = Synthetic(pairs=2)
    made.ring = [r._replace(parent=0) if r.stage == "SERVER_QUEUE_WAIT" else r
                 for r in made.ring if r.stage != "WORKER_SUBMIT"]
    for run in (_run(made), _run(Synthetic(pairs=2), trace=None)):
        assert op_timeline.of(run) is None
        for name in ("op_submit_ms", "op_around_device_ms",
                     "launch_to_device_ms", "op_ready_tail_ms",
                     "host_read_tail_ms", "trace_clock_slack_us"):
            assert common.load_module("layers", name).read(run) is None


def test_ids_sent_at_submit_leave_the_tiling_as_it_was():
    """A WORKER_ROW_IDS inside every WORKER_SUBMIT and two more fields on
    every launch record move no tile: the six still sum to the op's
    latency, and `row_ids_landed_share` reads the launches whose ids had
    landed."""
    plain, sent = Synthetic(pairs=4), Synthetic(pairs=4, ids_at_submit=True)
    found = sent.timeline()
    assert found["ops"] == plain.timeline()["ops"]
    for got in found["ops"].values():
        assert got["sum_of_means_ms"] == pytest.approx(
            got["latency_ms"]["mean"], abs=1e-6)
    _same_as_oracle(found, oracle(sent.raw, sent.trace, sent.samples))
    read = common.load_module("layers", "row_ids_landed_share").read
    assert read(_run(sent)) == 50.0
    # half of the launches from a program that has not the field: they
    # are not counted as launches whose ids were late
    sent.ring = [r._replace(ids_from="", ids_ready=0)
                 if r.stage == "TABLE_ROW_LAUNCH" and r.op % 4 < 2 else r
                 for r in sent.ring]
    assert read(_run(sent)) == 50.0


def test_a_trace_without_the_field_reads_no_landed_share():
    """The parent of the PR that brought `ids_from`: launch records without
    it; a ring cut recorded before the field existed; an untraced run."""
    read = common.load_module("layers", "row_ids_landed_share").read
    assert read(_run(Synthetic(pairs=2))) is None
    assert read(_run(Synthetic(pairs=2), _op_trace=None, trace=None)) is None
    _, trace, _ = op_timeline.load_cut(FIXTURE + ".json.gz")
    launches = trace.spans("TABLE_ROW_LAUNCH")
    assert launches and all((r.ids_from, r.ids_ready) == ("", 0)
                            for r in launches)
    assert read(SimpleNamespace(_op_trace=trace)) is None


# -- a cut recorded on the chip ------------------------------------------------

def test_recorded_cut_reduces_to_what_the_oracle_worked_out():
    raw, trace, samples = op_timeline.load_cut(FIXTURE + ".json.gz")
    with open(FIXTURE + ".expected.json") as f:
        expected = json.load(f)
    found = op_timeline.timeline(raw, trace, samples)
    assert found["unowned_programs"] == 0
    assert found["launches"] == expected["launches"]
    _same_as_oracle(found, expected)
    _same_as_oracle(found, oracle(raw, trace, samples))
    for kind, got in found["ops"].items():
        assert got["sum_of_means_ms"] == pytest.approx(
            got["latency_ms"]["mean"], abs=1e-6)
    idle = found["idle_s"]
    assert sum(idle["by_segment"].values()) == pytest.approx(idle["total"],
                                                             abs=1e-12)


def test_cut_ends_between_two_ops_and_loads_back_the_same():
    made = Synthetic(pairs=6)
    kept = op_timeline.cut(made.raw, made.trace, made.samples, 12.0)
    assert json.loads(json.dumps(kept)) == kept
    ops = sorted(s for spans in kept["samples"].values() for s in spans)
    assert 0 < len(ops) < 12 and kept["window_ns"][1] >= ops[-1][1]
    launches = [r for r in kept["ring"] if r[3] == "TABLE_ROW_LAUNCH"]
    events = [e for p in kept["planes"] if p["name"] == "/host:CPU"
              for line in p["lines"] for e in line["events"]
              if e[0] == "TABLE_ROW_LAUNCH"]
    assert len(launches) == len(events) == len(ops)


# -- the program's half ----------------------------------------------------------

ROWS, COLS = 256, 128
IDS = np.arange(32, dtype=np.int32)
ONES = np.ones((len(IDS), COLS), np.float32)


@pytest.fixture
def tracing():
    mv.set_flag("profile_annotations", True)
    Dashboard.profile_annotations = True
    yield
    Dashboard.profile_annotations = False


def _table():
    return mv.create_table("matrix", ROWS, COLS,
                           init_value=np.zeros((ROWS, COLS), np.float32))


def _window(t0):
    Zoo.instance().server.run_serialized(lambda: None)
    return op_trace.of(SimpleNamespace(window=(t0, time.perf_counter())))


def _one(trace, stage, op):
    found, = [r for r in trace.spans(stage) if r.op == op]
    return found


@pytest.mark.parametrize("device", [False, True], ids=["host", "device"])
def test_an_add_and_a_get_each_leave_one_causal_chain(tracing, device):
    import jax
    # a table on one device: a device-path op's ids go up at submit
    mv.init(mesh_shape="1")
    table = _table()
    table.add(ONES, row_ids=IDS)       # compile outside the window
    table.get(IDS)
    delta = jax.device_put(ONES)
    # other rows than the window's: its Add finds none of its ids kept
    table.wait(table.add_device_async(delta, IDS + 100))
    table.wait_device(table.get_device_async(IDS + 100),
                      IDS).block_until_ready()
    t0 = time.perf_counter()
    samples = {}
    for kind in ("add", "get"):
        a = time.perf_counter_ns()
        if kind == "add":
            op = (table.add_device_async(delta, IDS) if device
                  else table.add_async(ONES, row_ids=IDS))
            table.wait(op)
        else:
            op = (table.get_device_async(IDS) if device
                  else table.get_async(IDS))
            table.wait_device(op, IDS) if device else table.wait_get(op, IDS)
        samples[kind] = (op, a, time.perf_counter_ns())
    trace = _window(t0)
    for kind, (op, a, b) in samples.items():
        KIND = kind.upper()
        submit = _one(trace, "WORKER_SUBMIT", op)
        assert submit.id and submit.n == len(IDS) and submit.parent == 0
        wait = _one(trace, "SERVER_QUEUE_WAIT", op)
        assert wait.parent == submit.id and wait.id == 0
        dispatch = _one(trace, "SERVER_DISPATCH_MSG", op)
        process = _one(trace, f"SERVER_PROCESS_{KIND}_MSG", op)
        table_op = _one(trace, f"TABLE_PROCESS_{KIND}", op)
        launch = _one(trace, "TABLE_ROW_LAUNCH", op)
        prep = _one(trace, "TABLE_ROW_PREP", op)
        assert process.parent == dispatch.id
        assert table_op.parent == process.id
        assert launch.parent == prep.parent == table_op.id
        # a device-path op's ids go up inside its submit, on the caller's
        # thread (a child of the span open there), before the message is
        # queued; the dispatcher's prep stays, with the rows named
        sent = [r for r in trace.spans("WORKER_ROW_IDS")
                if r.parent == submit.id]
        assert len(sent) == int(device) and prep.n == len(IDS)
        assert launch.ids_from == ("caller" if device else "dispatcher")
        assert launch.ids_ready in (0, 1)
        for up in sent:
            # the Add sends its ids up; the Get names the same rows and
            # launches on them where they lie (PR 39): nothing goes up
            assert up.n == len(IDS)
            assert up.bytes >= 4 * len(IDS) if kind == "add" \
                else up.bytes == 0 and launch.ids_ready == 1
            assert submit.start_ns <= up.start_ns
            assert up.start_ns + up.dur_ns <= wait.start_ns
        waited = _one(trace, "WORKER_WAIT", op)
        # the message is stamped into the queue inside the submit, service
        # begins when the wait ends, the launch lies inside the service:
        # submit | queue_wait | service, in order and without overlap
        enqueued = wait.start_ns
        began = wait.start_ns + wait.dur_ns
        assert a <= submit.start_ns <= enqueued \
            <= submit.start_ns + submit.dur_ns
        assert enqueued <= began <= dispatch.start_ns <= prep.start_ns
        assert prep.start_ns + prep.dur_ns <= launch.start_ns
        assert launch.start_ns + launch.dur_ns <= b
        assert submit.start_ns + submit.dur_ns <= waited.start_ns
    mv.shutdown()


def test_a_sync_op_and_another_table_kind_submit_under_their_msg_id(tracing):
    mv.init()
    table = _table()
    array = mv.create_table("array", 64)
    t0 = time.perf_counter()
    table.add(ONES, row_ids=IDS)
    array.get()
    trace = _window(t0)
    submits = trace.spans("WORKER_SUBMIT")
    assert len(submits) == 2 and all(s.op and s.n == 0 for s in submits)
    sync, = trace.spans("WORKER_TABLE_SYNC_ADD")
    assert submits[0].parent == sync.id
    for submit in submits:
        assert _one(trace, "SERVER_QUEUE_WAIT", submit.op).parent == submit.id
    mv.shutdown()


def test_switch_off_leaves_no_submit_and_stores_no_span_id():
    mv.init()
    table = _table()
    server = Zoo.instance().server
    sent = []
    push = server._queue.push
    server._queue.push = lambda msg: (sent.append(msg), push(msg))[1]
    t0 = time.perf_counter()
    with span("NEVER_OPENED") as off:
        off.op = 7                     # what `_submit` does to its section
        table.wait(table.add_async(ONES, row_ids=IDS))
        table.wait_get(table.get_async(IDS), IDS)
    records, _ = dashboard.RING.window(t0, time.perf_counter())
    assert records == [] and off.id == 0
    assert len(sent) == 2 and all(m.enq_span == 0 for m in sent)
    # on: a message sent from inside a section carries that section's id
    Dashboard.profile_annotations = True
    try:
        with span("SENDER") as inside:
            server.send(Message(src=-1, dst=-1, type=MsgType.Server_Execute,
                                data=[lambda: None, _ExecWaiter()]))
        server.run_serialized(lambda: None)
    finally:
        Dashboard.profile_annotations = False
    assert sent[-2].enq_span == inside.id != 0
    mv.shutdown()


def test_a_served_requests_wait_hangs_under_its_serve_handle(tracing):
    mv.init(remote_workers=1)
    table = _table()
    client = mv.remote_connect(mv.serve("127.0.0.1:0"))
    remote = client.table(table.table_id)
    remote.add(ONES, row_ids=IDS)
    t0 = time.perf_counter()
    remote.get(IDS)
    trace = _window(t0)
    wait, = trace.spans("SERVER_QUEUE_WAIT")
    handle, = [r for r in trace.spans("SERVE_HANDLE") if r.op == wait.op]
    assert wait.parent == handle.id
    client.close()
    mv.shutdown()


def test_a_messages_span_id_does_not_reach_the_wire():
    from multiverso_tpu.runtime.net import TcpNet
    net = TcpNet()
    msg = Message(src=3, dst=0, type=MsgType.Request_Get, table_id=2,
                  msg_id=11, req_id=5, data=[np.arange(4, dtype=np.float32)])
    plain = bytes(net._frame(msg, 0))
    msg.enq_span, msg.enq_ns = 123_456, 987_654_321
    frame = bytes(net._frame(msg, 0))
    assert frame == plain
    pos = [0]

    def read(n):
        pos[0] += n
        return frame[pos[0] - n:pos[0]]

    decoded = net._read_frame(read, set())
    assert decoded.enq_span == 0 and decoded.enq_ns == 0
    assert decoded.msg_id == 11


def test_submits_from_many_threads_each_cause_their_own_wait(tracing):
    """More workers than cores, a short switch interval: every queue wait
    names the submit of its own op, never a neighbour's."""
    import sys
    mv.init(local_workers=8)
    table = _table()
    table.add(ONES, row_ids=IDS)
    t0 = time.perf_counter()
    done, failed = [], []

    def worker(slot):
        try:
            with mv.worker(slot):
                for _ in range(20):
                    op = table.add_async(ONES, row_ids=IDS)
                    table.wait(op)
                    done.append(op)
        except Exception as e:  # noqa: BLE001 — reported below
            failed.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(s,))
                   for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not failed and not any(t.is_alive() for t in threads)
    trace = _window(t0)
    submits = {r.op: r for r in trace.spans("WORKER_SUBMIT")}
    waits = trace.spans("SERVER_QUEUE_WAIT")
    assert sorted(submits) == sorted(done) and len(waits) == len(done) == 160
    assert all(w.parent == submits[w.op].id for w in waits)
    mv.shutdown()
