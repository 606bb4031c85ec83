"""From a profiler trace to numbers: device busy and idle time, device time by
operation, and the idle gaps by what the host was doing.

Two steps, so that the arithmetic can be checked without a chip:
``load_xplane`` turns JAX's ``.xplane.pb`` into plain data
(``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns], ...]}]}]}``), and ``reduce`` works on that plain data alone.
``benchmark/fixtures/`` holds one small recorded trace in the plain form.

    python benchmark/trace_reduce.py --dump <dir>        what a trace holds
    python benchmark/trace_reduce.py --cut <dir> <ms> <out.json>
"""

import glob
import gzip
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# host spans that may own an idle gap: the benchmark's own and the
# program's monitors (upper-case names)
HOST_SPAN = re.compile(r"^(bench\.[\w.]+|[A-Z][A-Z0-9_]{3,})$")


def load_xplane(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_HLO = re.compile(r"^(%[^\s=]+) = \S+ ([\w\-]+)\(")


def short_name(name):
    """An operation's name and opcode out of the HLO text the trace gives:
    '%fusion.3 = f32[8,128]{...} fusion(...)' -> '%fusion.3 fusion'."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name.split(" = ")[0][:80]


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


class Reduction:
    """busy_s and window_s in seconds; raw_ops maps a device operation's
    name as the trace gives it (the HLO text, shapes included) to [events,
    seconds] on device 0 inside the window, op_seconds the same by short
    name."""

    def __init__(self, window_s, busy_s, busy_by_device, raw_ops, gaps):
        self.window_s, self.busy_s = window_s, busy_s
        self.busy_by_device = busy_by_device
        self.raw_ops, self.gaps = raw_ops, gaps
        self.op_seconds = {}
        for name, (events, seconds) in raw_ops.items():
            slot = self.op_seconds.setdefault(short_name(name), [0, 0.0])
            slot[0] += events
            slot[1] += seconds

    @property
    def idle_share(self):
        return 1.0 - self.busy_by_device[0] / self.window_s

    def ops_matching(self, pattern):
        """[(raw name, events, seconds)] of the device-0 operations whose
        short name matches the regular expression."""
        rx = re.compile(pattern)
        return [(k, n, s) for k, (n, s) in self.raw_ops.items()
                if rx.search(short_name(k))]

    def breakdown(self, top=10):
        ops = sorted(((k, s) for k, (_, s) in self.op_seconds.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, s] for k, s in ops],
                "idle_gaps": [[k, s] for k, s in gaps]}


def reduce(trace, chips=1):
    """The plain form of a trace -> Reduction. The window is the host span
    ``bench.window`` where the trace has one, else the extent of the device
    operations. Busy is the union of the intervals in which an operation
    ran on a device; a gap is charged to the shortest host span that covers
    its middle, or to ``(no span)``."""
    devices, host_spans, window = {}, [], None
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            if m:
                if line["name"] == OPS_LINE:
                    devices.setdefault(int(m.group(1)), []).extend(
                        line["events"])
                continue
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    window = (start, start + dur)
                elif HOST_SPAN.match(name):
                    host_spans.append((start, start + dur, name))
    if not devices:
        raise ValueError("the trace holds no device operation: planes "
                         + ", ".join(p["name"] for p in trace["planes"]))
    ids = sorted(devices)[:chips]
    if window is None:
        window = (min(e[1] for d in ids for e in devices[d]),
                  max(e[1] + e[2] for d in ids for e in devices[d]))
    lo, hi = window
    busy = {}
    for d in ids:
        busy[d] = _clip(_union([[s, s + n] for _, s, n in devices[d]]),
                        lo, hi)
    busy_s = {d: sum(b - a for a, b in busy[d]) * 1e-9 for d in ids}
    first = ids[0]
    op_seconds = {}
    for name, start, dur in devices[first]:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            slot = op_seconds.setdefault(name, [0, 0.0])
            slot[0] += 1
            slot[1] += (b - a) * 1e-9
    gaps = {}
    edges = [lo] + [t for iv in busy[first] for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        owners = [(e - s, name) for s, e, name in host_spans if s <= mid < e]
        owner = min(owners)[1] if owners else "(no span)"
        gaps[owner] = gaps.get(owner, 0.0) + (b - a) * 1e-9
    return Reduction((hi - lo) * 1e-9,
                     sum(busy_s.values()) / len(ids),
                     [busy_s[d] for d in ids], op_seconds, gaps)


def reduce_dir(trace_dir, chips=1):
    return reduce(load_xplane(find_xplane(trace_dir)), chips)


def cut(trace, milliseconds):
    """The first ``milliseconds`` of the window of a trace in plain form,
    device operations and host spans only: small enough to keep."""
    lo = None
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    lo = start
    if lo is None:
        raise ValueError("no bench.window span to cut from")
    hi = lo + int(milliseconds * 1e6)
    planes = []
    for plane in trace["planes"]:
        device = DEVICE_PLANE.match(plane["name"])
        lines = []
        for line in plane["lines"]:
            if device and line["name"] != OPS_LINE:
                continue
            events = [[n, s, d] for n, s, d in line["events"]
                      if (device or HOST_SPAN.match(n)) and s < hi
                      and s + d > lo]
            events = [[n, max(s, lo) - lo, min(s + d, hi) - max(s, lo)]
                      if n != WINDOW_SPAN else [n, 0, hi - lo]
                      for n, s, d in events]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def _dump(trace_dir):
    trace = load_xplane(find_xplane(trace_dir))
    for plane in trace["planes"]:
        print("plane", plane["name"])
        for line in plane["lines"]:
            names = {}
            for name, _, dur in line["events"]:
                slot = names.setdefault(name, [0, 0])
                slot[0] += 1
                slot[1] += dur
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            print("  line", repr(line["name"]), len(line["events"]), "events")
            for name, (count, total) in top:
                print(f"    {total * 1e-6:10.3f} ms {count:7d}  {name[:110]}")


if __name__ == "__main__":
    if sys.argv[1] == "--dump":
        _dump(sys.argv[2])
    elif sys.argv[1] == "--cut":
        small = cut(load_xplane(find_xplane(sys.argv[2])), float(sys.argv[3]))
        opener = gzip.open if sys.argv[4].endswith(".gz") else open
        with opener(sys.argv[4], "wt") as f:
            json.dump(small, f)
