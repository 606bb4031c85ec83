"""Named section timers (Monitor/Dashboard) — tracing & profiling subsystem.

Reference capability (not copied): statically-registered named section timers
via ``MONITOR_BEGIN/END`` macros aggregating count/total/average, with a
global ``Dashboard::Watch/Display`` (``include/multiverso/dashboard.h:16-75``,
``src/dashboard.cpp:14-49``).

TPU-era additions: monitors double as ``jax.profiler.TraceAnnotation`` scopes
when profiling is enabled, so named sections show up in TPU traces; the timer
is a context manager / decorator instead of macro pairs. The registry also
holds the telemetry subsystem's units (``multiverso_tpu/obs/``): monotonic
``Counter``\\ s, log-bucketed ``Histogram``\\ s (every ``monitor`` section
records its duration distribution, not just the average), and point-in-time
``Gauge``\\ s. ``snapshot()`` serializes the whole registry for the stats
RPC / metrics JSONL; ``render(format="prom")`` emits Prometheus text
exposition. Metric catalog: ``docs/observability.md``.

The op trace: while ``Dashboard.profile_annotations`` is on, every
``monitor``/``span`` section and every ``obs.trace.hop`` also appends one
:class:`OpRecord` to ``RING``, a fixed in-memory ring on the
``time.perf_counter_ns`` clock; ``RING.window(t0, t1)`` cuts it to a
stretch of that clock (``docs/observability.md`` §2.1).
"""

from __future__ import annotations

import itertools
import re
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

try:  # profiler annotations are optional — pure-host use works without jax
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover
    _TraceAnnotation = None


class Monitor:
    """count / total-elapse / average for one named code section.

    The in-progress start time is THREAD-LOCAL: two threads timing the
    same named section concurrently each measure their own span (a single
    shared slot would let thread B's ``begin`` overwrite thread A's,
    corrupting both durations — the historical bug)."""

    def __init__(self, name: str) -> None:
        self.name = name
        self._count = 0
        self._elapse = 0.0  # seconds
        self._tls = threading.local()  # per-thread in-progress start time
        self._lock = threading.Lock()

    def begin(self) -> None:
        self._tls.begin = time.perf_counter()

    def end(self) -> None:
        begin = getattr(self._tls, "begin", None)
        if begin is None:
            return
        self._tls.begin = None
        self.observe(time.perf_counter() - begin)

    def observe(self, seconds: float) -> None:
        """Record one completed span (the begin/end pair fused — what the
        ``monitor`` context manager calls with its own local clock)."""
        with self._lock:
            self._count += 1
            self._elapse += seconds

    @property
    def count(self) -> int:
        return self._count

    @property
    def elapse_ms(self) -> float:
        return self._elapse * 1e3

    @property
    def average_ms(self) -> float:
        return self.elapse_ms / self._count if self._count else 0.0

    def reset(self) -> None:
        with self._lock:
            self._count = 0
            self._elapse = 0.0
            self._tls = threading.local()

    def __repr__(self) -> str:
        return (f"Monitor({self.name}: count={self.count}, "
                f"elapse={self.elapse_ms:.3f}ms, average={self.average_ms:.3f}ms)")


class Counter:
    """Monotonic event counter — the fault subsystem's observability unit
    (retries, reconnects, evictions, injected faults, dedup hits). Section
    timers (Monitor) measure durations; Counters record discrete events
    that have none."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def add(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0

    def __repr__(self) -> str:
        return f"Counter({self.name}: {self.value})"


def _prom_name(name: str, suffix: str = "") -> str:
    base = re.sub(r"[^a-zA-Z0-9_]", "_", name).lower().strip("_")
    return f"mvtpu_{base}{suffix}"


# per-shard series names (ROUTER_SHARD3_SECONDS, FLEET_SHARD0_REPLICA_LAG)
# collapse into one labeled Prometheus family: the shard index moves from
# the metric name into a shard="3" label, so operators aggregate and
# alert across shards without a regex in every query
_SHARD_SERIES = re.compile(
    r"^(?P<pre>.+?)_SHARD(?P<idx>\d+)(?P<post>(?:_[A-Za-z0-9_]+)?)$")


def _split_shard(name: str):
    """``NAME_SHARD<k>_X`` -> (``NAME_X``, "k"); others -> (name, None)."""
    m = _SHARD_SERIES.match(name)
    if m is None:
        return name, None
    return m.group("pre") + m.group("post"), m.group("idx")


# per-tenant counter families (admission + chargeback planes) collapse
# the same way: TENANT_ctr_SHED becomes mvtpu_tenant_shed_total with a
# tenant="ctr" label. The suffix alternation is anchored so tenant names
# containing underscores (including "_default") split unambiguously.
_TENANT_SERIES = re.compile(
    r"^TENANT_(?P<tenant>.+)_(?P<suffix>ADMITTED|SHED|BYTES)$")


def split_tenant(name: str):
    """``TENANT_<t>_<SUFFIX>`` -> (``t``, ``SUFFIX``); others ->
    (None, None)."""
    m = _TENANT_SERIES.match(name)
    if m is None:
        return None, None
    return m.group("tenant"), m.group("suffix")


def _prom_escape(value: str) -> str:
    """Label-value escaping per the Prometheus text exposition format:
    backslash, double-quote and newline."""
    return (value.replace("\\", "\\\\")
                 .replace('"', '\\"')
                 .replace("\n", "\\n"))


class Dashboard:
    """Global registry of monitors (reference: ``Dashboard::Watch/Display``)
    plus the telemetry units: counters, histograms, gauges."""

    _monitors: Dict[str, Monitor] = {}
    _counters: Dict[str, Counter] = {}
    _histograms: Dict[str, "object"] = {}  # name -> obs.metrics.Histogram
    _gauges: Dict[str, "object"] = {}      # name -> obs.metrics.Gauge
    _lock = threading.Lock()
    profile_annotations: bool = False

    @classmethod
    def get(cls, name: str) -> Monitor:
        with cls._lock:
            mon = cls._monitors.get(name)
            if mon is None:
                mon = cls._monitors[name] = Monitor(name)
            return mon

    @classmethod
    def watch(cls, name: str) -> Optional[Monitor]:
        with cls._lock:
            return cls._monitors.get(name)

    @classmethod
    def counter(cls, name: str) -> Counter:
        with cls._lock:
            ctr = cls._counters.get(name)
            if ctr is None:
                ctr = cls._counters[name] = Counter(name)
            return ctr

    @classmethod
    def counter_value(cls, name: str) -> int:
        """Current count; 0 when the counter was never touched."""
        with cls._lock:
            ctr = cls._counters.get(name)
        return ctr.value if ctr is not None else 0

    @classmethod
    def histogram(cls, name: str, bounds=None):
        """Log-bucketed latency histogram (obs/metrics.py); created on
        first use like monitors/counters. ``bounds`` applies only at
        creation — count-valued histograms (rows per fused apply) pass
        unit-based geometric edges instead of the 1µs latency default,
        whose top edge (~134) they would overflow."""
        with cls._lock:
            hist = cls._histograms.get(name)
            if hist is None:
                # lazy import: dashboard is imported by everything, obs
                # only by what uses it — keeps the import graph acyclic
                from multiverso_tpu.obs.metrics import Histogram
                hist = cls._histograms[name] = Histogram(name, bounds=bounds)
            return hist

    @classmethod
    def gauge(cls, name: str):
        with cls._lock:
            g = cls._gauges.get(name)
            if g is None:
                from multiverso_tpu.obs.metrics import Gauge
                g = cls._gauges[name] = Gauge(name)
            return g

    @classmethod
    def gauge_value(cls, name: str) -> float:
        with cls._lock:
            g = cls._gauges.get(name)
        return g.value if g is not None else 0.0

    @classmethod
    def snapshot(cls) -> dict:
        """The whole registry as plain JSON-serializable data — the stats
        RPC payload, the metrics JSONL line, and the flight-recorder
        snapshot all share this one format."""
        with cls._lock:
            monitors = list(cls._monitors.values())
            counters = list(cls._counters.values())
            histograms = list(cls._histograms.values())
            gauges = list(cls._gauges.values())
        return {
            "monitors": {m.name: {"count": m.count,
                                  "elapse_ms": m.elapse_ms,
                                  "average_ms": m.average_ms}
                         for m in monitors},
            "counters": {c.name: c.value for c in counters},
            "gauges": {g.name: g.value for g in gauges},
            "histograms": {h.name: h.to_dict() for h in histograms},
        }

    @classmethod
    def render(cls, format: str = "text") -> str:
        """Operator-facing dump (returned, never printed; ``display()``
        keeps the reference's print-and-return contract).

        ``format="text"``: aligned monitor/counter/gauge/histogram tables
        an operator can read off a log or a debug endpoint.
        ``format="prom"``: Prometheus text exposition (counters/gauges/
        histograms with cumulative ``_bucket{le=...}`` rows) for scrape
        endpoints and pushgateways."""
        if format == "prom":
            return cls._render_prom()
        if format != "text":
            raise ValueError(f"render: unknown format {format!r} "
                             "(want 'text' or 'prom')")
        with cls._lock:
            monitors = list(cls._monitors.values())
            counters = list(cls._counters.values())
            histograms = list(cls._histograms.values())
            gauges = list(cls._gauges.values())
        lines = ["== dashboard =="]
        if monitors:
            lines.append(f"{'section':<36} {'count':>10} {'total_ms':>12} "
                         f"{'avg_ms':>10}")
            for m in monitors:
                lines.append(f"{m.name:<36} {m.count:>10} "
                             f"{m.elapse_ms:>12.3f} {m.average_ms:>10.3f}")
        if counters:
            lines.append(f"{'counter':<36} {'value':>10}")
            for c in counters:
                lines.append(f"{c.name:<36} {c.value:>10}")
        if gauges:
            lines.append(f"{'gauge':<36} {'value':>10}")
            for g in gauges:
                lines.append(f"{g.name:<36} {g.value:>10g}")
        if histograms:
            lines.append(f"{'histogram':<36} {'count':>8} {'p50_ms':>10} "
                         f"{'p95_ms':>10} {'p99_ms':>10} {'max_ms':>10}")
            for h in histograms:
                lines.append(f"{h.name:<36} {h.count:>8} "
                             f"{h.p50 * 1e3:>10.3f} {h.p95 * 1e3:>10.3f} "
                             f"{h.p99 * 1e3:>10.3f} {h.max * 1e3:>10.3f}")
        if not (monitors or counters or gauges or histograms):
            lines.append("(no monitors or counters recorded)")
        return "\n".join(lines)

    @classmethod
    def identity(cls) -> Dict[str, str]:
        """This process's fleet identity as Prometheus labels, from the
        ``metrics_shard`` / ``metrics_role`` flags (set by ``mv.serve``,
        shard-group children and replicas at startup). Empty when
        neither is set — single-process dashboards stay label-free."""
        from multiverso_tpu import config
        labels: Dict[str, str] = {}
        try:
            shard = int(config.get_flag("metrics_shard"))
            role = str(config.get_flag("metrics_role"))
        except Exception:  # noqa: BLE001 — render before flag definition
            return labels
        if shard >= 0:
            labels["shard"] = str(shard)
        if role:
            labels["role"] = role
        return labels

    @classmethod
    def set_identity(cls, shard: Optional[int] = None,
                     role: Optional[str] = None) -> None:
        """Stamp the process's fleet identity (flag-backed, so a
        dashboard reset does not lose it)."""
        from multiverso_tpu import config
        if shard is not None:
            config.set_flag("metrics_shard", int(shard))
        if role is not None:
            config.set_flag("metrics_role", str(role))

    @classmethod
    def _render_prom(cls) -> str:
        with cls._lock:
            monitors = list(cls._monitors.values())
            counters = list(cls._counters.values())
            histograms = list(cls._histograms.values())
            gauges = list(cls._gauges.values())
        ident = cls.identity()

        def lab(shard: Optional[str], le: Optional[str] = None,
                tenant: Optional[str] = None) -> str:
            labels = dict(ident)
            if shard is not None:
                # a per-shard series names its OWN shard — it wins over
                # the process identity (a launcher holding the fleet's
                # ROUTER_SHARD<k> series has no shard identity anyway)
                labels["shard"] = shard
            if tenant is not None:
                labels["tenant"] = tenant
            parts = [f'{k}="{_prom_escape(v)}"'
                     for k, v in sorted(labels.items())]
            if le is not None:
                parts.append(f'le="{le}"')
            return "{" + ",".join(parts) + "}" if parts else ""

        lines: list = []
        typed = set()

        def head(n: str, kind: str) -> None:
            # one # TYPE line per family — shard-labeled series of one
            # family share it
            if n not in typed:
                typed.add(n)
                lines.append(f"# TYPE {n} {kind}")

        for c in counters:
            tenant, suffix = split_tenant(c.name)
            if tenant is not None:
                n = _prom_name(f"TENANT_{suffix}")
                head(n, "counter")
                lines.append(
                    f"{n}_total{lab(None, tenant=tenant)} {c.value}")
                continue
            family, shard = _split_shard(c.name)
            n = _prom_name(family)
            head(n, "counter")
            lines.append(f"{n}_total{lab(shard)} {c.value}")
        for g in gauges:
            family, shard = _split_shard(g.name)
            n = _prom_name(family)
            head(n, "gauge")
            lines.append(f"{n}{lab(shard)} {g.value:g}")
        for m in monitors:
            family, shard = _split_shard(m.name)
            n = _prom_name(family)
            head(f"{n}_seconds", "summary")
            lines.append(f"{n}_seconds_sum{lab(shard)} "
                         f"{m.elapse_ms / 1e3:.9g}")
            lines.append(f"{n}_seconds_count{lab(shard)} {m.count}")
        for h in histograms:
            family, shard = _split_shard(h.name)
            n = _prom_name(family)
            data = h.to_dict()
            head(n, "histogram")
            cum = 0
            for bound, bucket in zip(data["bounds"], data["buckets"]):
                cum += bucket
                lines.append(
                    f'{n}_bucket{lab(shard, le=f"{bound:.9g}")} {cum}')
            lines.append(f'{n}_bucket{lab(shard, le="+Inf")} '
                         f'{data["count"]}')
            lines.append(f"{n}_sum{lab(shard)} {data['sum']:.9g}")
            lines.append(f"{n}_count{lab(shard)} {data['count']}")
        return "\n".join(lines) + ("\n" if lines else "")

    @classmethod
    def display(cls) -> str:
        with cls._lock:
            lines = ["--------------Dashboard--------------------"]
            lines.extend(repr(m) for m in cls._monitors.values())
            lines.extend(repr(c) for c in cls._counters.values())
            lines.extend(repr(g) for g in cls._gauges.values())
            lines.extend(repr(h) for h in cls._histograms.values())
        # the "why is it slow" panel rides along once the sampling
        # profiler has data (rendered OUTSIDE the registry lock)
        from multiverso_tpu.obs.profiler import PROFILER
        if PROFILER.samples:
            lines.append(PROFILER.render())
        text = "\n".join(lines)
        print(text, flush=True)
        return text

    @classmethod
    def reset(cls) -> None:
        """Zero every registered object IN PLACE. Clearing the dicts
        instead would orphan cached references: a module that held on to
        ``Dashboard.counter("X")`` would keep bumping an object no longer
        in the registry while readers see a fresh zero forever."""
        with cls._lock:
            objs = (list(cls._monitors.values())
                    + list(cls._counters.values())
                    + list(cls._histograms.values())
                    + list(cls._gauges.values()))
        for obj in objs:
            obj.reset()
        RING.reset()


class _OpFields(NamedTuple):
    seq: int
    id: int
    parent: int
    stage: str
    start_ns: int
    dur_ns: int
    cpu_ns: int
    op: int
    n: int
    path: str = ""
    descriptors: int = 0
    bytes: int = 0
    shards: int = 0
    max_shard_n: int = 0
    exchange_bytes: int = 0
    dups: int = 0
    updater: str = ""
    state_rows: int = 0
    state_bytes: int = 0
    waits: int = 0
    ids_from: str = ""
    ids_ready: int = 0
    ordinal: int = 0
    worker: int = -1


_OP_DEFAULTS = tuple(_OpFields._field_defaults.get(f)
                     for f in _OpFields._fields)


class OpRecord(_OpFields):
    """One stage of one op's passage through the program. ``seq`` is the
    append order; ``id`` names a span (0 for a point) and ``parent`` the
    span that caused this one (0 = none); times are
    ``time.perf_counter_ns`` (one clock for every process of a Linux
    host); ``cpu_ns`` is the thread's own CPU time over the span
    (``time.thread_time_ns``: a first touch burns it, a wait does not)
    for the sections that ask for it, else 0; ``op`` is the request's
    ``req_id``, else its ``msg_id``; ``n`` counts rows, bytes or fused
    messages, by stage. A row launch (``TABLE_ROW_LAUNCH``) also says
    which program served it (``path``: ``pallas`` or ``xla``), the DMA
    descriptors it issues, the semaphore ``waits`` it issues for them (two
    a whole row group: ``descriptors / waits`` is the kernel's group) and
    the bytes of table rows it moves; on a table
    whose rows are sharded over chips it also carries the ``shards`` that
    launched (``n`` is then the slots of all of them), the fullest shard's
    slots (``max_shard_n``) and the bytes of table rows that crossed chips
    (``exchange_bytes``). Every other stage leaves the seven empty, but
    for the ``bytes`` of ids a section sent up (``WORKER_ROW_IDS``, the
    caller at submit; the ``TABLE_ROW_PREP`` of a routed op on a table
    sharded over chips: 0 where the op launched on ids kept from the op
    before). The
    ``TABLE_ROW_PREP`` of a host row Add says how many of its value rows
    were summed into an earlier row of the same id (``dups``; its ``n`` is
    the distinct rows that went up). The launch of an Add under a stateful
    updater names the updater (``updater``; empty under a linear one), the
    id slots whose state it read and wrote (``state_rows``) and the bytes
    of state that is (``state_bytes``, read and write). Every row launch
    says who uploaded its ids (``ids_from``: ``caller``, an in-process
    device-path op's own thread at submit, or ``dispatcher``, in the op's
    ``TABLE_ROW_PREP``) and whether they had landed when the launch began
    (``ids_ready``: the id array's ``is_ready()``, 1 or 0). The six
    ``CLIENT_*`` records of a served op's client half say which worker's
    client stamped them (``worker``: its ``worker_id``; -1 on every other
    record), in its own ring or, carried there, in its server's.

    ``_make`` also takes a row shorter than the fields, from a cut
    recorded before the last of them existed: they read their defaults."""

    __slots__ = ()

    @classmethod
    def _make(cls, row) -> "OpRecord":
        row = tuple(row)
        return tuple.__new__(cls, row + _OP_DEFAULTS[len(row):])


class OpRing:
    """Fixed ring of op records, written only while
    ``Dashboard.profile_annotations`` is on. Preallocated; an append
    takes no lock (``next`` on a C counter is one step under the GIL and
    each record lands in its own slot); the oldest record is overwritten
    and ``window`` says when that cost it part of what was asked for."""

    def __init__(self, size: int = 1 << 17) -> None:
        if size < 1 or size & (size - 1):
            raise ValueError("OpRing: size must be a power of two")
        self._mask = size - 1
        self.reset()

    def reset(self) -> None:
        self._slots: List[Optional[tuple]] = [None] * (self._mask + 1)
        self._seq = itertools.count()

    @property
    def size(self) -> int:
        """How many records the ring holds before it overwrites."""
        return self._mask + 1

    def append(self, span_id: int, parent: int, stage: str, start_ns: int,
               dur_ns: int, cpu_ns: int, op: int, n: int, path: str = "",
               descriptors: int = 0, bytes: int = 0, shards: int = 0,
               max_shard_n: int = 0, exchange_bytes: int = 0,
               dups: int = 0, updater: str = "", state_rows: int = 0,
               state_bytes: int = 0, waits: int = 0, ids_from: str = "",
               ids_ready: int = 0, ordinal: int = 0,
               worker: int = -1) -> None:
        seq = next(self._seq)
        self._slots[seq & self._mask] = (seq, span_id, parent, stage,
                                         start_ns, dur_ns, cpu_ns, op, n,
                                         path, descriptors, bytes, shards,
                                         max_shard_n, exchange_bytes, dups,
                                         updater, state_rows, state_bytes,
                                         waits, ids_from, ids_ready,
                                         ordinal, worker)

    def point(self, stage: str, op: int) -> None:
        """A point of an op's passage (``hop``), caused by the span the
        calling thread is in."""
        self.append(0, current_span(), stage, time.perf_counter_ns(), 0, 0,
                    op, 0)

    def _kept(self) -> List[tuple]:
        return sorted(r for r in list(self._slots) if r is not None)

    @property
    def overwritten(self) -> int:
        """Records lost to the ring's size since the last reset."""
        kept = self._kept()
        return kept[-1][0] + 1 - len(kept) if kept else 0

    def window(self, t0: float, t1: float) -> Tuple[List[OpRecord], bool]:
        """The records that lie wholly between two ``time.perf_counter``
        instants (seconds), in append order, and whether the ring
        overwrote a record that may have lain there: records are
        appended as their spans end, so everything lost ended no later
        than the oldest record kept."""
        lo, hi = int(t0 * 1e9), int(t1 * 1e9)
        kept = self._kept()
        lost = bool(kept) and kept[0][0] > 0 \
            and kept[0][4] + kept[0][5] > lo
        return ([OpRecord._make(r) for r in kept
                 if r[4] >= lo and r[4] + r[5] <= hi], lost)


RING = OpRing()
_span_ids = itertools.count(1)
_op_tls = threading.local()  # .span / .op: what the thread is inside


def current_span() -> int:
    """The id of the op-trace section the calling thread is in (0: none)."""
    return getattr(_op_tls, "span", 0)


class _Section:
    """One timed same-thread section. Always: its duration goes to the
    ``feeds`` it was given (``monitor``: the Monitor and Histogram of its
    name). While ``Dashboard.profile_annotations`` is on it is also a
    ``TraceAnnotation`` of its name and one span record in ``RING``,
    child of the section the thread was in; sections and hops inside it
    inherit its ``op`` unless they name their own. ``n`` and ``op`` may
    be set until the section ends (an ``op`` set late names this record
    alone: what ran inside has inherited the one before); ``id`` is 0
    while the switch is off.

    ``cpu`` asks for the thread's CPU time too. Only the few sections
    whose question it answers take it (is a busy dispatcher computing or
    waiting; is a buffer's fill a first touch or a wait): under gVisor,
    where the chip's host runs, the thread CPU clock is a 5.8 us call
    into the sandbox's kernel and ticks in 10 ms, so it is a sampling
    estimate that means something over sums of a second or more."""

    __slots__ = ("_name", "_feeds", "op", "n", "id", "start_ns", "dur_ns",
                 "_parent", "_outer_op", "_cpu", "_cpu0", "_ann",
                 "path", "descriptors", "bytes", "shards", "max_shard_n",
                 "exchange_bytes", "dups", "updater", "state_rows",
                 "state_bytes", "waits", "ids_from", "ids_ready", "ordinal")

    def __init__(self, name: str, feeds: Optional[tuple], op: int, n: int,
                 cpu: bool) -> None:
        self._name, self._feeds, self.op, self.n = name, feeds, op, n
        self._cpu = cpu
        self.id = 0
        self.path, self.descriptors, self.bytes, self.waits = "", 0, 0, 0
        self.shards = self.max_shard_n = self.exchange_bytes = 0
        self.dups = 0
        self.updater, self.state_rows, self.state_bytes = "", 0, 0
        self.ids_from, self.ids_ready = "", 0
        self.ordinal = 0

    def __enter__(self) -> "_Section":
        if Dashboard.profile_annotations:
            tls = _op_tls
            self._parent = getattr(tls, "span", 0)
            self._outer_op = getattr(tls, "op", 0)
            self.id = tls.span = next(_span_ids)
            if self.op:
                tls.op = self.op
            else:
                self.op = self._outer_op
            self._ann = None
            if _TraceAnnotation is not None:
                self._ann = _TraceAnnotation(self._name)
                self._ann.__enter__()
            if self._cpu:
                self._cpu0 = time.thread_time_ns()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_ns = time.perf_counter_ns() - self.start_ns
        if self.id:
            cpu = time.thread_time_ns() - self._cpu0 if self._cpu else 0
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            _op_tls.span, _op_tls.op = self._parent, self._outer_op
            RING.append(self.id, self._parent, self._name, self.start_ns,
                        self.dur_ns, cpu, self.op, self.n, self.path,
                        self.descriptors, self.bytes, self.shards,
                        self.max_shard_n, self.exchange_bytes, self.dups,
                        self.updater, self.state_rows, self.state_bytes,
                        self.waits, self.ids_from, self.ids_ready,
                        self.ordinal)
        if self._feeds is not None:
            seconds = self.dur_ns * 1e-9
            for unit in self._feeds:
                unit.observe(seconds)
        return False


class _Off:
    """What ``span`` hands out while the switch is off: nothing is timed
    and what a section would carry goes nowhere."""

    __slots__ = ("n", "op", "path", "descriptors", "bytes", "shards",
                 "max_shard_n", "exchange_bytes", "dups", "updater",
                 "state_rows", "state_bytes", "waits", "ids_from",
                 "ids_ready", "ordinal")
    id = 0

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()
_monitor_feeds: Dict[str, tuple] = {}


def monitor(name: str, op: int = 0, n: int = 0,
            cpu: bool = False) -> _Section:
    """``MONITOR_BEGIN(name) ... MONITOR_END(name)`` as a context manager.
    The duration feeds BOTH the monitor (count/total/average) and the
    same-named histogram (p50/p95/p99) — every timed section gets a
    distribution for free — and, while profiling is on, the op trace
    (see :class:`_Section`). The two are resolved once per name: the
    registry's lock stays off the hot path (``Dashboard.reset`` zeroes
    objects in place, so the references stay live)."""
    feeds = _monitor_feeds.get(name)
    if feeds is None:
        feeds = _monitor_feeds[name] = (Dashboard.get(name),
                                        Dashboard.histogram(name))
    return _Section(name, feeds, op, n, cpu)


def span(name: str, op: int = 0, n: int = 0,
         feeds: Optional[tuple] = None, cpu: bool = False):
    """A section that exists only in the op trace: one predicate while
    ``Dashboard.profile_annotations`` is off. ``feeds`` (objects with
    ``observe(seconds)``) makes its duration an always-on series under
    another name."""
    if feeds is None and not Dashboard.profile_annotations:
        return _OFF
    return _Section(name, feeds, op, n, cpu)


def count(name: str, n: int = 1) -> None:
    """Bump a named event counter (``Dashboard.counter(name).add(n)``)."""
    Dashboard.counter(name).add(n)


def observe(name: str, seconds: float) -> None:
    """Record one sample into a named histogram."""
    Dashboard.histogram(name).observe(seconds)


def gauge_set(name: str, value: float) -> None:
    """Set a named gauge (last writer wins)."""
    Dashboard.gauge(name).set(value)


def gauge_add(name: str, delta: float = 1.0) -> None:
    """Atomically add to a named gauge."""
    Dashboard.gauge(name).add(delta)


class Timer:
    """Chrono stopwatch in ms (reference: ``util/timer.h``)."""

    def __init__(self) -> None:
        self.start()

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def elapse_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3
