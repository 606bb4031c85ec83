"""What every driver and reader of the benchmark shares: file lookup by name,
the seeded Zipf row draw, the compile clock, host spans and percentiles.

Nothing here knows a cell, a configuration or a metric by name."""

import importlib.util
import json
import os
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py as a module; names may hold '.' and '-'."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"benchmark/{kind}/{name}.py is not there")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peaks_for(device_kind):
    """The published peaks of a device kind; an unknown kind is an error,
    never a default."""
    table = load_json("benchmark", "peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"benchmark/peaks.json")
    return table[device_kind]


def mix_seed(seed, *salts):
    """A 63-bit numpy seed from --seed (any whole number) and salts."""
    return np.random.SeedSequence([int(seed) & (2**64 - 1), *salts])


class ZipfRows:
    """Row popularity Zipf(exponent) over a seeded permutation of the row
    ids (the key draw of bench.py's TrafficGen): rank r has weight
    r**-exponent and lands on row perm[r], so hot rows are scattered."""

    def __init__(self, rows, exponent, seed):
        ranks = np.arange(1, rows + 1, dtype=np.float64)
        pmf = ranks ** -float(exponent)
        self._cdf = np.cumsum(pmf / pmf.sum())
        self._perm = np.random.default_rng(
            mix_seed(seed, 0x7065726D)).permutation(rows).astype(np.int32)
        self.rows = rows

    def distinct(self, rng, n):
        """n distinct rows in draw order: draws with replacement, repeats
        dropped, which is sampling without replacement by weight (a worker
        combines its own duplicates before it sends)."""
        seen = np.empty(0, np.int64)
        while len(seen) < n:
            more = np.searchsorted(self._cdf, rng.random(2 * n + 64))
            both = np.concatenate([seen, np.minimum(more, self.rows - 1)])
            _, first = np.unique(both, return_index=True)
            seen = both[np.sort(first)]
        return self._perm[seen[:n]]


class CompileClock:
    """Backend compiles as JAX itself reports them (chip_smoke.py's clock),
    each with the host time at which it ended."""

    def __init__(self):
        import jax.monitoring
        self.events = []  # (ended_at perf_counter, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.events.append((time.perf_counter(), duration))

    def between(self, t0, t1):
        return [d for t, d in self.events if t0 <= t <= t1]

    def total(self):
        return sum(d for _, d in self.events)


class Spans:
    """The benchmark's own spans around its calls into the program, kept in
    memory; in a traced run each is also a TraceAnnotation, so it sits on
    the profiler's clock beside the device operations."""

    def __init__(self, annotate):
        self.samples = {}
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    def span(self, name):
        return _Span(self, name)


class _Span:
    __slots__ = ("_spans", "_name", "_t0", "_ann")

    def __init__(self, spans, name):
        self._spans, self._name = spans, name

    def __enter__(self):
        ann = self._spans._annotation
        self._ann = ann(self._name) if ann else None
        if self._ann:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann:
            self._ann.__exit__(*exc)
        self._spans.samples.setdefault(self._name, []).append(
            (self._t0, t1))
        return False


def percentile(samples, q):
    """The q-th percentile (0-100) of samples, linear between ranks."""
    if len(samples) == 0:
        raise ValueError("no samples")
    return float(np.percentile(np.asarray(samples, np.float64), q))


class Comparisons:
    """Every number `correct` rests on, printed beside its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        ok = bool(value <= limit)
        self.rows.append({"compared": name, "value": value, "limit": limit,
                          "ok": ok})
        print(json.dumps(self.rows[-1]), flush=True)
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)
