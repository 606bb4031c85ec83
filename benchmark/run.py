#!/usr/bin/env python
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that holds the cell's chips: it loads the cell's configuration
and traffic files, hands them to the driver the traffic file names, lets the
driver set up (program start, data from --seed, warm-up of the cell's own
shapes, the checks that come before the window), measures one window, lets
the driver check what the window produced, and prints one JSON object as its
last line. With --trace 1 the window is short and runs under JAX's profiler;
the last line then carries the cell's per-layer metrics, each read by
benchmark/layers/<metric>.py, in place of the end-to-end ones.

It knows no cell, configuration, driver or metric by name: all of them are
files found through BENCHMARK.json. Without a TPU it exits non-zero before
any phase. --rehearse shrinks every size for the sandbox (CPU, interpreted
kernels) and prints counts only, under no metric's name.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common  # noqa: E402


class Run:
    """What a driver and the per-layer readers are handed."""

    def __init__(self, cell, config, traffic, seed, rehearse, tracing):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.rehearse, self.tracing = seed, rehearse, tracing
        self.chips = int(cell["chips"])
        self.spans = common.Spans(annotate=tracing)
        self.compare = common.Comparisons()
        self.attempted = self.failed = 0
        self.result = {}         # the driver's counts and samples
        self.monitors = {}       # program monitors over the window
        self.compiles_in_window = []
        self.window = (0.0, 0.0)
        self.trace = None        # trace_reduce.Reduction in a traced run
        self.peaks = None
        self.device = {}
        self._phases, self._phase_t = [], T_START

    def phase(self, name):
        """Name the stretch of set-up that just ended; printed with the
        run's other set-up facts."""
        now = time.perf_counter()
        self._phases.append([name, round(now - self._phase_t, 3)])
        self._phase_t = now


def _named(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    sys.exit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def _in_cell(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


def _monitor_deltas(before, after):
    out = {}
    for name, now in after.items():
        was = before.get(name, {"count": 0, "elapse_ms": 0.0})
        count = now["count"] - was["count"]
        if count > 0:
            out[name] = {"count": count,
                         "elapse_ms": now["elapse_ms"] - was["elapse_ms"]}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on the CPU; counts only, no metric")
    args = parser.parse_args(argv)

    bench = common.load_json("BENCHMARK.json")
    cell = _named(bench["workloads"], args.workload, "workload")
    config_entry = _named(bench["configs"], cell["config"], "configuration")
    config = common.load_json(config_entry["file"])
    traffic = common.load_json("benchmark", "traffic",
                               cell["traffic"] + ".json")
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds
    tracing = bool(args.trace)
    if tracing:
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
    if args.rehearse:
        # the sandbox: CPU, four virtual devices for a four-chip cell
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        seconds = min(seconds, 2.0)

    import jax

    import multiverso_tpu as mv

    cache_dir = mv.configure_compile_cache()
    if cache_dir:
        # small programs too: a run after the first compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    run = Run(cell, config, traffic, args.seed, args.rehearse, tracing)
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < run.chips):
        sys.exit(f"benchmark: cell {cell['name']} needs {run.chips} TPU "
                 f"chip(s); JAX found {len(devices)} "
                 f"{devices[0].platform} device(s)")
    run.device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse:
        try:
            run.peaks = common.peaks_for(run.device["kind"])
        except KeyError as e:
            sys.exit(f"benchmark: {e.args[0]}")
    print(json.dumps({"cell": cell["name"], "seed": args.seed,
                      "seconds": seconds, "trace": args.trace,
                      "rehearse": args.rehearse, "device": run.device,
                      "compile_cache_dir": cache_dir}), flush=True)

    from multiverso_tpu.dashboard import Dashboard

    run.phase("imports and device")
    clock = common.CompileClock()
    driver = common.load_module("drivers", traffic["driver"]).Driver(run)
    trace_dir = None
    try:
        driver.setup()
        if tracing:
            # the program's monitors become host spans on the trace's clock
            Dashboard.profile_annotations = True
            trace_dir = os.path.join(common.BENCH_DIR, ".trace",
                                     cell["name"])
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            # the reduction reads device operations and TraceAnnotations; a
            # Python-level event for every call would only slow the host
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        before = Dashboard.snapshot()["monitors"]
        t0 = time.perf_counter()
        setup_s = t0 - T_START
        with run.spans.span("bench.window"):
            t1 = driver.window(seconds)
        run.window = (t0, t1)
        run.monitors = _monitor_deltas(before,
                                       Dashboard.snapshot()["monitors"])
        if tracing:
            jax.profiler.stop_trace()
        run.compiles_in_window = clock.between(t0, t1)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:run.chips])
        driver.finish()
    finally:
        driver.close()

    device = dict(run.device, memory_peak_bytes=int(peak))
    line = {"correct": run.compare.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": {}, "device": device}
    print(json.dumps({"window_s": t1 - t0, "setup_s": setup_s,
                      "compile_s": clock.total(),
                      "compiles_in_window": len(run.compiles_in_window),
                      "setup_phases": run._phases,
                      "monitors": run.monitors}), flush=True)
    if args.rehearse:
        # counts only: no name of the benchmark's metrics
        line["counts"] = {k: v for k, v in run.result.items()
                          if isinstance(v, int)}
        print(json.dumps(line), flush=True)
        return 0

    if tracing:
        from benchmark import trace_reduce
        run.trace = trace_reduce.reduce_dir(trace_dir, run.chips)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
        for metric in bench["per_layer"]:
            if not _in_cell(metric, cell["name"]):
                continue
            value = common.load_module("layers", metric["name"]).read(run)
            if value is not None:
                line["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    else:
        values = dict(driver.end_to_end(), setup_s=setup_s)
        for metric in bench["end_to_end"]:
            if _in_cell(metric, cell["name"]):
                line["metrics"][metric["name"]] = {
                    "value": values[metric["name"]], "unit": metric["unit"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
