#!/usr/bin/env python
"""The runtime's floor under an in-process op, on the chip, by hand:

    python benchmark/tests/bare_loop.py [--seconds 4] [--add-mb 500] [--get-mb 160]

Two bare `jax.jit` programs, one of about the row scatter-add's device time
and one of about the gather's, called and `block_until_ready`-ed alternately
in a closed loop: no table, no dispatcher, no second thread. The window runs
under `run.py`'s profiler options with the op trace on, each jit call inside a
section of the name the join looks for (`TABLE_ROW_LAUNCH`) and each op inside
a `bench.op.*` span, and is read by `op_timeline.timeline`, the reader of the
cells: its `launch_to_device` and `ready_tail` are what the runtime charges
any caller on this host, to lay beside a cell's. The `add` program updates its
buffer in place (donated), as a scatter-add does; the `get` program makes a
new array, as a gather does. Exits non-zero without a TPU."""

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, op_timeline, op_trace, trace_reduce  # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--add-mb", type=int, default=500,
                        help="float32 megabytes the add program rewrites")
    parser.add_argument("--get-mb", type=int, default=160,
                        help="float32 megabytes the get program reads")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from multiverso_tpu.dashboard import RING, Dashboard, span

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"bare_loop: needs a TPU; JAX found {device.platform}")
    add = jax.jit(lambda x: x + 1.0, donate_argnums=0)
    get = jax.jit(lambda x: x * 0.5)
    table = jnp.zeros((args.add_mb << 18,), jnp.float32)
    rows = jnp.ones((args.get_mb << 18,), jnp.float32)
    for _ in range(3):
        table = add(table)
        get(rows).block_until_ready()
    table.block_until_ready()

    spans = common.Spans(annotate=True)
    trace_dir = os.path.join(common.BENCH_DIR, ".trace", "bare-loop")
    shutil.rmtree(trace_dir, ignore_errors=True)
    Dashboard.profile_annotations = True
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    with spans.span("bench.window"):
        while time.perf_counter() < t0 + args.seconds:
            with spans.span("bench.op.add"):
                with span(op_timeline.LAUNCH):
                    table = add(table)
                table.block_until_ready()
            with spans.span("bench.op.get"):
                with span(op_timeline.LAUNCH):
                    out = get(rows)
                out.block_until_ready()
        t1 = time.perf_counter()
    jax.profiler.stop_trace()
    Dashboard.profile_annotations = False

    records, overwrote = RING.window(t0, t1)
    assert not overwrote
    raw = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    samples = {name: [(round(a * 1e9), round(b * 1e9)) for a, b in pairs]
               for name, pairs in spans.samples.items()
               if name.startswith("bench.op.")}
    found = op_timeline.timeline(
        raw, op_trace.Trace(records, int(t0 * 1e9), int(t1 * 1e9)), samples)
    print(json.dumps({"bare_loop": dict(
        found, device={"platform": device.platform,
                       "kind": device.device_kind},
        add_mb=args.add_mb, get_mb=args.get_mb, window_s=t1 - t0)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
