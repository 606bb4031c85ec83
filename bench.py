#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line.

``python bench.py`` with no mode flag is ONE process that holds the chip and
runs the in-process device legs only (device word2vec, PS word2vec, matrix
row Add/Get, ResNet ASGD). It fails without a TPU, never swaps a kernel for
a fallback, starts no process, and exits non-zero when a leg raises. Legs
that start children or time a CPU harness run under their own mode flags
(``--wire-bench``, ``--apply-bench``, ``--shards N``, ``--read-bench``, ...,
see ``__main__``): their numbers are counts and host-side timings, never
device metrics.

Headline: word2vec skip-gram+NS training throughput (words/sec/chip) on the
HBM-resident block-mode path — the BASELINE.md north-star metric
("WordEmbedding words/sec/chip"). The reference published NO words/sec
figure (BASELINE.md: its only form is the live "Words/thread/second" log
line), so the headline value is reported absolute. ``vs_baseline`` is the
one quantified target BASELINE.json does state — MatrixTable row-Add p50
latency < 50 µs — expressed as target/measured (>1 = beating it); see
``vs_baseline_note`` in the output.

Extra fields: MatrixTable row Add/Get device-path timings at the reference
perf-harness shape (1M×50 fp32, ``Test/test_matrix_perf.cpp:32-45``) plus
dense whole-table bandwidth.

Timing note: every measurement is *fetch-forced* — a 1-element device→host
read after the op chain, which cannot return before everything it depends
on has run. Whether ``jax.block_until_ready`` alone would do on the current
machine is not measured.
"""

import functools
import json
import threading
import time

import numpy as np


def _fetch(x):
    """Force full completion of everything `x` depends on."""
    return np.asarray(x)


def load_metrics(path):
    """Ingest a MetricsLogger JSONL stream (the ``metrics_path`` flag):
    one dashboard snapshot dict per line — monitors, counters, gauges,
    histograms as bucket arrays (rebuild with ``obs.metrics.Histogram.
    from_dict`` for quantiles). This is the bench-side half of the format
    contract ``make metrics-smoke`` asserts."""
    from multiverso_tpu.obs.logger import load_metrics as _load
    return _load(path)


def _env_fingerprint():
    """Environment identity stamped into every bench JSON (the ``env``
    key): results measured in different environments are not comparable
    — the r05↔r06 incomparability used to live only in a prose note and
    silently produced bogus regression verdicts. ``--compare`` warns (or
    refuses under ``--require-same-env``) when fingerprints differ."""
    import os
    import socket
    import jax
    devices = jax.devices()
    return {"hostname": socket.gethostname(),
            "nproc": os.cpu_count() or 0,
            "jax_backend": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


# --attribute mode: set from __main__, consumed by the leg wrappers
_ATTRIBUTE = False


def _collect_leg_attribution(label, tables):
    """``--attribute``: decompose the traces the leg just left in the
    local store into a critical-path table (obs/critpath.py) plus its
    per-tenant chargeback split (obs/chargeback.py), then clear the
    store so the next leg attributes only its own traffic."""
    try:
        from multiverso_tpu.obs.chargeback import charge
        from multiverso_tpu.obs.collector import TraceCollector
        from multiverso_tpu.obs.critpath import attribute
        from multiverso_tpu.obs.trace import TRACES
        collector = TraceCollector([], include_local=True)
        collector.collect()
        spans = collector.stitch()
        TRACES.reset()
        report = attribute(spans)
        if report.rows:
            tables[label] = report.to_dict()
            chargeback = charge(spans)
            if chargeback.rows:
                tables[label]["chargeback"] = chargeback.to_dict()
    except Exception as exc:  # attribution must never sink the bench
        tables[label] = {"error": repr(exc)[:200]}


def bench_profile_overhead(rows=100_000, cols=128, passes=20):
    """Continuous-profiler overhead A/B on the in-process dense pass:
    the same donated whole-table pass timed with the sampler off, then
    with a continuous ``SamplingProfiler`` running at the default
    ``profile_hz`` and feeding PROFILE_* gauges. The acceptance bar is
    ``profile_overhead_pct`` <= 3 (min-of-3 both legs, so shared-host
    noise has to hit every rep to fake an overhead)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.obs.profiler import SamplingProfiler

    dense = jax.jit(lambda d: d + 1.0, donate_argnums=(0,))
    d = dense(jnp.zeros((rows, cols), jnp.float32))
    _fetch(d[0, :1])

    def leg():
        nonlocal d
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(passes):
                d = dense(d)
            _fetch(d[0, :1])
            best = min(best, time.perf_counter() - t0)
        return best

    base = leg()
    profiler = SamplingProfiler(emit_metrics=True)
    profiler.start()
    try:
        profiled = leg()
    finally:
        profiler.stop()
    overhead_pct = (profiled - base) / base * 100.0 if base > 0 else 0.0
    return {
        "profile_overhead_pct": round(overhead_pct, 2),
        "profile_dense_base_seconds": round(base, 6),
        "profile_dense_profiled_seconds": round(profiled, 6),
        "profile_samples": profiler.samples,
    }


def bench_word2vec(vocab=100_000, dim=128, block_tokens=8192, n_blocks=40):
    import jax

    from chip_smoke import zipf_setup
    from multiverso_tpu.models.word2vec import (Word2VecConfig, init_params,
                                                make_corpus_train_step)

    d, draw = zipf_setup(vocab, seed=0)
    # neg_sharing=8: the TPU-native benchmark recipe — one negative set per
    # 8 adjacent centers cuts negative row traffic 8x (row-granular HBM ops
    # sit at a ~13ns/row descriptor floor) and shapes the negative
    # contraction for the MXU; convergence at this setting is covered by
    # tests/test_word2vec.py::test_training_separates_clusters_neg_sharing
    config = Word2VecConfig(vocab_size=vocab, dim=dim, window=5, negatives=5,
                            block_tokens=block_tokens, sample=0.0,
                            neg_sharing=8)
    params = init_params(config, mesh=None)
    # scan-mode: ONE dispatch per n_blocks — measures the chip, not the
    # host's per-dispatch cost
    step = make_corpus_train_step(config, d)

    # zipf-ish synthetic corpus, sampled via inverse CDF
    stack_dev = jax.device_put(
        draw(n_blocks * block_tokens).reshape(n_blocks, block_tokens))

    key = jax.random.PRNGKey(0)

    # slope over pass count: (T(k2 passes) − T(k1 passes)) / Δpasses removes
    # the fixed cost of the closing fetch from the throughput figure
    def run_passes(k):
        nonlocal params, key
        best = float("inf")
        loss = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(k):
                key, sub = jax.random.split(key)
                params, loss = step(params, sub, stack_dev, config.lr)
            _fetch(params["w_in"][0, :1])
            best = min(best, time.perf_counter() - t0)
        return best, loss

    run_passes(1)  # compile + warm
    k1, k2 = 1, 4
    t1, _ = run_passes(k1)
    t2, loss = run_passes(k2)
    per_pass = (t2 - t1) / (k2 - k1)
    if per_pass <= 0:
        # noisy measurement (t2 <= t1): fall back to the k2 average rather
        # than report an absurd slope-derived figure
        per_pass = t2 / k2
    words = n_blocks * block_tokens
    # loss is a few passes over a 327k-token synthetic corpus — barely off
    # init (ln 2 ≈ 0.6931); convergence is covered by tests/test_word2vec.py.
    # A non-finite loss means the run diverged: refuse to report throughput.
    loss = float(loss)
    final_w = _fetch(params["w_in"][:2, :2])
    if not (np.isfinite(loss) and np.isfinite(final_w).all()):
        raise RuntimeError(
            f"word2vec bench diverged (loss={loss}); not reporting throughput")
    return words / per_pass, loss


def bench_ps_word2vec(vocab=100_000, dim=128, block_tokens=8192, n_blocks=4,
                      group=64, batch_pairs=32768):
    """End-to-end parameter-server words/sec: the full product path —
    candidate-row pulls through the dispatcher, compact-space scan training,
    delta pushes through the updater (the reference's only benchmarked
    configuration: WordEmbedding skip-gram on PS tables).

    ``group`` coalesces that many 8192-token blocks per submission — the
    production ``PSTrainer.train(group=...)`` recipe: per-submission fixed
    costs (candidate shaping, the packed upload, the fused dispatch;
    their size is not measured on the current machine) amortize group-fold
    while the kernel still chunks internally at batch_pairs granularity, so
    the per-row update schedule matches ungrouped feeding.

    Timing is wall-clock over the PIPELINED submit/finish loop (the
    reference's benchmarked configuration ran its block pipeline,
    distributed_wordembedding.cpp:202-223), which is honest by
    construction: block i+1's candidate pull reads the table buffers block
    i's push wrote, so the dependency chain threads through EVERY block —
    one dependent fetch of the final table state forces the entire
    pipeline (per-block stats fetches would put a blocking device→host
    round trip between submissions and drain the pipeline being timed).
    Compile time is excluded by warming every block (all trace buckets)
    before timing; the figure is the best-of-reps average over the
    steady-state submissions.
    """
    import multiverso_tpu as mv
    from chip_smoke import zipf_setup
    from multiverso_tpu.models.word2vec import PSTrainer, Word2VecConfig

    d, draw = zipf_setup(vocab, seed=0)
    # neg_sharing=8 matches the device-path bench recipe (see
    # bench_word2vec): at group>=16 the fused-kernel share of block time
    # dominates the amortized dispatch, and shared negatives cut its
    # gather/scatter traffic measurably (+33% at group=16 measured);
    # PS-path convergence at this setting is covered by
    # tests/test_word2vec.py::test_ps_trainer_grouped_pipelined_learns[8]
    # group=64 x batch_pairs=32768 (scan chunk 8192, matching the device
    # path's step granularity): chosen from a PR-5 sweep (group 16/32/64
    # at bp=8192: 2.05/2.45/2.62 M words/s; 64 at bp=32768: 2.69M — chunk
    # 2048 -> 8192 closes the per-step overhead gap vs the device bench,
    # which also steps 8192 tokens at a time); not measured on the
    # current machine
    config = Word2VecConfig(vocab_size=vocab, dim=dim, window=5, negatives=5,
                            batch_pairs=batch_pairs, sample=0.0,
                            neg_sharing=8)

    blocks = [draw(block_tokens * group) for _ in range(n_blocks)]

    mv.init([])
    try:
        trainer = PSTrainer(config, d)
        for b in blocks:  # compile + warm every block's pow2 trace buckets
            trainer.train_block(b)

        def run(k):
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                pend = None
                for i in range(k):
                    nxt = trainer.submit_block(blocks[i % n_blocks])
                    if pend is not None:
                        trainer.finish_block(pend, fetch_stats=False)
                    pend = nxt
                if pend is not None:
                    trainer.finish_block(pend, fetch_stats=False)
                # single dependent fetch: forces every queued pull/train/
                # push in the run (see the docstring's honesty note)
                _fetch(trainer.input_table.get_device()[0, :1])
                best = min(best, time.perf_counter() - t0)
            return best
        # every trace bucket is warmed above, so there is no per-run fixed
        # cost to subtract: best-of-reps average over the steady-state
        # submissions is the honest figure (a 2-point slope doubles the
        # run-to-run noise instead of removing anything)
        k2 = max(16 // group, 8)
        per_block = run(k2) / (k2 * group)
        stats = trainer.last_block_stats
        # dashboard snapshot alongside the throughput figure: the request
        # path's latency DISTRIBUTION (obs/ telemetry — the monitor
        # sections double as log-bucketed histograms), so a p99
        # regression is visible even when the mean throughput holds
        from multiverso_tpu.dashboard import Dashboard
        add_hist = Dashboard.histogram("SERVER_PROCESS_ADD_MSG")
        get_hist = Dashboard.histogram("SERVER_PROCESS_GET_MSG")
        return {
            "ps_words_per_sec": round(block_tokens / per_block, 1),
            "ps_block_tokens": block_tokens,
            "ps_block_group": group,
            "ps_rows_pulled_per_submission": (stats["in_rows"]
                                              + stats["out_rows"]),
            "ps_add_p50_us": round(add_hist.p50 * 1e6, 1),
            "ps_add_p95_us": round(add_hist.p95 * 1e6, 1),
            "ps_add_p99_us": round(add_hist.p99 * 1e6, 1),
            "ps_get_p99_us": round(get_hist.p99 * 1e6, 1),
            "ps_requests_observed": add_hist.count + get_hist.count,
        }
    finally:
        mv.shutdown()


def bench_matrix_table(rows=1_000_000, cols=50, batch_rows=1024):
    """Device-path row Add/Get on the reference perf-harness table
    (1M×50 fp32, physically 128-lane padded like ``MatrixServer``).

    Add = the Pallas row-DMA scatter (the production linear-updater path on
    TPU, ~8× XLA's scatter); Get = XLA dynamic gather (faster than per-row
    DMA). Timing = scan-length slope (T(k2)−T(k1))/(k2−k1) inside single
    dispatches with per-step-varying ids — immune to the closing fetch's
    fixed cost, CSE, and async-dispatch underreporting.
    """
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.parallel.mesh import pad_to_multiple
    padded_cols = pad_to_multiple(cols, 128)
    # the production kernel, compiled for the device that holds the table;
    # no other backend may stand in for it
    add_op = functools.partial(
        pallas_rows.scatter_add_rows,
        interpret=pallas_rows.interpret_for(jax.devices()[0].platform))

    rng = np.random.default_rng(0)
    base = jax.device_put(
        rng.choice(rows, batch_rows, replace=False).astype(np.int32))
    vals = jax.device_put(np.ones((batch_rows, padded_cols), np.float32))

    def make_add(iters):
        @jax.jit
        def f(d, base, vals):
            def body(tab, i):
                ids = (base + i * 7919) % rows
                return add_op(tab, ids, vals), 0.0
            tab, _ = lax.scan(body, d, jnp.arange(iters))
            return tab[0, :1]
        return f

    def make_get(iters):
        @jax.jit
        def f(d, base):
            def body(acc, i):
                ids = (base + i * 7919) % rows
                return acc + d[ids].sum(), 0.0
            acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(iters))
            return acc
        return f

    def slope(makef, args, k1=100, k2=1100):
        f1, f2 = makef(k1), makef(k2)
        _fetch(f1(*args))
        _fetch(f2(*args))
        def timed(f):
            t0 = time.perf_counter()
            _fetch(f(*args))
            return time.perf_counter() - t0
        # interleaved f1/f2 reps; per-point min is sound — noise only
        # ever adds time
        b1 = b2 = float("inf")
        for _ in range(18):
            b1 = min(b1, timed(f1))
            b2 = min(b2, timed(f2))
        per_op = (b2 - b1) / (k2 - k1)
        # timer noise on fast backends can invert the two points; fall back
        # to the k2 average rather than report an absurd slope figure
        return per_op if per_op > 0 else b2 / k2

    data = jnp.zeros((rows, padded_cols), jnp.float32)
    # k2-k1 sets the signal the slope measures: 3000 ops of device work
    # must dwarf the jitter of the two closing fetches (sized at PR 5 for
    # ~27us/op; not re-derived on the current machine)
    k1, k2 = 200, 3200
    add_per_op = slope(make_add, (data, base, vals), k1, k2)
    get_per_op = slope(make_get, (data, base), k1, k2)

    # dense whole-table pass (the reference's get-all path): incremental
    # cost of 10 extra donated passes over one fetch
    dense = jax.jit(lambda d: d + 1.0, donate_argnums=(0,))
    d2 = dense(jnp.zeros((rows, padded_cols), jnp.float32))
    _fetch(d2[0, :1])
    def dense_time(extra):
        nonlocal d2
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(extra):
                d2 = dense(d2)
            _fetch(d2[0, :1])
            best = min(best, time.perf_counter() - t0)
        return best
    n_extra = 10
    # per-point minima over interleaved samples: each min independently
    # converges to the true time (noise only adds), so the difference is
    # burst-robust — unlike per-pair increments, where a burst inflating
    # the baseline point yields a tiny positive increment and an absurd
    # multi-thousand-GB/s figure
    tns, t0s = [], []
    for _ in range(3):
        tns.append(dense_time(n_extra))
        t0s.append(dense_time(0))
    inc = min(tns) - min(t0s)
    # fallback (sustained load made the baseline dearer than the passes):
    # charge the full n_extra run — an upper bound on per-pass cost
    dense_per_pass = inc / n_extra if inc > 0 else min(tns) / n_extra
    dense_bytes = rows * padded_cols * 4 * 2  # read + write

    batch_bytes = batch_rows * cols * 4
    return {
        "matrix_add_p50_us": round(add_per_op * 1e6, 1),
        "matrix_get_p50_us": round(get_per_op * 1e6, 1),
        "matrix_add_gbps": round(batch_bytes / add_per_op / 1e9, 2),
        "matrix_get_gbps": round(batch_bytes / get_per_op / 1e9, 2),
        "matrix_dense_gbps": round(dense_bytes / max(dense_per_pass, 1e-9) / 1e9, 1),
    }


def bench_wire_compression(rows=1024, cols=128, nonzero_rows=0.1):
    """Bytes saved by SparseFilter on a host wire hop at reference-like
    sparsity (the reference compressed exactly such row-delta payloads,
    ``src/table/sparse_matrix_table.cpp:147-153``): a row-subset delta where
    10% of rows are dense and the rest untouched."""
    from multiverso_tpu.runtime import wire

    rng = np.random.default_rng(0)
    delta = np.zeros((rows, cols), np.float32)
    hot = rng.choice(rows, int(rows * nonzero_rows), replace=False)
    delta[hot] = rng.standard_normal((len(hot), cols)).astype(np.float32)
    blobs = wire.encode(delta, compress=True)
    compressed = sum(np.asarray(b).nbytes for b in blobs)
    return round(delta.nbytes / compressed, 2)


def bench_wire(n_rtt=1500, bulk_frames=256, bulk_kb=256, n_adds=2000,
               producers=4, window=64):
    """Wire micro-bench — the syscall/copy overhead the zero-copy
    coalescing drain loop (runtime/net.py) attacks, with coalescing on
    (default flags) vs the legacy per-frame sendall posture
    (wire_coalesce_frames=0) on the SAME workloads:

    - raw transport RTT (256-byte frame ping-pong, no dispatcher) and
      one-way bulk bandwidth (256 KiB frames — where the legacy
      ``tobytes`` copy per frame is pure loss);
    - end-to-end KV-table Adds over a served endpoint: sync p50, plus
      ``producers`` concurrent worker threads pushing windowed async
      Adds through ONE client — the burst shape whose frames coalesce
      per syscall (frames/bytes-per-syscall reported from the live
      send-path counters)."""
    import threading

    import multiverso_tpu as mv
    from multiverso_tpu.config import FLAGS
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.runtime.message import Message, MsgType
    from multiverso_tpu.runtime.net import TcpNet

    def rtt_leg(coalesce):
        FLAGS.reset()
        mv.set_flag("wire_coalesce_frames", 64 if coalesce else 0)
        nets = [TcpNet() for _ in range(2)]
        eps = [net.bind(r, "127.0.0.1:0") for r, net in enumerate(nets)]
        for net in nets:
            net.connect(eps)

        def echo():
            while True:
                m = nets[1].recv()
                if m is None:
                    return
                r = m.create_reply()
                r.data = [np.float32(0)]
                nets[1].send(r)

        threading.Thread(target=echo, daemon=True).start()
        small = np.ones(64, np.float32)
        lat = []
        for i in range(n_rtt):
            t0 = time.perf_counter()
            nets[0].send(Message(src=0, dst=1, type=MsgType.Request_Add,
                                 msg_id=i, data=[small]))
            nets[0].recv()
            lat.append(time.perf_counter() - t0)
        for net in nets:
            net.finalize()
        return float(np.median(lat)) * 1e6

    def bulk_leg(coalesce):
        """SEND-side cost of bulk frames into a raw byte sink — where
        the legacy path's per-frame ``tobytes`` copy is pure loss."""
        import socket as socket_mod
        FLAGS.reset()
        mv.set_flag("wire_coalesce_frames", 64 if coalesce else 0)
        listener = socket_mod.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        net = TcpNet()
        net.rank = 0
        net.connect([f"127.0.0.1:{listener.getsockname()[1]}"])
        net._socket_for(0)
        conn, _ = listener.accept()

        def sink():
            while conn.recv(1 << 20):
                pass

        threading.Thread(target=sink, daemon=True).start()
        big = np.ones(bulk_kb * 256, np.float32)  # bulk_kb KiB payload
        net.send_to(0, [big])  # warm
        t0 = time.perf_counter()
        for _ in range(bulk_frames):
            net.send_to(0, [big])
        net._flush_queues(timeout=120)  # everything handed to the kernel
        dt = time.perf_counter() - t0
        net.finalize()
        listener.close()
        conn.close()
        return bulk_frames * big.nbytes / dt / 1e9

    def served_leg(coalesce):
        FLAGS.reset()
        mv.set_flag("wire_coalesce_frames", 64 if coalesce else 0)
        mv.set_flag("heartbeat_seconds", 0)
        mv.init(remote_workers=2)
        try:
            table = mv.create_table("kv")
            endpoint = mv.serve("127.0.0.1:0")
            client = mv.remote_connect(endpoint)
            rt = client.table(table.table_id)
            keys = list(range(64))
            vals = [1.0] * 64
            for _ in range(4):
                rt.add(keys, vals)
            Dashboard.reset()
            lat = []
            for _ in range(300):  # one outstanding request: pure RTT
                t0 = time.perf_counter()
                rt.add(keys, vals)
                lat.append(time.perf_counter() - t0)

            def push(count):
                handles = []
                for _ in range(count):
                    handles.append(rt.add_async(keys, vals))
                    if len(handles) >= window:
                        rt.wait(handles.pop(0))
                for h in handles:
                    rt.wait(h)

            per = n_adds // producers
            threads = [threading.Thread(target=push, args=(per,))
                       for _ in range(producers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            syscalls = Dashboard.counter_value("SEND_SYSCALLS")
            frames = Dashboard.counter_value("SEND_COALESCED_FRAMES")
            sbytes = Dashboard.counter_value("SEND_COALESCED_BYTES")
            fps = Dashboard.histogram("WIRE_FRAMES_PER_SYSCALL")
            client.close()
            return {
                "p50_us": round(float(np.median(lat)) * 1e6, 1),
                "adds_per_sec": round(per * producers / dt, 1),
                "frames_per_syscall_p50": (round(fps.p50, 2)
                                           if fps.count else None),
                "frames_per_syscall": (round(frames / syscalls, 2)
                                       if syscalls and frames else None),
                "bytes_per_syscall": (round(sbytes / syscalls, 1)
                                      if syscalls and sbytes else None),
            }
        finally:
            mv.shutdown()

    # interleaved A/B reps: the host is shared, so adjacent pairs see the
    # same load epoch; latency takes min (noise only adds time),
    # bandwidth/throughput take max
    rtts, rtts_l, gbps, gbps_l = [], [], [], []
    for _ in range(3):
        rtts.append(rtt_leg(True))
        rtts_l.append(rtt_leg(False))
        gbps.append(bulk_leg(True))
        gbps_l.append(bulk_leg(False))
    co = served_leg(True)
    legacy = served_leg(False)
    co2 = served_leg(True)
    legacy2 = served_leg(False)
    best = max(co, co2, key=lambda r: r["adds_per_sec"])
    best_l = max(legacy, legacy2, key=lambda r: r["adds_per_sec"])
    return {
        "wire_rtt_us": round(min(rtts), 1),
        "wire_rtt_us_legacy": round(min(rtts_l), 1),
        "wire_bulk_gbps": round(max(gbps), 3),
        "wire_bulk_gbps_legacy": round(max(gbps_l), 3),
        "wire_add_p50_us": min(co["p50_us"], co2["p50_us"]),
        "wire_add_p50_us_legacy": min(legacy["p50_us"],
                                      legacy2["p50_us"]),
        "wire_pipelined_adds_per_sec": best["adds_per_sec"],
        "wire_pipelined_adds_per_sec_legacy": best_l["adds_per_sec"],
        "wire_frames_per_syscall_p50": best["frames_per_syscall_p50"],
        "wire_frames_per_syscall": best["frames_per_syscall"],
        "wire_bytes_per_syscall": best["bytes_per_syscall"],
        "wire_coalesce_speedup_x": round(
            best["adds_per_sec"]
            / max(best_l["adds_per_sec"], 1e-9), 2),
    }


def _apply_child() -> None:
    """Serving child for the apply-path bench: one CPU-mesh process
    serving a MatrixTable (like the shard bench's children, this measures
    the serving machinery — transport + dispatcher + fused apply — not
    accelerator silicon). Flags ride env vars; prints the endpoint and
    sleeps until killed."""
    import os
    import multiverso_tpu as mv
    mv.init(remote_workers=8,
            wire_shm=os.environ.get("MV_APPLY_SHM", "1") == "1",
            apply_batch_msgs=int(os.environ.get("MV_APPLY_BATCH", "64")),
            heartbeat_seconds=0)
    table = mv.create_table(
        "matrix", num_row=int(os.environ.get("MV_APPLY_ROWS", "65536")),
        num_col=int(os.environ.get("MV_APPLY_COLS", "128")))
    endpoint = mv.serve("127.0.0.1:0")
    print(f"serving {endpoint} {table.table_id}", flush=True)
    time.sleep(600)


def bench_apply_path(rows=65536, cols=128, batch_rows=1024, n_adds=400,
                     producers=4, window=32):
    """Apply-path micro-bench — the receive-side mirror of ``bench_wire``,
    measuring the two attacks on the served-Add software overhead against
    a SEPARATE colocated serving process (the deployment shape the shm
    transport exists for; an in-process server would serialize the
    transport's polling with the dispatcher on the GIL and measure
    neither):

    - **micro-batched fused apply** (runtime/server.py): A/B'd fused
      (apply_batch_msgs=64) vs per-message (=0) under the same
      multi-producer load, with the server's APPLY_BATCH_ROWS histogram
      (via the stats RPC) proving batching actually happened;
    - **shm ring transport** (runtime/shm.py): the same served workload
      plus a small-payload RTT over shm vs TCP.

    Served GB/s counts acknowledged delta-payload bytes over wall clock;
    the producer sweep reports how the fused batch grows with
    concurrency. Children run the CPU mesh — this is serving-machinery
    throughput, not accelerator bandwidth."""
    import os
    import subprocess
    import sys as sys_mod
    import threading

    import multiverso_tpu as mv
    from multiverso_tpu.config import FLAGS

    me = os.path.abspath(__file__)

    def served_leg(use_shm, fuse, n_producers):
        FLAGS.reset()
        mv.set_flag("wire_shm", bool(use_shm))
        mv.set_flag("heartbeat_seconds", 0)
        env = dict(os.environ)
        # the child's platform is written, never inherited: this process
        # may hold the chip
        env.update(JAX_PLATFORMS="cpu",
                   MV_APPLY_SHM="1" if use_shm else "0",
                   MV_APPLY_BATCH="64" if fuse else "0",
                   MV_APPLY_ROWS=str(rows), MV_APPLY_COLS=str(cols))
        child = subprocess.Popen([sys_mod.executable, me, "_apply_child"],
                                 stdout=subprocess.PIPE, text=True,
                                 env=env)
        try:
            for _ in range(50):
                line = child.stdout.readline().strip()
                if line.startswith("serving "):
                    _, endpoint, table_id = line.split()
                    break
            else:
                raise RuntimeError("apply-bench child never served")
            client = mv.remote_connect(endpoint)
            rt = client.table(int(table_id))
            rng = np.random.default_rng(0)
            id_batches = [rng.choice(rows, batch_rows, replace=False)
                          .astype(np.int32) for _ in range(8)]
            vals = np.ones((batch_rows, cols), np.float32)
            small_ids = np.arange(8, dtype=np.int32)
            small = np.ones((8, cols), np.float32)
            for b in id_batches[:4]:  # warm the jit buckets
                rt.add(vals, row_ids=b)
            rt.add(small, row_ids=small_ids)
            lat = []
            for _ in range(200):  # small-payload RTT, one outstanding
                t0 = time.perf_counter()
                rt.add(small, row_ids=small_ids)
                lat.append(time.perf_counter() - t0)

            def push(count):
                handles = []
                for i in range(count):
                    handles.append(rt.add_async(vals,
                                                row_ids=id_batches[i % 8]))
                    if len(handles) >= window:
                        rt.wait(handles.pop(0))
                for h in handles:
                    rt.wait(h)

            per = max(1, n_adds // n_producers)
            threads = [threading.Thread(target=push, args=(per,))
                       for _ in range(n_producers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            payload = per * n_producers * batch_rows * cols * 4
            snap = mv.stats(endpoint)  # server-side apply telemetry
            rows_hist = snap.histogram("APPLY_BATCH_ROWS")
            client.close()
            return {
                "gbps": round(payload / dt / 1e9, 3),
                "adds_per_sec": round(per * n_producers / dt, 1),
                "p50_us": round(float(np.median(lat)) * 1e6, 1),
                "batch_rows_p50": (round(rows_hist.p50, 1)
                                   if rows_hist is not None
                                   and rows_hist.count else None),
                "fused_calls": snap.counter("APPLY_FUSED_CALLS"),
                "batched_msgs": snap.counter("APPLY_BATCHED_MSGS"),
            }
        finally:
            child.kill()
            child.wait(timeout=30)

    # interleaved A/B reps (shared host): latency takes min, GB/s takes max
    def best(legs):
        out = max(legs, key=lambda r: r["gbps"])
        out["p50_us"] = min(leg["p50_us"] for leg in legs)
        return out

    fused_shm = best([served_leg(True, True, producers) for _ in range(2)])
    permsg_shm = best([served_leg(True, False, producers)
                       for _ in range(2)])
    fused_tcp = best([served_leg(False, True, producers)
                      for _ in range(2)])
    sweep = {}
    for n in (1, 8):
        leg = served_leg(True, True, n)
        sweep[str(n)] = {"gbps": leg["gbps"],
                         "batch_rows_p50": leg["batch_rows_p50"]}
    sweep[str(producers)] = {"gbps": fused_shm["gbps"],
                             "batch_rows_p50": fused_shm["batch_rows_p50"]}
    return {
        "served_add_gbps": fused_shm["gbps"],
        "served_add_gbps_permsg": permsg_shm["gbps"],
        "served_add_gbps_tcp": fused_tcp["gbps"],
        "served_add_p50_us_shm": fused_shm["p50_us"],
        "served_add_p50_us_tcp": fused_tcp["p50_us"],
        "served_adds_per_sec": fused_shm["adds_per_sec"],
        "apply_batch_rows_p50": fused_shm["batch_rows_p50"],
        "apply_fused_calls": fused_shm["fused_calls"],
        "apply_batched_msgs": fused_shm["batched_msgs"],
        "apply_fused_speedup_x": round(
            fused_shm["gbps"] / max(permsg_shm["gbps"], 1e-9), 2),
        "apply_shm_speedup_x": round(
            fused_shm["gbps"] / max(fused_tcp["gbps"], 1e-9), 2),
        "apply_producer_sweep": sweep,
        "apply_batch_rows_cols": [batch_rows, cols],
    }


def bench_resnet_asgd(depth=20, batch=128, steps=24, warmup=4):
    """ResNet ASGD cost — the shape of the reference's only PUBLISHED
    numbers (torch/lasagne ResNet-32 CIFAR ASGD,
    ``binding/python/docs/BENCHMARK.md:57-59``). Two figures:

    - ``resnet_images_per_sec``: plain jitted train-step throughput on the
      chip (CIFAR shape, batch 128, bfloat16 matmuls);
    - ``asgd_sync_overhead_pct``: extra wall-clock per step when every
      batch ALSO syncs the full 270k-param model through a PS table — the
      reference's "1P1G with Multiverso" overhead row measured 175.4 ->
      194.4 s/epoch = +10.8%; smaller is better.

    Same per-batch python-loop dispatch on both sides, fetch-forced."""
    import jax
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.ext import PytreeParamManager
    from multiverso_tpu.models.resnet import (ResNetConfig, init_resnet,
                                              make_train_step, synthetic_cifar,
                                              train_state)

    cfg = ResNetConfig(depth=depth)
    model, variables = init_resnet(cfg, jax.random.PRNGKey(0))
    step = make_train_step(model, cfg)
    X, y = synthetic_cifar(batch * 8, num_classes=10)
    # data staged in HBM once — measures the chip + sync machinery, not
    # per-step host->device transfer of the batch
    batches = [(jax.device_put(jnp.asarray(X[i:i + batch])),
                jax.device_put(jnp.asarray(y[i:i + batch])))
               for i in range(0, len(X) - batch + 1, batch)]

    def run(n, state, view=None, pipeline=False, drain=True):
        for i in range(n):
            xb, yb = batches[i % len(batches)]
            state, _ = step(state, xb, yb, cfg.lr)
            if view is not None:
                state["params"] = (view.sync_pipelined(state["params"])
                                   if pipeline
                                   else view.sync(state["params"]))
        if view is not None and pipeline and drain:
            state["params"] = view.drain()
        _fetch(jax.tree.leaves(state["params"])[0])
        return state

    state = run(warmup, train_state(model, cfg, variables))
    mv.init([])
    try:
        view = PytreeParamManager(state["params"]).worker_view(device=True)
        state = run(warmup, state, view)
        # PAIRED deltas over FINE-GRAINED alternation (round-4 verdict
        # weak #3, hardened round 5): plain/sync/pipelined alternate in
        # small adjacent blocks so a seconds-scale external load burst
        # lands on all three variants of a rep roughly equally; the
        # overhead is the MEDIAN of per-rep differences. (Coarse per-
        # variant minima compared times from different load epochs and
        # reported negative overheads — an artifact, not a speedup.)
        blk = max(4, steps // 4)
        reps = 12

        def timed(view_=None, pipeline=False):
            nonlocal state
            # the pipeline DRAIN is excluded from the timed region (and
            # run untimed right after): steady-state pipelined training
            # drains once per epoch, so charging one flush per 6-step
            # block would inflate the overhead ~4x vs real use
            t0 = time.perf_counter()
            state = run(blk, state, view_, pipeline, drain=False)
            dt = (time.perf_counter() - t0) / blk
            if pipeline:
                state["params"] = view_.drain()
            return dt

        # plain-sync-plain-pipe-plain sandwiches: each variant is
        # compared against the MEAN of its surrounding plain blocks, so
        # linear load drift cancels exactly and only burst EDGES inside
        # one ~100ms sandwich can bias a rep — then the median across
        # reps drops those
        plain_s, d_sync_s, d_pipe_s, d_null_s = [], [], [], []
        for _ in range(reps):
            p1 = timed()
            s = timed(view)
            p2 = timed()
            pp = timed(view, pipeline=True)
            p3 = timed()
            plain_s.extend([p1, p2, p3])
            d_sync_s.append(s - (p1 + p2) / 2)
            d_pipe_s.append(pp - (p2 + p3) / 2)
            # null sandwich (plain vs its plain neighbors): the same
            # estimator applied where the true delta IS zero — its
            # magnitude is the run's measured noise floor, so a reported
            # overhead smaller than it reads as zero-within-noise
            # (pipelined overhead genuinely sits there: overlap hides
            # the submission entirely at these step times)
            d_null_s.append(p2 - (p1 + p3) / 2)
    finally:
        mv.shutdown()
    med_plain = float(np.median(plain_s))
    d_sync = float(np.median(d_sync_s))
    d_pipe = float(np.median(d_pipe_s))
    noise = float(np.median(np.abs(d_null_s)))
    return {
        # throughput keeps the burst-robust minimum (noise only adds time)
        "resnet_images_per_sec": round(batch / min(plain_s), 1),
        "asgd_sync_overhead_pct": round(100.0 * d_sync / med_plain, 1),
        # absolute cost of one full-model sync (reference context: its
        # +10.8% overhead row was ~140ms/batch absolute on 1.3s steps)
        "asgd_sync_ms": round(1e3 * d_sync, 2),
        # one-round-stale pipelined sync (sync_pipelined): the submission
        # overlaps the next batch's compute — the reference LR pipeline's
        # double-buffer shape applied to ASGD
        "asgd_pipelined_overhead_pct": round(100.0 * d_pipe / med_plain, 1),
        # measured per-run noise floor (null plain-vs-plain sandwich):
        # any |overhead| below this is zero-within-noise on the shared
        # chip, not a speedup or a regression
        "asgd_noise_floor_pct": round(100.0 * noise / med_plain, 1),
    }


def bench_sharded(shards, rows=4096, cols=32, batch_rows=256,
                  n_batches=240, window=32):
    """Sharded serving-tier throughput (docs/sharding.md): MatrixTable
    row Adds through the ShardedClient router against a local
    ``shards``-process ShardGroup, next to the SAME workload against a
    1-shard group — an apples-to-apples scaling ratio (both sides pay the
    router + wire path; only the server fan-out differs). Reports
    aggregate adds/rows per second plus each shard's served-Add count and
    dispatcher p50 from the live stats RPC, so BENCH_*.json records a
    scaling curve per run. Local groups run CPU children — this measures
    the serving machinery (dispatcher fan-out), not accelerator silicon."""
    import multiverso_tpu as mv
    from multiverso_tpu.shard.group import ShardGroup

    def run_group(n):
        group = ShardGroup(
            [{"kind": "matrix", "num_row": rows, "num_col": cols}],
            shards=n, flags={"remote_workers": 4}).start()
        try:
            client = group.connect()
            table = client.table(0)
            rng = np.random.default_rng(0)
            batches = [rng.choice(rows, batch_rows, replace=False)
                       .astype(np.int32) for _ in range(16)]
            vals = np.ones((batch_rows, cols), np.float32)
            for b in batches[:4]:  # warm every shard's jit buckets
                table.add(vals, row_ids=b)
            handles = []
            t0 = time.perf_counter()
            for i in range(n_batches):
                handles.append(table.add_async(vals,
                                               row_ids=batches[i % 16]))
                if len(handles) >= window:
                    table.wait(handles.pop(0))
            for h in handles:
                table.wait(h)
            dt = time.perf_counter() - t0
            merged = mv.stats_all(group.endpoints)
            per_shard = {}
            for k, sub in enumerate(merged.shards):
                hist = sub.histogram("SERVER_PROCESS_ADD_MSG")
                per_shard[f"shard{k}"] = {
                    "adds_served": hist.count if hist else 0,
                    "add_p50_us": round((hist.p50 if hist else 0.0) * 1e6,
                                        1)}
            client.close()
            return n_batches / dt, per_shard
        finally:
            group.stop()

    sharded_bps, per_shard = run_group(shards)
    single_bps, _ = run_group(1)
    return {
        "shards": shards,
        "sharded_row_adds_per_sec": round(sharded_bps * batch_rows, 1),
        "sharded_batches_per_sec": round(sharded_bps, 1),
        "single_row_adds_per_sec": round(single_bps * batch_rows, 1),
        "sharded_scaling_x": round(sharded_bps / single_bps, 2),
        "sharded_batch_rows": batch_rows,
        "per_shard": per_shard,
    }


def bench_audit(rows=4096, cols=32, batch_rows=256, n_batches=160,
                window=32, audit_interval=0.2):
    """Fleet-integrity-plane overhead A/B (docs/observability.md §audit):
    the same windowed row-Add stream against a live 2-shard group, timed
    with the auditor off and then with the background ``mv.audit`` sweep
    digesting every member at ``audit_interval`` — the digest fold runs
    dispatcher-serialized on each shard, so this measures exactly what a
    production fleet pays for continuous divergence auditing
    (``audit_overhead_pct``, min-of-3 both legs). One consistent cut of
    the loaded fleet is timed alongside (``cut_fleet_seconds``) so the
    PITR snapshot cost rides every BENCH_*.json."""
    import multiverso_tpu as mv
    from multiverso_tpu.shard.group import ShardGroup

    group = ShardGroup(
        [{"kind": "matrix", "num_row": rows, "num_col": cols}],
        shards=2, durable=True, flags={"remote_workers": 4}).start()
    try:
        client = group.connect()
        table = client.table(0)
        rng = np.random.default_rng(0)
        batches = [rng.choice(rows, batch_rows, replace=False)
                   .astype(np.int32) for _ in range(16)]
        vals = np.ones((batch_rows, cols), np.float32)
        for b in batches[:4]:  # warm every shard's jit buckets
            table.add(vals, row_ids=b)

        def leg():
            best = float("inf")
            for _ in range(3):
                handles = []
                t0 = time.perf_counter()
                for i in range(n_batches):
                    handles.append(table.add_async(vals,
                                                   row_ids=batches[i % 16]))
                    if len(handles) >= window:
                        table.wait(handles.pop(0))
                for h in handles:
                    table.wait(h)
                best = min(best, time.perf_counter() - t0)
            return best

        base = leg()
        auditor = mv.audit(group, interval=audit_interval)
        try:
            audited = leg()
        finally:
            auditor.stop()
        sweeps = (auditor.last_report or {}).get("shards", [])
        t0 = time.perf_counter()
        mv.cut_fleet(group, cut_id="bench")
        cut_seconds = time.perf_counter() - t0
        client.close()
        overhead = (audited - base) / base * 100.0 if base > 0 else 0.0
        return {
            "audit_overhead_pct": round(overhead, 2),
            "audit_base_seconds": round(base, 6),
            "audit_audited_seconds": round(audited, 6),
            "audit_interval_seconds": audit_interval,
            "audit_members_per_sweep": len(sweeps),
            "cut_fleet_seconds": round(cut_seconds, 4),
        }
    finally:
        group.stop()


class TrafficGen:
    """Realistic serving-traffic generator (the ROADMAP scenario item's
    first slice): Zipfian key skew over a permuted key space, a
    read/write mix, and a target-QPS pacer. Deterministic per seed, so
    every A/B leg replays the identical op stream."""

    def __init__(self, key_space, zipf_s=1.2, read_fraction=0.95,
                 target_qps=0.0, seed=0):
        self.key_space = int(key_space)
        self.zipf_s = float(zipf_s)
        self.read_fraction = float(read_fraction)
        self.target_qps = float(target_qps)
        self._rng = np.random.default_rng(seed)
        ranks = np.arange(1, self.key_space + 1, dtype=np.float64)
        pmf = ranks ** -self.zipf_s
        self._cdf = np.cumsum(pmf / pmf.sum())
        # hot ranks land on scattered keys, not 0..k (a real keyspace's
        # hot set is not contiguous)
        self._perm = self._rng.permutation(self.key_space)
        self._t0 = None
        self._issued = 0

    def draw_key(self):
        return int(self._perm[int(np.searchsorted(
            self._cdf, self._rng.random()))])

    def next_op(self):
        """-> ("get"|"add", key). Paces to target_qps when set (token
        timing against the wall clock); 0 = unthrottled."""
        if self.target_qps > 0:
            if self._t0 is None:
                self._t0 = time.perf_counter()
            due = self._t0 + self._issued / self.target_qps
            lag = due - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
        self._issued += 1
        kind = ("get" if self._rng.random() < self.read_fraction
                else "add")
        return kind, self.draw_key()


def bench_read(rows=8192, cols=32, seconds=5.0, zipf_s=1.6,
               write_qps=50.0, n_readers=4, replicas=2):
    """Read-path serving A/B (docs/serving.md): hot-key Zipfian Gets
    against a 1-shard group with ``replicas`` serving read replicas,
    under a concurrent write stream — aggregate Get/s for primary-only
    vs replica vs replica+cache vs hedged routing, with the cache hit
    rate and the proof that replica-served Gets consume ZERO primary
    worker slots (the primary's Get-dispatch count during the replica
    legs is fallbacks only). Readers dial the shard's primary directly
    (one shard needs no router hop — the sharded router path is benched
    by bench_sharded and drilled in tests/test_replica.py). Local CPU
    children: this measures the serving machinery, not silicon."""
    import multiverso_tpu as mv
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.shard.group import ShardGroup

    group = ShardGroup(
        [{"kind": "matrix", "num_row": rows, "num_col": cols}],
        shards=1, replicas=replicas,
        flags={"remote_workers": 8, "heartbeat_seconds": 0.2}).start()
    result = {"read_key_space": rows, "read_zipf_s": zipf_s,
              "read_write_qps": write_qps, "read_replicas": replicas,
              "read_seconds": seconds}
    try:
        mv.set_flag("read_staleness_records", 1 << 30)
        seed_client = group.connect(read_preference="primary")
        table = seed_client.table(0)
        base = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
        table.add(base, row_ids=np.arange(rows, dtype=np.int32))
        # wait for the replicas to drain the seed adds
        deadline = time.monotonic() + 60
        for fleet in group.replica_endpoints:
            for ep in fleet:
                while time.monotonic() < deadline:
                    probe = mv.watermark(ep)
                    if probe["watermark"] >= 1 and probe["lag"] == 0:
                        break
                    time.sleep(0.1)

        def primary_get_msgs():
            hist = mv.stats(group.endpoints[0]).histogram(
                "SERVER_PROCESS_GET_MSG")
            return hist.count if hist else 0

        def run_leg(name, preference, cache_bytes):
            mv.set_flag("client_cache_bytes", cache_bytes)
            mv.set_flag("read_lease_seconds", 5.0)
            client = mv.remote_connect(
                group.endpoints[0],
                read_endpoints=group.replica_endpoints[0],
                read_preference=preference)
            leg_table = client.table(0)
            hits0 = Dashboard.counter_value("READ_CACHE_HITS")
            miss0 = Dashboard.counter_value("READ_CACHE_MISSES")
            primary0 = primary_get_msgs()
            gets = [0] * n_readers
            stop = threading.Event()
            errors = []

            def reader(idx):
                gen = TrafficGen(rows, zipf_s=zipf_s, read_fraction=1.0,
                                 seed=100 + idx)
                ids = np.zeros(1, np.int32)
                while not stop.is_set():
                    try:
                        ids[0] = gen.draw_key()
                        leg_table.get(row_ids=ids)
                        gets[idx] += 1
                    except Exception as exc:  # noqa: BLE001
                        errors.append(exc)
                        return

            def writer():
                gen = TrafficGen(rows, zipf_s=zipf_s, read_fraction=0.0,
                                 target_qps=write_qps, seed=7)
                vals = np.ones((1, cols), np.float32)
                ids = np.zeros(1, np.int32)
                while not stop.is_set():
                    ids[0] = gen.draw_key()
                    try:
                        table.add_async(vals, row_ids=ids)
                    except Exception:  # noqa: BLE001 — writer is ambience
                        return
                    gen.next_op()  # pace

            threads = [threading.Thread(target=reader, args=(i,))
                       for i in range(n_readers)]
            wthread = threading.Thread(target=writer)
            for t in threads:
                t.start()
            wthread.start()
            time.sleep(seconds)
            stop.set()
            for t in threads + [wthread]:
                t.join(timeout=30)
            client.close()
            if errors:
                raise errors[0]
            total = sum(gets)
            leg = {f"read_gets_per_sec_{name}": round(total / seconds, 1),
                   f"read_primary_get_msgs_{name}":
                       primary_get_msgs() - primary0}
            hits = Dashboard.counter_value("READ_CACHE_HITS") - hits0
            misses = Dashboard.counter_value("READ_CACHE_MISSES") - miss0
            if cache_bytes and (hits + misses):
                leg["read_cache_hit_rate"] = round(hits / (hits + misses),
                                                   3)
            return leg

        legs = [("primary", "primary", 0),
                ("replica", "replica", 0),
                ("replica_cache", "replica", 64 << 20),
                ("hedged", "hedged", 0)]
        for name, preference, cache_bytes in legs:
            result.update(run_leg(name, preference, cache_bytes))
        mv.set_flag("client_cache_bytes", 0)
        primary_gps = result["read_gets_per_sec_primary"]
        if primary_gps:
            result["read_speedup_replica_x"] = round(
                result["read_gets_per_sec_replica"] / primary_gps, 2)
            result["read_speedup_replica_cache_x"] = round(
                result["read_gets_per_sec_replica_cache"] / primary_gps, 2)
            result["read_speedup_hedged_x"] = round(
                result["read_gets_per_sec_hedged"] / primary_gps, 2)
        seed_client.close()
    finally:
        group.stop()
    return result


def bench_tiered(key_space=600_000, width=8, ratio=10, ops=40_000,
                 zipf_s=1.1, read_fraction=0.95, cold_bits=8):
    """Tiered beyond-RAM serving (docs/tiered_storage.md): a
    TieredSparseServer holding a table ``ratio``x larger than its
    hot-tier budget, under the TrafficGen Zipf op stream (s≈1.1 — the
    recommender skew). The hot set is pre-warmed to steady state (the
    generator's top ranks are touched enough to pass admission — what a
    live server reaches after its first traffic minutes), then the
    measured window reports the converged hot-tier hit rate and
    throughput via counter deltas. In-process and CPU-only: this
    measures the tiering machinery, not silicon."""
    import shutil
    import tempfile

    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.tables.sparse_table import TieredSparseServer

    table_bytes = key_space * width * 4
    resident = table_bytes // ratio
    hot_rows = resident // (width * 4)
    tier_dir = tempfile.mkdtemp(prefix="mvtier_bench_")
    server = TieredSparseServer(key_space, width,
                                resident_bytes=resident,
                                cold_bits=cold_bits, tier_dir=tier_dir)
    try:
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        batch = 50_000
        for start in range(0, key_space, batch):
            keys = np.arange(start, min(start + batch, key_space),
                             dtype=np.int64)
            vals = rng.standard_normal((len(keys), width)).astype(np.float32)
            server.process_add((keys, vals, None))
        populate_s = time.perf_counter() - t0

        gen = TrafficGen(key_space, zipf_s=zipf_s,
                         read_fraction=read_fraction, seed=3)
        # steady-state warm: rank r's key is gen._perm[r]; touching the
        # top `hot_rows` ranks via the Add path (zero deltas — value
        # no-ops) promotes exactly the set Zipf traffic keeps hot
        warm = np.ascontiguousarray(gen._perm[:hot_rows], dtype=np.int64)
        zeros = np.zeros((4096, width), np.float32)
        for start in range(0, len(warm), 4096):
            chunk = warm[start:start + 4096]
            server.process_add((chunk, zeros[:len(chunk)], None))

        hot0 = Dashboard.counter_value("TIER_HOT_HITS")
        cold0 = Dashboard.counter_value("TIER_COLD_HITS")
        demo0 = Dashboard.counter_value("TIER_DEMOTIONS")
        promo0 = Dashboard.counter_value("TIER_PROMOTIONS")
        one = np.ones((1, width), np.float32)
        key = np.zeros(1, np.int64)
        gets = adds = 0
        t0 = time.perf_counter()
        for _ in range(ops):
            kind, k = gen.next_op()
            key[0] = k
            if kind == "get":
                server.process_get((key, None))
                gets += 1
            else:
                server.process_add((key, one, None))
                adds += 1
        elapsed = time.perf_counter() - t0
        hot = Dashboard.counter_value("TIER_HOT_HITS") - hot0
        cold = Dashboard.counter_value("TIER_COLD_HITS") - cold0
        stats = server.tier_stats()
        raw_cold = stats["cold_rows"] * (width * 4 + 8)  # row + key bytes
        return {
            "tiered_key_space": key_space,
            "tiered_width": width,
            "tiered_table_mb": round(table_bytes / 2 ** 20, 2),
            "tiered_resident_mb": round(resident / 2 ** 20, 2),
            "tiered_size_ratio": round(table_bytes / resident, 2),
            "tiered_cold_bits": cold_bits,
            "tiered_zipf_s": zipf_s,
            "tiered_ops": ops,
            "tiered_hot_hit_rate": round(hot / max(1, hot + cold), 4),
            "tiered_ops_per_sec": round(ops / elapsed, 1),
            "tiered_gets_per_sec": round(gets / elapsed, 1),
            "tiered_cold_fetches": cold,
            "tiered_promotions":
                Dashboard.counter_value("TIER_PROMOTIONS") - promo0,
            "tiered_demotions":
                Dashboard.counter_value("TIER_DEMOTIONS") - demo0,
            "tiered_populate_rows_per_sec": round(key_space / populate_s, 1),
            "tiered_cold_compression_x": round(
                raw_cold / max(1, stats["cold_bytes"]), 2),
            "tiered_hot_rows": stats["hot_rows"],
            "tiered_cold_rows": stats["cold_rows"],
        }
    finally:
        server._tier.close()
        shutil.rmtree(tier_dir, ignore_errors=True)


def bench_query(key_space=600_000, width=8, ratio=10, n_queries=40,
                batch=16, k=16, cold_bits=8, rows=4096, cols=32,
                seconds=4.0, n_readers=4, replicas=2):
    """Query-plane serving bench (docs/serving.md): two legs of the
    server-side top-k pushdown.

    Tiered leg: ``query_table`` over a TieredSparseServer holding a
    table ``ratio``x larger than its hot-tier budget — every query
    scans the cold segments batch-wise (compressed-domain scoring at
    ``cold_bits`` >= 4), so QPS/p99 here price the full beyond-RAM
    scan. The leg also proves the scan is a pure READ of the tier:
    TIER_PROMOTIONS and the hot/cold hit counters must not move (a
    query that promoted scanned rows would evict the real working set).

    Replica leg: Zipf-less steady query stream against a 1-shard group
    with serving read replicas, ``read_preference=replica`` — QPS/p99
    for replica-served queries plus the proof that the primary
    dispatched ZERO queries during the window (its
    SERVER_PROCESS_QUERY_MSG count is flat; fallbacks would show here).
    Local CPU children: this measures the serving machinery, not
    silicon."""
    import shutil
    import tempfile

    import multiverso_tpu as mv
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.query.engine import query_table
    from multiverso_tpu.shard.group import ShardGroup
    from multiverso_tpu.tables.sparse_table import TieredSparseServer

    result = {"query_key_space": key_space, "query_width": width,
              "query_k": k, "query_batch": batch,
              "query_replicas": replicas}

    # -- tiered leg: cold-segment scan QPS/p99 + no-promotion proof ----
    table_bytes = key_space * width * 4
    resident = table_bytes // ratio
    tier_dir = tempfile.mkdtemp(prefix="mvquery_bench_")
    server = TieredSparseServer(key_space, width,
                                resident_bytes=resident,
                                cold_bits=cold_bits, tier_dir=tier_dir)
    try:
        rng = np.random.default_rng(0)
        seed_batch = 50_000
        for start in range(0, key_space, seed_batch):
            keys = np.arange(start, min(start + seed_batch, key_space),
                             dtype=np.int64)
            vals = rng.standard_normal((len(keys), width)).astype(np.float32)
            server.process_add((keys, vals, None))
        result["query_tiered_size_ratio"] = round(table_bytes / resident, 2)

        promo0 = Dashboard.counter_value("TIER_PROMOTIONS")
        hot0 = Dashboard.counter_value("TIER_HOT_HITS")
        cold0 = Dashboard.counter_value("TIER_COLD_HITS")
        seg0 = Dashboard.counter_value("QUERY_COLD_SEGMENTS_SCANNED")
        comp0 = Dashboard.counter_value("QUERY_COMPRESSED_SEGMENTS")
        lat = []
        vecs = rng.standard_normal((batch, width)).astype(np.float32)
        query_table(server, (vecs, k, "dot"))  # warm the jit caches
        t0 = time.perf_counter()
        for i in range(n_queries):
            q = rng.standard_normal((batch, width)).astype(np.float32)
            tq = time.perf_counter()
            query_table(server, (q, k, "dot"))
            lat.append(time.perf_counter() - tq)
        elapsed = time.perf_counter() - t0
        result.update({
            "query_tiered_qps": round(n_queries / elapsed, 1),
            "query_tiered_p99_ms": round(
                float(np.percentile(lat, 99)) * 1e3, 2),
            "query_tiered_cold_segments":
                Dashboard.counter_value("QUERY_COLD_SEGMENTS_SCANNED") - seg0,
            "query_tiered_compressed_segments":
                Dashboard.counter_value("QUERY_COMPRESSED_SEGMENTS") - comp0,
            # all three must be 0: the scan never promotes and never
            # touches the tier's hit path, so the hit rate is unchanged
            "query_tiered_promotions":
                Dashboard.counter_value("TIER_PROMOTIONS") - promo0,
            "query_tiered_hot_hits":
                Dashboard.counter_value("TIER_HOT_HITS") - hot0,
            "query_tiered_cold_hits":
                Dashboard.counter_value("TIER_COLD_HITS") - cold0,
        })
    finally:
        server._tier.close()
        shutil.rmtree(tier_dir, ignore_errors=True)

    # -- replica leg: replica-served QPS/p99 + zero-primary proof ------
    group = ShardGroup(
        [{"kind": "matrix", "num_row": rows, "num_col": cols}],
        shards=1, replicas=replicas,
        flags={"remote_workers": 8, "heartbeat_seconds": 0.2}).start()
    try:
        mv.set_flag("read_staleness_records", 1 << 30)
        mv.set_flag("client_cache_bytes", 0)  # measure serving, not cache
        seed_client = group.connect(read_preference="primary")
        table = seed_client.table(0)
        base = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
        table.add(base, row_ids=np.arange(rows, dtype=np.int32))
        deadline = time.monotonic() + 60
        for fleet in group.replica_endpoints:
            for ep in fleet:
                while time.monotonic() < deadline:
                    probe = mv.watermark(ep)
                    if probe["watermark"] >= 1 and probe["lag"] == 0:
                        break
                    time.sleep(0.1)

        def primary_query_msgs():
            hist = mv.stats(group.endpoints[0]).histogram(
                "SERVER_PROCESS_QUERY_MSG")
            return hist.count if hist else 0

        client = mv.remote_connect(
            group.endpoints[0],
            read_endpoints=group.replica_endpoints[0],
            read_preference="replica")
        leg_table = client.table(0)
        served0 = Dashboard.counter_value("QUERIES_VIA_REPLICA")
        fall0 = Dashboard.counter_value("QUERY_PRIMARY_FALLBACKS")
        primary0 = primary_query_msgs()
        counts = [0] * n_readers
        lats = [[] for _ in range(n_readers)]
        stop = threading.Event()
        errors = []

        def reader(idx):
            gen = np.random.default_rng(100 + idx)
            while not stop.is_set():
                try:
                    q = gen.standard_normal((batch, cols)).astype(np.float32)
                    tq = time.perf_counter()
                    leg_table.query(q, k, metric="dot")
                    lats[idx].append(time.perf_counter() - tq)
                    counts[idx] += 1
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return

        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(n_readers)]
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        client.close()
        seed_client.close()
        if errors:
            raise errors[0]
        all_lat = [x for per in lats for x in per]
        result.update({
            "query_qps_replica": round(sum(counts) / seconds, 1),
            "query_p99_ms_replica": round(
                float(np.percentile(all_lat, 99)) * 1e3, 2) if all_lat
                else None,
            "query_via_replica":
                Dashboard.counter_value("QUERIES_VIA_REPLICA") - served0,
            "query_primary_fallbacks":
                Dashboard.counter_value("QUERY_PRIMARY_FALLBACKS") - fall0,
            # the acceptance proof: replica-served queries consume zero
            # primary dispatches (any fallback would move this count)
            "query_primary_dispatches": primary_query_msgs() - primary0,
        })
    finally:
        group.stop()
    return result


def bench_autopilot(rows=256, cols=16, zipf_s=1.2, tick_interval=0.5,
                    recover_seconds=2.0, timeout_seconds=45.0):
    """Fleet-autopilot reaction drill (docs/autopilot.md): a TrafficGen
    Zipf hotspot lands entirely on shard 0 of a live 2-shard durable
    group while a background trickle keeps shard 1 warm, and a
    deterministic ``mv.autopilot`` loop (manual recorder sampling, one
    ``tick_now`` per ``tick_interval``) reads its own router telemetry
    and splits the hot shard through the live migration machinery.
    Reports the wall-clock from hotspot onset to the executed split
    (``autopilot_time_to_split_seconds``), client Add p99 during the hot
    window vs after the split (``..p99_hot_ms`` / ``..p99_recovered_ms``
    — recovery evidence, not a silicon number on this box), and the
    acked-Add conservation check (mirror equality across the autopilot's
    topology change; ``autopilot_acked_rows_lost`` must be 0)."""
    import threading

    import multiverso_tpu as mv
    from multiverso_tpu.obs.timeseries import TimeSeriesRecorder
    from multiverso_tpu.shard.group import ShardGroup

    # the drill recipe (tests/test_autopilot.py Zipf drill): one-tick
    # hysteresis, merges off, thresholds the hot/cold skew clears
    mv.set_flag("autopilot_hysteresis_ticks", 1)
    mv.set_flag("autopilot_window_seconds", 4 * tick_interval)
    mv.set_flag("reshard_cold_qps", 0.0)
    mv.set_flag("reshard_min_qps", 1.0)
    mv.set_flag("reshard_hot_ratio", 2.0)

    recorder = TimeSeriesRecorder(interval=3600.0, samples=64)
    group = ShardGroup(
        [{"kind": "matrix", "num_row": rows, "num_col": cols}],
        shards=2, durable=True, flags={"remote_workers": 4}).start()
    try:
        client = group.connect()
        table = client.table(0)
        model = np.zeros((rows, cols), np.float32)
        span = rows // 2                 # shard 0 owns rows [0, span)
        stop = threading.Event()
        lock = threading.Lock()
        lat_ms, lat_lock = [], threading.Lock()

        def hot_writer(seed):
            # the hotspot: Zipf-skewed keys confined to shard 0's span
            gen = TrafficGen(span, zipf_s=zipf_s, read_fraction=0.0,
                             seed=seed)
            rng = np.random.default_rng(seed)
            while not stop.is_set():
                ids = []
                while len(ids) < 4:
                    k = gen.draw_key()
                    if k not in ids:
                        ids.append(k)
                ids = np.asarray(ids, np.int32)
                vals = rng.integers(0, 5, (4, cols)).astype(np.float32)
                t0 = time.perf_counter()
                table.add(vals, row_ids=ids)
                dt = (time.perf_counter() - t0) * 1e3
                with lock:
                    model[ids] += vals
                with lat_lock:
                    lat_ms.append((time.perf_counter(), dt))
                time.sleep(0.002)

        def background_writer():
            # a thin uniform trickle on shard 1 — the cold side of the
            # hot/cold ratio the detector judges
            rng = np.random.default_rng(99)
            vals = np.ones((2, cols), np.float32)
            while not stop.is_set():
                ids = rng.choice(np.arange(span, rows), 2,
                                 replace=False).astype(np.int32)
                table.add(vals, row_ids=ids)
                with lock:
                    model[ids] += vals
                time.sleep(0.05)

        threads = [threading.Thread(target=hot_writer, args=(s,),
                                    daemon=True) for s in (1, 2)]
        threads.append(threading.Thread(target=background_writer,
                                        daemon=True))
        pilot = mv.autopilot(group, interval=0, recorder=recorder)
        recorder.sample_now(t=time.time())
        hot_t0 = time.perf_counter()
        for t in threads:
            t.start()

        split_at = ticks = None
        deadline = hot_t0 + timeout_seconds
        while time.perf_counter() < deadline:
            time.sleep(tick_interval)
            recorder.sample_now(t=time.time())
            rec = pilot.tick_now(now=time.time())
            if rec.get("action") == "split" and \
                    (rec.get("outcome") or {}).get("ok"):
                split_at = time.perf_counter()
                ticks = pilot.ticks
                break
        if split_at is None:
            raise RuntimeError("autopilot never split the hot shard "
                               f"within {timeout_seconds}s")

        time.sleep(recover_seconds)      # traffic on the new layout
        stop.set()
        for t in threads:
            t.join(timeout=60)
        pilot.stop()

        with lat_lock:
            hot = [ms for (at, ms) in lat_ms if at <= split_at]
            recovered = [ms for (at, ms) in lat_ms if at > split_at]
        final = table.get()
        lost = int(np.count_nonzero(
            np.any(final != model, axis=1)))
        client.close()
        return {
            "autopilot_time_to_split_seconds": round(
                split_at - hot_t0, 3),
            "autopilot_ticks_to_split": ticks,
            "autopilot_tick_interval_seconds": tick_interval,
            "autopilot_zipf_s": zipf_s,
            "autopilot_shards_after": int(group.num_shards),
            "autopilot_p99_hot_ms": round(
                float(np.percentile(hot, 99)), 3) if hot else 0.0,
            "autopilot_p99_recovered_ms": round(
                float(np.percentile(recovered, 99)), 3)
                if recovered else 0.0,
            "autopilot_hot_adds": len(hot) + len(recovered),
            "autopilot_acked_rows_lost": lost,
        }
    finally:
        group.stop()


def bench_overload(rows=64, cols=8, seconds=6.0, zipf_s=1.2,
                   queue_limit=4, tenant_qps=40.0, tenant_burst=20):
    """Overload-survival leg (docs/fault_tolerance.md overload runbook):
    the train-while-serve drill as a measured bench. A 2-shard matrix
    group runs with the full governor stack armed — priority lanes,
    admission queue limit, a tenant token bucket on the training table,
    request deadlines, client retry budget and circuit breaker — while
    shard 1's primary drips its Add replies through the ``stall``
    gray-failure chaos mode. Four unthrottled Zipf writers storm both
    shards and two readers flood hot keys on the healthy shard.

    Reports the shed rate (refused Adds / attempted Adds — the gate's
    brownout depth), per-lane client p99s (serving Gets vs training
    Adds: the number the lanes exist to protect), retry-budget denials,
    breaker trips, deadline drops, and the acked-Add conservation check
    (applied + shed must equal every completion a writer saw —
    ``overload_acked_adds_lost`` must be 0)."""
    import os

    import multiverso_tpu as mv
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.shard.group import ShardGroup

    span = rows // 2                     # shard 0 owns rows [0, span)
    os.environ["MV_CHAOS_SHARD"] = "1"
    os.environ["MV_CHAOS_SPEC"] = "stall:type=Reply_Add,every=2,seconds=0.25"
    mv.set_flag("request_retry_seconds", 0.2)
    mv.set_flag("retry_budget_tokens", 8.0)
    mv.set_flag("retry_budget_ratio", 0.5)
    mv.set_flag("breaker_failures", 3)
    mv.set_flag("breaker_reset_seconds", 0.5)
    tenant_spec = (f"train:tables=0,qps={tenant_qps},"
                   f"burst={tenant_burst}")
    # the spec must ALSO be set client-side (group flags reach only the
    # child servers): the submit sites resolve it to tag every span for
    # the chargeback table below
    mv.set_flag("tenant_quota_spec", tenant_spec)
    group = ShardGroup(
        [{"kind": "matrix", "num_row": rows, "num_col": cols}],
        shards=2,
        flags={"remote_workers": 8,
               "request_retry_seconds": 0.2,
               "request_deadline_seconds": 30.0,
               "admission_queue_limit": queue_limit,
               "tenant_quota_spec": tenant_spec,
               "heartbeat_seconds": 0.2}).start()
    try:
        client = group.connect()
        table = client.table(0)
        stop = threading.Event()
        completions = [0, 0]
        lock = threading.Lock()
        add_lat, read_lat, lat_lock = [], [], threading.Lock()
        errors = []

        def writer(shard, seed):
            gen = TrafficGen(span, zipf_s=zipf_s, read_fraction=0.0,
                             seed=seed)
            vals = np.ones((1, cols), np.float32)
            ids = np.zeros(1, np.int32)
            while not stop.is_set():
                ids[0] = shard * span + gen.draw_key()
                t0 = time.perf_counter()
                try:
                    table.add(vals, row_ids=ids)
                except Exception as exc:  # noqa: BLE001
                    if "circuit open" in repr(exc):
                        time.sleep(0.05)  # truthful fast-fail: back off
                        continue
                    errors.append(exc)
                    return
                with lat_lock:
                    add_lat.append(time.perf_counter() - t0)
                with lock:
                    completions[shard] += 1

        def reader():
            gen = TrafficGen(span, zipf_s=zipf_s, read_fraction=1.0,
                             seed=42)
            ids = np.zeros(1, np.int32)
            while not stop.is_set():
                ids[0] = gen.draw_key()  # rows [0, span): healthy shard
                t0 = time.perf_counter()
                try:
                    table.get(row_ids=ids)
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return
                with lat_lock:
                    read_lat.append(time.perf_counter() - t0)

        threads = ([threading.Thread(target=writer, args=(s, 10 + s),
                                     daemon=True)
                    for s in (0, 1) for _ in range(2)]
                   + [threading.Thread(target=reader, daemon=True)
                      for _ in range(2)])
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        if errors:
            raise RuntimeError(f"overload bench traffic errored: "
                               f"{errors[0]!r}")

        final = np.asarray(table.get())
        shard_stats = [mv.stats(ep, timeout=30.0)
                       for ep in group.endpoints]
        shed_srv = sum(s.counter("SHED_ADDS") for s in shard_stats)
        drops = sum(s.counter("DEADLINE_EXPIRED_DROPS")
                    for s in shard_stats)
        lost = 0
        for shard, stats in enumerate(shard_stats):
            applied = int(round(float(
                final[shard * span:(shard + 1) * span].sum()) / cols))
            shed = (stats.counter("SHED_ADDS")
                    + stats.counter("DEADLINE_EXPIRED_DROPS"))
            lost += abs(completions[shard] - applied - shed)
        attempted = sum(completions)
        # chargeback plane (BENCH_r12): per-tenant admit/shed splits off
        # the TENANT_<t>_* families plus the tenant-partitioned
        # critical-path table, so a multi-core run MEASURES isolation
        from multiverso_tpu.dashboard import split_tenant
        tenant_split = {}
        for stats in shard_stats:
            for name, value in stats.counters.items():
                tenant, suffix = split_tenant(name)
                if tenant is not None and suffix in ("ADMITTED", "SHED"):
                    split = tenant_split.setdefault(
                        tenant, {"admitted": 0, "shed": 0})
                    split[suffix.lower()] += int(value)
        try:
            chargeback_table = mv.chargeback(group, timeout=30.0).to_dict()
        except Exception as exc:  # noqa: BLE001 — never sink the bench
            chargeback_table = {"error": repr(exc)[:200]}
        client.close()
        return {
            "overload_seconds": seconds,
            "overload_zipf_s": zipf_s,
            "overload_add_completions": attempted,
            "overload_adds_shed": int(shed_srv),
            "overload_shed_rate": round(
                shed_srv / attempted, 4) if attempted else 0.0,
            "overload_serving_get_p99_ms": round(float(
                np.percentile(read_lat, 99)) * 1e3, 3) if read_lat
                else 0.0,
            "overload_training_add_p99_ms": round(float(
                np.percentile(add_lat, 99)) * 1e3, 3) if add_lat
                else 0.0,
            "overload_serving_gets": len(read_lat),
            "overload_deadline_drops": int(drops),
            "overload_retry_budget_denials": int(
                Dashboard.counter_value("RETRY_BUDGET_DENIALS")),
            "overload_breaker_trips": int(
                Dashboard.counter_value("BREAKER_TRIPS")),
            "overload_client_adds_shed": int(
                Dashboard.counter_value("CLIENT_ADDS_SHED")),
            "overload_stalled_replies": int(
                shard_stats[1].counter("FAULT_INJECTED_STALL")),
            "overload_acked_adds_lost": int(lost),
            "overload_tenant_split": tenant_split,
            "overload_chargeback": chargeback_table,
        }
    finally:
        group.stop()
        mv.set_flag("tenant_quota_spec", "")
        os.environ.pop("MV_CHAOS_SHARD", None)
        os.environ.pop("MV_CHAOS_SPEC", None)


def bench_autotune(rows=8192, cols=32, batch_rows=256, producers=4,
                   window=24, leg_adds=320, tune_seconds=10.0,
                   rtt_probes=200, threshold=0.10):
    """Self-tuning A/B (docs/autotune.md): hand-tuned-best static
    posture vs the KnobController, same workload, same process, same
    measurement.

    Four legs run the identical measured pass: a loopback-TCP
    multi-producer add storm (windowed ``add_async`` pipelining) with
    one serial small-add prober riding alongside — throughput comes
    from the storm, p99 from the prober's round trips *under that
    load*. Measuring the prober inside the storm keeps the judged
    workload identical to the one the tuner senses; a quiet-wire RTT
    probe after the fact would grade a batching posture on a workload
    it was never tuned for.

    * ``legacy``   — batching and coalescing off (the r06 baseline);
    * ``defaults`` — the shipped flag defaults;
    * ``batched``  — the hand-tuned posture BENCH_r08 settled on
      (``apply_batch_msgs=256``, ``wire_coalesce_frames=256``);
    * ``auto``     — the shipped defaults plus ``autotune=true`` on a
      fast cadence, given ``tune_seconds`` of the same mixture to
      converge, then STOPPED so the measured phase grades the posture
      it converged to (not its in-flight experiments); its
      steps/reverts/commits land in the flight recorder
      (``BENCH_autotune_flight.jsonl`` — the CI audit-trail artifact).

    The best static leg (by throughput-weighted p99) and the auto leg
    are then written as two single-leg result files and diffed through
    the bench's own ``--compare`` machinery with the same-environment
    refusal armed — ``autotune_compare_regressions`` must come back
    empty for the self-tuner to claim parity with the hand tuning."""
    import os

    import multiverso_tpu as mv
    from multiverso_tpu.config import FLAGS

    artifact_dir = os.environ.get("MV_AUTOTUNE_ARTIFACT_DIR", ".")
    flight_path = os.path.join(artifact_dir, "BENCH_autotune_flight.jsonl")
    postures = {
        "legacy": {"apply_batch_msgs": 0, "wire_coalesce_frames": 0,
                   "wire_coalesce_bytes": 0},
        "defaults": {},
        "batched": {"apply_batch_msgs": 256, "wire_coalesce_frames": 256},
    }

    def leg(posture, auto=False):
        FLAGS.reset()
        # identical observability posture in EVERY leg (the sampler and
        # profiler tax must not differ between the compared legs); only
        # the controller itself is the A/B variable
        flags = dict(posture)
        flags.update(heartbeat_seconds=0, remote_workers=2,
                     timeseries_interval_seconds=0.25,
                     profile_continuous=True)
        if auto:
            flags.update(autotune=True,
                         autotune_interval_seconds=0.4,
                         autotune_window_seconds=2.0,
                         autotune_hysteresis_ticks=1,
                         autotune_cooldown_seconds=0.8,
                         autotune_verify_ticks=2,
                         flight_recorder_path=flight_path)
        mv.init(**flags)
        table = mv.create_table("matrix", num_row=rows, num_col=cols)
        endpoint = mv.serve("127.0.0.1:0")
        client = mv.remote_connect(endpoint)
        rt = client.table(table.table_id)
        rng = np.random.default_rng(0)
        id_batches = [np.sort(rng.choice(rows, batch_rows,
                                         replace=False)).astype(np.int32)
                      for _ in range(8)]
        vals = np.ones((batch_rows, cols), np.float32)
        for ids in id_batches[:4]:          # warm the path end to end
            rt.add(vals, row_ids=ids)

        def push(count, seed):
            handles = []
            for i in range(count):
                handles.append(
                    rt.add_async(vals, row_ids=id_batches[(seed + i) % 8]))
                if len(handles) >= window:
                    rt.wait(handles.pop(0))
            for h in handles:
                rt.wait(h)

        def storm(total):
            per = max(1, total // producers)
            threads = [threading.Thread(target=push, args=(per, s),
                                        daemon=True)
                       for s in range(producers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return per * producers, time.perf_counter() - t0

        def prober(stop, lat):
            small_ids = np.arange(8, dtype=np.int32)
            small = np.ones((8, cols), np.float32)
            while not stop.is_set() and len(lat) < rtt_probes:
                t0 = time.perf_counter()
                rt.add(small, row_ids=small_ids)
                lat.append(time.perf_counter() - t0)

        def measured_pass():
            stop, lat = threading.Event(), []
            probe = threading.Thread(target=prober, args=(stop, lat),
                                     daemon=True)
            probe.start()
            n, dt = storm(leg_adds)
            stop.set()
            probe.join(timeout=60)
            return n, dt, lat

        tuner_out = tuned = None
        if auto:
            # convergence phase: the measured mixture stays up until
            # the tuner's budget runs out — steps verify live
            deadline = time.perf_counter() + tune_seconds
            while time.perf_counter() < deadline:
                measured_pass()
            # freeze the converged posture BEFORE measuring: a tuner
            # still experimenting mid-pass would be graded on its own
            # probe steps, not on the posture it converged to. stop()
            # aborts any unverified in-flight step back to its old
            # value, so what survives is exactly the committed state.
            tuner = mv.autotune()
            status = tuner.status() if tuner is not None else {}
            tuner_out = {k: status.get(k, 0) for k in
                         ("ticks", "steps", "reverts", "commits")}
            stepped = {r["verdict"]["flag"]
                       for r in (tuner.history if tuner is not None else ())
                       if r.get("action") == "commit"}
            if tuner is not None:
                tuner.stop()
            tuned = {f: mv.get_flag(f) for f in sorted(stepped)}
        measured_pass()                     # one identical warm pass
        out = None
        for _ in range(2):                  # best-of-2: 1-core p99 noise
            n, dt, lat = measured_pass()
            cand = {"adds_per_sec": round(n / dt, 1),
                    "p99_ms": round(float(np.percentile(lat, 99)) * 1e3,
                                    3)}
            cand["objective_x"] = round(
                cand["adds_per_sec"] / max(cand["p99_ms"], 1e-3), 1)
            if out is None or cand["objective_x"] > out["objective_x"]:
                out = cand
        if auto:
            out["tuner"] = tuner_out
            out["tuned_flags"] = tuned
        client.close()
        mv.shutdown()
        FLAGS.reset()
        return out

    legs = {name: leg(p) for name, p in postures.items()}
    legs["auto"] = leg(postures["defaults"], auto=True)
    hand_best = max(postures, key=lambda k: legs[k]["objective_x"])

    # the A/B verdict rides the bench's own compare machinery: two
    # single-leg files, same-env refusal armed, suffix-driven directions
    files = {}
    for name in (hand_best, "auto"):
        path = os.path.join(artifact_dir, f"BENCH_autotune_{name}.json")
        with open(path, "w") as fh:
            json.dump({"metric": "adds_per_sec", **legs[name],
                       "env": _env_fingerprint()}, fh)
        files[name] = path
    mismatch = _env_mismatch(_load_bench_env(files[hand_best]),
                             _load_bench_env(files["auto"]))
    regressions = bench_compare(files[hand_best], files["auto"],
                                threshold=threshold)
    return {
        "autotune_adds_per_sec": legs["auto"]["adds_per_sec"],
        "autotune_p99_ms": legs["auto"]["p99_ms"],
        "autotune_objective_x": legs["auto"]["objective_x"],
        "autotune_hand_best_posture": hand_best,
        "autotune_hand_best_adds_per_sec": legs[hand_best]["adds_per_sec"],
        "autotune_hand_best_p99_ms": legs[hand_best]["p99_ms"],
        "autotune_vs_hand_best_x": round(
            legs["auto"]["objective_x"]
            / max(legs[hand_best]["objective_x"], 1e-9), 3),
        "autotune_steps": legs["auto"]["tuner"]["steps"],
        "autotune_reverts": legs["auto"]["tuner"]["reverts"],
        "autotune_commits": legs["auto"]["tuner"]["commits"],
        "autotune_ticks": legs["auto"]["tuner"]["ticks"],
        "autotune_tuned_flags": legs["auto"]["tuned_flags"],
        "autotune_compare_regressions": regressions,
        "autotune_compare_same_env": not mismatch,
        "autotune_legs": legs,
        "autotune_flight_path": flight_path,
    }


def main():
    """The default mode: the in-process device legs on the chip this
    process holds. A leg that raises ends the run non-zero."""
    import sys

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench.py: the device legs need a TPU; JAX found "
                 f"{len(devices)} {devices[0].platform} device(s). The CPU "
                 "harnesses run under their own mode flags.")
    words_per_sec, final_loss = bench_word2vec()
    ps = bench_ps_word2vec()
    matrix = bench_matrix_table()
    resnet = bench_resnet_asgd()
    print(json.dumps({
        "metric": "word2vec_words_per_sec_per_chip",
        "value": round(words_per_sec, 1),
        "unit": "words/s",
        # no published words/sec baseline exists (BASELINE.md: the reference
        # only ever logged a live "Words/thread/second" line), so no ratio is
        # reported for the headline metric; the one quantified BASELINE.json
        # target (matrix row-Add p50 < 50us) gets its own field below
        "vs_baseline": None,
        "vs_baseline_note": ("no published words/sec baseline; see "
                             "matrix_add_p50_vs_target for the quantified "
                             "BASELINE.json latency target (>1 = beating it)"),
        "matrix_add_p50_vs_target": round(50.0 / matrix["matrix_add_p50_us"], 2),
        "final_loss": round(final_loss, 4),
        **ps,
        **matrix,
        **resnet,
        "env": _env_fingerprint(),
    }))


def _parse_shards_arg(argv):
    """``--shards N`` / ``--shards=N`` -> N, or None when absent."""
    for i, arg in enumerate(argv):
        if arg == "--shards" and i + 1 < len(argv):
            return int(argv[i + 1])
        if arg.startswith("--shards="):
            return int(arg.split("=", 1)[1])
    return None


# -- regression compare (bench.py --compare A.json B.json) --------------------
# CI runs this non-blocking against the previous round's BENCH_r*.json so a
# perf regression is VISIBLE in the log even when environment noise makes it
# non-fatal; operators run it blocking before accepting a perf-sensitive PR.

# direction classification by key shape: latencies regress UP,
# throughputs/ratios regress DOWN; everything else (configs, counts,
# notes, nested sweeps) is not a comparable metric
_LOWER_BETTER_SUFFIXES = ("_us", "_ms", "_seconds")
_HIGHER_BETTER_MARKS = ("per_sec", "_gbps", "_x", "hit_rate",
                        "vs_target")


def _bench_metric_direction(key):
    """'down' (lower is better), 'up' (higher is better), or None
    (not a comparable metric)."""
    if key.endswith(_LOWER_BETTER_SUFFIXES) or key.endswith(
            "overhead_pct"):
        return "down"
    if any(mark in key for mark in _HIGHER_BETTER_MARKS):
        return "up"
    return None


def _load_bench_json(path):
    """A bench result file: either the raw one-line JSON ``main()``
    prints, or a BENCH_r*.json round wrapper (result under 'parsed')."""
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data.get("parsed"), dict):
        data = data["parsed"]
    return {k: float(v) for k, v in data.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def _load_bench_env(path):
    """The ``env`` fingerprint of a bench result file, or None for
    pre-fingerprint files (they predate the stamp and cannot differ)."""
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data.get("parsed"), dict):
        data = data["parsed"]
    env = data.get("env")
    return env if isinstance(env, dict) else None


def _env_mismatch(env_a, env_b):
    """Fingerprint fields that differ between two bench envs; empty when
    they match or when either file predates fingerprinting."""
    if not env_a or not env_b:
        return []
    return sorted(k for k in set(env_a) | set(env_b)
                  if env_a.get(k) != env_b.get(k))


def bench_compare(path_a, path_b, threshold=0.10):
    """Compare two bench result files (A = baseline, B = candidate):
    any throughput down or latency up by more than ``threshold``
    (fractional) is a regression. Prints a verdict table; returns the
    list of regressed metric names (empty = pass). Differing environment
    fingerprints print a loud warning first — the verdicts below it are
    then cross-environment noise, not regressions."""
    mismatch = _env_mismatch(_load_bench_env(path_a),
                             _load_bench_env(path_b))
    if mismatch:
        env_a, env_b = _load_bench_env(path_a), _load_bench_env(path_b)
        print("WARNING: environment fingerprints differ — the verdicts "
              "below compare different environments and are NOT "
              "regression evidence:")
        for field in mismatch:
            print(f"  {field}: A={env_a.get(field)!r}  "
                  f"B={env_b.get(field)!r}")
    a, b = _load_bench_json(path_a), _load_bench_json(path_b)
    rows, regressions = [], []
    for key in sorted(set(a) & set(b)):
        direction = _bench_metric_direction(key)
        if direction is None or a[key] == 0:
            continue
        change = (b[key] - a[key]) / abs(a[key])
        if direction == "down":
            regressed = change > threshold
            improved = change < -threshold
        else:
            regressed = change < -threshold
            improved = change > threshold
        verdict = ("REGRESSED" if regressed
                   else "improved" if improved else "ok")
        if regressed:
            regressions.append(key)
        rows.append((key, a[key], b[key], change * 100.0, verdict))
    print(f"bench compare: A={path_a}  B={path_b}  "
          f"threshold={threshold * 100:.0f}%")
    print(f"{'metric':<36} {'A':>14} {'B':>14} {'delta':>8}  verdict")
    for key, va, vb, pct, verdict in rows:
        print(f"{key:<36} {va:>14.4g} {vb:>14.4g} {pct:>+7.1f}%  "
              f"{verdict}")
    if regressions:
        print(f"REGRESSIONS ({len(regressions)}): "
              + ", ".join(regressions))
    else:
        print("no regressions beyond threshold")
    return regressions


def _run_compare(argv):
    """``--compare A.json B.json [--threshold 0.1]
    [--require-same-env]`` -> exit status. With ``--require-same-env``
    a fingerprint mismatch refuses the comparison (exit 2) instead of
    producing cross-environment verdicts under a warning."""
    import sys
    i = argv.index("--compare")
    paths = [a for a in argv[i + 1:] if not a.startswith("--")][:2]
    if len(paths) != 2:
        print("usage: bench.py --compare A.json B.json "
              "[--threshold 0.1] [--require-same-env]", file=sys.stderr)
        return 2
    if "--require-same-env" in argv:
        mismatch = _env_mismatch(_load_bench_env(paths[0]),
                                 _load_bench_env(paths[1]))
        if mismatch:
            print("refusing to compare: environment fingerprints differ "
                  f"({', '.join(mismatch)}); drop --require-same-env to "
                  "compare anyway under a warning", file=sys.stderr)
            return 2
    threshold = 0.10
    for j, arg in enumerate(argv):
        if arg == "--threshold" and j + 1 < len(argv):
            threshold = float(argv[j + 1])
        elif arg.startswith("--threshold="):
            threshold = float(arg.split("=", 1)[1])
    return 1 if bench_compare(paths[0], paths[1], threshold) else 0


if __name__ == "__main__":
    import sys
    # --attribute: attach critical-path tables (obs/critpath.py) to the
    # printed JSON — per serving leg in the full run, one table in the
    # single-leg modes
    _ATTRIBUTE = "--attribute" in sys.argv[1:]
    # before the first compile of any mode (the serving child included)
    import multiverso_tpu
    multiverso_tpu.configure_compile_cache()

    def _single_leg_result(result):
        if _ATTRIBUTE:
            tables = {}
            _collect_leg_attribution(result["metric"], tables)
            result["attribution"] = tables
        result["env"] = _env_fingerprint()
        return result

    if len(sys.argv) >= 2 and sys.argv[1] == "_apply_child":
        _apply_child()
    elif "--wire-bench" in sys.argv[1:]:
        # wire micro-bench only: SparseFilter compression ratio, TCP
        # RTT/bandwidth and served KV Adds, coalesced vs per-frame sends
        print(json.dumps(_single_leg_result(
            {"metric": "wire_pipelined_adds_per_sec",
             "wire_sparse_compression_x": bench_wire_compression(),
             **bench_wire()})))
    elif "--profile-bench" in sys.argv[1:]:
        # sampling-profiler overhead A/B on the in-process dense pass
        print(json.dumps(_single_leg_result(
            {"metric": "profile_overhead_pct",
             **bench_profile_overhead()})))
    elif "--apply-bench" in sys.argv[1:]:
        # apply-path micro-bench only (`make apply-bench`): fused vs
        # per-message A/B, producer sweep, shm vs TCP RTT
        print(json.dumps(_single_leg_result(
            {"metric": "served_add_gbps", **bench_apply_path()})))
    elif "--read-bench" in sys.argv[1:]:
        # read-path A/B only (`make read-bench`): Zipf hot-key Gets,
        # primary vs replica vs replica+cache vs hedged
        print(json.dumps(_single_leg_result(
            {"metric": "read_gets_per_sec_replica_cache",
             **bench_read()})))
    elif "--audit-bench" in sys.argv[1:]:
        # fleet-integrity leg only (`make audit` CI job / operators):
        # background-auditor overhead A/B + one timed consistent cut
        print(json.dumps(_single_leg_result(
            {"metric": "audit_overhead_pct", **bench_audit()})))
    elif "--tiered-bench" in sys.argv[1:]:
        # tiered beyond-RAM leg only (`make tiered` smoke / operators):
        # 10x-over-budget table under Zipf, reports hot-tier hit rate
        print(json.dumps(_single_leg_result(
            {"metric": "tiered_hot_hit_rate", **bench_tiered()})))
    elif "--query-bench" in sys.argv[1:]:
        # query-plane leg only (`make query-bench` / CI `query` job):
        # tiered cold-scan QPS/p99 with the no-promotion proof, plus
        # replica-served query QPS/p99 with zero primary dispatches
        print(json.dumps(_single_leg_result(
            {"metric": "query_qps_replica", **bench_query()})))
    elif "--autopilot-bench" in sys.argv[1:]:
        # fleet-autopilot leg only (`make autopilot` drill / operators):
        # Zipf hotspot shift -> time-to-split, p99 recovery, acked-Add
        # conservation across the autopilot's own topology change
        print(json.dumps(_single_leg_result(
            {"metric": "autopilot_time_to_split_seconds",
             **bench_autopilot()})))
    elif "--overload-bench" in sys.argv[1:]:
        # overload-survival leg only (`make overload` drill / operators):
        # train-while-serve storm with a stalled shard; reports shed
        # rate, per-lane p99s, retry-budget denials, acked-Add loss
        print(json.dumps(_single_leg_result(
            {"metric": "overload_serving_get_p99_ms",
             **bench_overload()})))
    elif "--autotune-bench" in sys.argv[1:]:
        # self-tuning A/B only (`make autotune-bench` / CI `autotune`
        # job): hand-tuned-best static posture vs the KnobController on
        # the identical storm, diffed through --compare machinery with
        # the same-env refusal armed; the tuner's audit trail lands in
        # BENCH_autotune_flight.jsonl
        print(json.dumps(_single_leg_result(
            {"metric": "autotune_adds_per_sec", **bench_autotune()})))
    elif "--compare" in sys.argv[1:]:
        # regression diff of two result files (CI runs non-blocking)
        sys.exit(_run_compare(sys.argv))
    else:
        shards = _parse_shards_arg(sys.argv[1:])
        if shards is not None:
            # sharded-tier scaling run only: spin a local ShardGroup and
            # report aggregate + per-shard throughput vs single-server
            print(json.dumps(_single_leg_result(
                {"metric": "sharded_row_adds_per_sec",
                 **bench_sharded(shards)})))
        else:
            main()
