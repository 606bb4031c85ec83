#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the main path starts on the chip.

One process holds the chip for the whole run and drives the normal entry
points (``mv.init`` -> ``create_table`` -> updater -> dispatcher;
``mv.serve`` -> ``mv.remote_connect``) at the full width of upstream's
own test shapes, with seeded random weights (it measures nothing: the
benchmark is ``benchmark/run.py``):

1. kernels   1,000,000 x 50 float32 MatrixTable: the Pallas row gather and
             scatter-add on its device state, then Add (duplicate ids),
             device Add and Get through the dispatcher, against numpy; then
             the same on 300,000 x 300 (rows of three lane tiles), so a
             width that stops working on the chip fails here. Each reports
             which program served its row launches.
   keyed     (one chip) the keyed FTRL table at the benchmark's key space,
             882,774,573 keys of ``(z, n)``: six Adds of 111,000 keys
             through the dispatcher, whose program steps the rows of 128 its
             keys live in inside the Pallas row kernel (PR 49), against the
             same Adds by XLA's gathers and scatters on a bare state: ``n``
             equal in every bit, ``z`` inside the benchmark reference's
             tolerance (the largest error printed), and no entry outside
             the rows named changed on either side.
2. trainer   word2vec PSTrainer, 100,000 x 128, three submissions of
             64 x 8,192 Zipf tokens through the fused device transaction.
3. server    ``mv.serve`` on the trainer's tables; ONE child process (pinned
             to the CPU, never touches a JAX backend) connects and checks an
             Add, a Get and a top-k query against numpy.
4. four chips (when JAX reports >= 4 devices) phases 1-2 again on a
             four-device mesh, shards checked per device: the tables' row
             Adds take the Pallas kernel on every shard's block, ids routed
             to their owners (``pallas_scatter: true``, launches counted by
             path); the bare kernels, which take one device's array, are
             left to the one-chip pass.

It fails (non-zero, no result line) without a TPU, sets no platform in code,
and catches no phase's failure: the first one is the exit code. Every line it
prints is JSON; times are set-up information (compile included), never a
metric. The last line is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py            # on the machine that holds the chip
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

# flags are sticky across mv.shutdown(): every init names all it relies on
_INIT_FLAGS = dict(sync=False, ssp_staleness=-1, deterministic=False,
                   ma=False, ps_role="default", updater_type="default",
                   local_workers=1, remote_workers=1, mesh_axes="server")


def emit(**fields):
    print(json.dumps(fields), flush=True)


class CompileClock:
    """Backend-compile seconds as JAX itself reports them, per phase."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration

    def take(self):
        seconds, self.seconds = self.seconds, 0.0
        return round(seconds, 2)


def scatter_facts(table):
    """(uses the Pallas scatter, interpret mode or None) as the table says."""
    server = table._server_table
    return server.plan.kernel, server.plan.interpret


def zipf_setup(vocab, seed):
    """A synthetic corpus: a dictionary with counts ~
    1e7/rank and a seeded token draw through its inverse CDF."""
    from multiverso_tpu.models.vocab import Dictionary
    counts = np.maximum((1e7 / np.arange(1, vocab + 1)).astype(np.int64), 5)
    d = Dictionary()
    d.words = [f"w{i}" for i in range(vocab)]
    d.word2id = {}
    d.counts = counts
    cdf = np.cumsum(counts.astype(np.float64) / counts.sum())
    rng = np.random.default_rng(seed)
    return d, lambda n: np.searchsorted(cdf, rng.random(n)).astype(np.int32)


def check_shards(table, devices):
    """Each table's rows sit in equal parts on ``devices`` distinct devices."""
    data = table._server_table.data
    shards = data.addressable_shards
    placed = {s.device for s in shards}
    assert len(shards) == len(placed) == devices, (len(shards), placed)
    rows = {s.data.shape[0] for s in shards}
    assert rows == {data.shape[0] // devices}, (rows, data.shape)
    return {"devices": sorted(str(d) for d in placed),
            "rows_per_device": rows.pop()}


# -- phase 1 -----------------------------------------------------------------

def phase_kernels(rows, cols, n_ids, expect_scatter, seed=0):
    """Row kernels bare on the table's device state (where the table uses
    them and lives on one device), then Add with duplicate ids, device Add
    and Get through the dispatcher; every result against a numpy mirror of
    the table."""
    import jax

    import multiverso_tpu as mv
    from multiverso_tpu.ops import pallas_rows

    from multiverso_tpu.dashboard import Dashboard

    launch_counters = [f"ROW_LAUNCH_{path}_{op}" for op in ("ADD", "GET")
                       for path in ("PALLAS", "XLA")]
    launched = {n: Dashboard.counter_value(n) for n in launch_counters}
    rng = np.random.default_rng(seed)
    mirror = rng.standard_normal((rows, cols)).astype(np.float32)
    table = mv.create_table("matrix", rows, cols, np.float32,
                            init_value=mirror)
    server = table._server_table
    pallas, interpret = scatter_facts(table)
    assert (pallas, interpret) == tuple(expect_scatter), (pallas, interpret)
    checks = {"table": f"{rows}x{cols}", "padded_cols": server.padded_cols,
              "row_group": pallas_rows.ROW_GROUP,
              "pallas_scatter": pallas, "interpret": interpret}

    ids = rng.choice(rows, n_ids, replace=False).astype(np.int32)
    if pallas:
        platforms = {d.platform for d in server.data.devices()}
        assert interpret == pallas_rows.interpret_for(platforms.pop())
    if pallas and len(server.data.devices()) == 1:
        # unique live ids plus sentinel pads (zero deltas) up to a whole
        # number of row groups
        pad = pallas_rows.ROW_GROUP - n_ids % pallas_rows.ROW_GROUP
        ids_p = np.concatenate(
            [ids, np.full(pad, server.sentinel_row, np.int32)])
        got = np.asarray(pallas_rows.gather_rows(
            server.data, ids_p, interpret=interpret))
        np.testing.assert_array_equal(got[:n_ids, :cols], mirror[ids])
        np.testing.assert_array_equal(got[n_ids:], 0.0)
        delta = np.zeros((n_ids + pad, server.padded_cols), np.float32)
        delta[:n_ids, :cols] = rng.standard_normal((n_ids, cols))
        # the kernel donates its table: no request is in flight here
        server.data = pallas_rows.scatter_add_rows(
            server.data, ids_p, delta, interpret=interpret)
        mirror[ids] += delta[:n_ids, :cols]
        got = np.asarray(pallas_rows.gather_rows(
            server.data, ids_p, interpret=interpret))
        np.testing.assert_array_equal(got[:n_ids, :cols], mirror[ids])
        np.testing.assert_array_equal(got[n_ids:], 0.0)
        checks["bare_kernels"] = f"gather+scatter_add, {n_ids}+{pad} ids"

    # Add with duplicate ids: the dedup branch where the kernel serves
    dup_ids = np.concatenate([ids, ids[: n_ids // 4]])
    vals = rng.standard_normal((len(dup_ids), cols)).astype(np.float32)
    table.add(vals, row_ids=dup_ids)
    np.add.at(mirror, dup_ids, vals)
    np.testing.assert_allclose(table.get(ids), mirror[ids], rtol=1e-5,
                               atol=1e-6)
    # device Add: the delta never leaves the device
    dev_vals = rng.standard_normal((n_ids, cols)).astype(np.float32)
    table.wait(table.add_device_async(jax.device_put(dev_vals), ids))
    mirror[ids] += dev_vals
    np.testing.assert_allclose(table.get(ids), mirror[ids], rtol=1e-5,
                               atol=1e-6)
    # rows no request named are bit-equal to their initial values
    others = np.setdiff1d(rng.choice(rows, n_ids, replace=False), ids)
    np.testing.assert_array_equal(table.get(others), mirror[others])
    checks["table_ops"] = (f"add {len(dup_ids)} ids ({n_ids // 4} "
                           f"duplicates), add_device {n_ids}, get")
    # which program served the row launches: both Adds the kernel's where
    # the table says it uses it, every Get XLA's gather
    checks["row_launches"] = {n: Dashboard.counter_value(n) - was
                              for n, was in launched.items()}
    served = "PALLAS" if pallas else "XLA"
    assert checks["row_launches"][f"ROW_LAUNCH_{served}_ADD"] == 2, checks
    assert checks["row_launches"]["ROW_LAUNCH_XLA_GET"] == 3, checks
    return table, checks


# -- phase 1b: the keyed FTRL table ---------------------------------------------

_FTRL_OPT = dict(alpha=0.1, beta=1.0, lambda1=1.0, lambda2=1.0)


def phase_keyed(size, n_keys, adds, expect_kernel=(True, False), seed=0):
    """``adds`` keyed FTRL Adds of ``n_keys`` keys (half of them in a dense
    head, so that rows are shared; a few named twice) on a table of
    ``size`` keys through the dispatcher, against the same Adds by the
    table's XLA program (`rows=None`: gathers, the rule, scatters) on a bare
    state of the same seeded values. The chip holds one state at a time:
    what is compared is every entry of every row of 128 an Add names, and
    the count of entries that differ from the seeded state anywhere, which
    must be the count inside those rows."""
    import jax
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from benchmark import common
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.tables import ftrl_table as ft
    from multiverso_tpu.tables.device_ids import live_slots
    from multiverso_tpu.utils import next_pow2

    ref = common.load_module("reference", "logreg-ftrl-criteo-tb")
    rng = np.random.default_rng(seed)
    piece = min(1 << 25, size)
    padded = -(-(size + 1) // 1024) * 1024

    @jax.jit
    def seeded(lo):
        keys = lo + jnp.arange(piece, dtype=jnp.int32)
        z, n = ref.init_zn(keys, seed, jnp)
        # the entries past the keys (the scratch key's) start as zeros
        return jnp.where(keys < size, z, 0.0), jnp.where(keys < size, n, 0.0)

    def source(lo, count):
        return tuple(s[:count] for s in seeded(jnp.int32(lo)))

    @jax.jit
    def changed_in(z, n, at, lo):
        """Entries of ``z`` and ``n`` from ``lo`` on, in the piece at
        ``at``, whose bits are not the seeded state's."""
        want_z, want_n = seeded(at)
        counted = at + jnp.arange(piece, dtype=jnp.int32) >= lo

        def differ(state, want):
            return jnp.sum(counted & (
                jax.lax.bitcast_convert_type(
                    jax.lax.dynamic_slice(state, (at,), (piece,)), jnp.int32)
                != jax.lax.bitcast_convert_type(want, jnp.int32)))

        return differ(z, want_z) + differ(n, want_n)

    def changed(z, n):
        # the last piece is laid back to end with the state and counts
        # from where the one before it ended
        return sum(int(changed_in(z, n, jnp.int32(min(lo, padded - piece)),
                                  jnp.int32(lo)))
                   for lo in range(0, padded, piece))

    ops = []
    for _ in range(adds):
        keys = np.concatenate([
            rng.integers(0, min(size, 1 << 17), n_keys // 2),
            rng.integers(0, size, n_keys - n_keys // 2)]).astype(np.int32)
        ops.append((keys, ref.to_float(ref.grad_k(rng, n_keys))))
    rows = np.unique(np.concatenate(
        [keys >> 7 for keys, _ in ops] + [[size >> 7]])).astype(np.int32)
    take = jax.jit(lambda s, r: s.reshape(-1, 128)[r])

    # XLA's path first, on a bare state (its program is the table's own)
    z = jnp.zeros(padded, jnp.float32)
    n = jnp.zeros(padded, jnp.float32)
    for lo in range(0, size, piece):
        z, n = ft._write_piece(z, n, *source(lo, min(piece, size - lo)),
                               jnp.int32(lo))
    _, add = ft._make_programs(scratch=size, **_FTRL_OPT)
    for keys, grad in ops:
        bucket = max(next_pow2(len(keys) + 1), 128)
        ids = np.full(bucket, size, np.int32)
        ids[:len(keys)] = keys
        z, n, _ = add(z, n, jnp.asarray(ids), jnp.asarray(grad),
                      live=live_slots(len(keys), bucket), rows=None)
    want = [np.asarray(take(s, rows)) for s in (z, n)]
    changed_xla = changed(z, n)
    del z, n

    launched = Dashboard.counter_value("ROW_LAUNCH_PALLAS_ADD")
    table = mv.create_table("ftrl", size, init=source, **_FTRL_OPT)
    plan = table._server_table.plan
    assert (plan.kernel, plan.interpret) == tuple(expect_kernel), plan.why
    for keys, grad in ops:
        table.add(keys, grad)
    state = [table.get_state_device(name) for name in "zn"]
    got = [np.asarray(take(s, rows)) for s in state]
    changed_kernel = changed(*state)
    row_keys = (rows.astype(np.int64)[:, None] * 128
                + np.arange(128)).reshape(-1)
    first = [np.where(row_keys < size, s, 0).astype(np.float32)
             for s in ref.init_zn(np.minimum(row_keys, size - 1), seed)]
    in_rows = [sum(ref.n_mismatch(side[i].reshape(-1), first[i])
                   for i in range(2)) for side in (want, got)]
    steps = np.zeros(len(row_keys), np.int64)
    for keys, _ in ops:
        named = np.unique(keys)     # a key named twice takes one step
        steps[np.searchsorted(rows, named >> 7) * 128 + (named & 127)] += 1
    checks = {
        "table": f"ftrl {size} keys", "adds": adds, "keys_an_add": n_keys,
        "rows_named": len(rows), "kernel": plan.why,
        "pallas_adds": Dashboard.counter_value("ROW_LAUNCH_PALLAS_ADD")
        - launched,
        "n_entries_differ": ref.n_mismatch(got[1], want[1]),
        "z_entries_differ": ref.n_mismatch(got[0], want[0]),
        "z_max_error_of_allowed": float(ref.z_error(
            got[0].reshape(-1), want[0].reshape(-1), steps)),
        "changed_outside_the_rows_named": [changed_xla - in_rows[0],
                                           changed_kernel - in_rows[1]]}
    assert checks["pallas_adds"] == adds, checks
    assert checks["n_entries_differ"] == 0, checks
    assert checks["z_max_error_of_allowed"] <= 1.0, checks
    assert checks["changed_outside_the_rows_named"] == [0, 0], checks
    assert in_rows[1] > adds * n_keys // 2, checks
    return table, checks


# -- phase 2 -----------------------------------------------------------------

def phase_trainer(vocab, dim, batch_pairs, block_tokens, group, submissions,
                  expect_scatter, seed=0):
    """A few PSTrainer submissions through the fused device transaction."""
    from multiverso_tpu.models.word2vec import PSTrainer, Word2VecConfig

    dictionary, draw = zipf_setup(vocab, seed)
    config = Word2VecConfig(vocab_size=vocab, dim=dim, window=5, negatives=5,
                            batch_pairs=batch_pairs, sample=0.0,
                            neg_sharing=8)
    trainer = PSTrainer(config, dictionary)
    assert trainer._can_transact(), "the fused transaction must engage"
    for table in (trainer.input_table, trainer.output_table):
        facts = scatter_facts(table)
        assert facts == tuple(expect_scatter), facts
    w_in0 = trainer.input_table.get()
    w_out0 = trainer.output_table.get()
    assert not w_out0.any()

    blocks = [draw(block_tokens * group) for _ in range(submissions)]
    losses, pending = [], None
    for block in blocks:
        submitted = trainer.submit_block(block)
        if pending is not None:
            losses.append(trainer.finish_block(pending))
        pending = submitted
    losses.append(trainer.finish_block(pending))
    assert len(losses) == submissions and np.isfinite(losses).all(), losses

    w_in1 = trainer.input_table.get()
    w_out1 = trainer.output_table.get()
    assert w_in1.shape == w_out1.shape == (vocab, dim)
    assert np.isfinite(w_in1).all() and np.isfinite(w_out1).all()
    touched = np.zeros(vocab, bool)
    touched[np.concatenate(blocks)] = True
    moved_in = (w_in1 != w_in0).any(axis=1)
    moved_out = w_out1.any(axis=1)
    # rows outside the touched set are bit-equal to their initial values
    np.testing.assert_array_equal(w_in1[~touched], w_in0[~touched])
    # every corpus token is some center's context, so its output row moved;
    # an input row moves once its contexts' output rows have left zero
    assert moved_out[touched].all()
    assert moved_in[touched].mean() > 0.5, moved_in[touched].mean()
    # beyond the corpus only drawn negatives move, a pool per submission
    extra = int(moved_out[~touched].sum())
    assert extra <= submissions * trainer.neg_pool, extra
    checks = {"tables": f"2 x {vocab}x{dim}", "group": group,
              "tokens_per_submission": block_tokens * group,
              "submissions": submissions,
              "losses": [round(float(x), 4) for x in losses],
              "rows_touched": int(touched.sum()),
              "input_rows_moved": int(moved_in.sum()),
              "output_rows_moved": int(moved_out.sum()),
              "untouched_rows_bit_equal": True}
    return trainer, w_in1, checks


# -- phase 3 -----------------------------------------------------------------

def phase_server(table, weights, n_rows, k, n_queries, seed=0):
    """Serve ``table`` (host copy ``weights``) and let one child process, off
    the chip, check an Add, a Get and a query against numpy."""
    import multiverso_tpu as mv
    from multiverso_tpu.dashboard import Dashboard

    names = ("SERVER_PROCESS_ADD_MSG", "SERVER_PROCESS_GET_MSG",
             "SERVER_PROCESS_QUERY_MSG")
    before = [Dashboard.histogram(n).count for n in names]
    rng = np.random.default_rng(seed)
    endpoint = mv.serve("127.0.0.1:0")
    with tempfile.TemporaryDirectory(prefix="mv_smoke_") as tmp:
        path = os.path.join(tmp, "case.npz")
        np.savez(path, weights=weights,
                 ids=rng.choice(len(weights), n_rows, replace=False)
                 .astype(np.int32),
                 delta=rng.standard_normal(
                     (n_rows, weights.shape[1])).astype(np.float32),
                 vecs=rng.standard_normal(
                     (n_queries, weights.shape[1])).astype(np.float32))
        # a chip belongs to one process: the child's platform is written,
        # not inherited, and it must finish without starting a backend
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--client", endpoint,
             str(table.table_id), path, str(k)],
            env=env, stdout=subprocess.PIPE, text=True, timeout=300)
    assert child.returncode == 0, f"client exited {child.returncode}"
    report = json.loads(child.stdout.strip().splitlines()[-1])
    assert report["backends_initialized"] is False, report
    served = [Dashboard.histogram(n).count - b
              for n, b in zip(names, before)]
    assert served[0] >= 1 and served[1] >= 2 and served[2] >= 1, served
    report["served"] = dict(zip(names, served))
    return report


def client_main(endpoint, table_id, path, k):
    """The child of phase 3: Add, Get and query over the wire, each against
    numpy; reports whether this process ever started a JAX backend."""
    import jax._src.xla_bridge as xla_bridge

    import multiverso_tpu as mv

    case = np.load(path)
    weights, ids, delta, vecs = (case[n] for n in
                                 ("weights", "ids", "delta", "vecs"))
    client = mv.remote_connect(endpoint)
    table = client.table(table_id)
    np.testing.assert_array_equal(table.get(ids), weights[ids])
    table.add(delta, row_ids=ids)
    weights[ids] += delta
    np.testing.assert_allclose(table.get(ids), weights[ids], rtol=1e-6,
                               atol=1e-7)

    got_ids, got_scores = mv.query(table, vecs, k, metric="dot")
    assert got_ids.shape == got_scores.shape == (len(vecs), k)
    exact = vecs.astype(np.float64) @ weights.astype(np.float64).T
    # the engine scores at float32 (Precision.HIGHEST): twice the bound of
    # a float32 sum of `cols` terms (cols * 2^-24 * |q|.|w|), over the
    # largest |q|.|w| of the table. One bfloat16 pass, a TPU's default
    # matmul, rounds each factor to 2^-8 and lands far outside it
    tol = (weights.shape[1] * 2.0 ** -23
           * (np.abs(vecs) @ np.abs(weights).T).max(axis=1))
    want_ids = np.lexsort((np.broadcast_to(np.arange(len(weights)),
                                           exact.shape), -exact))[:, :k]
    kth = np.take_along_axis(exact, want_ids[:, -1:], axis=1)
    picked = np.take_along_axis(exact, got_ids, axis=1)
    assert all(len(set(row)) == k for row in got_ids.tolist())
    assert (np.diff(got_scores, axis=1) <= 0).all()
    assert (np.abs(got_scores - picked) <= tol[:, None]).all()
    assert (picked >= kth - 2 * tol[:, None]).all()
    client.close()
    emit(add_rows=len(ids), get_rows=len(ids),
         query=f"{len(vecs)} x top-{k} dot over {weights.shape}",
         query_ids_equal_numpy=bool((got_ids == want_ids).all()),
         query_score_error_over_bound=float(
             (np.abs(got_scores - picked) / tol[:, None]).max()),
         backends_initialized=xla_bridge.backends_are_initialized())


# -- set-up information --------------------------------------------------------

def host_device_costs(repeats=20):
    """Median microseconds of the host<->device operations the design rules
    in docs/DESIGN.md section 3 are argued from: submitting a trivial jitted
    dispatch (the call returns) and completing it, uploading 4 floats, and
    fetching a scalar that is already computed."""
    import jax

    step = jax.jit(lambda x: x + 1.0)
    host = np.zeros(4, np.float32)
    x = jax.device_put(host)
    step(x)[0].block_until_ready()  # compile the dispatch and the index

    def median_us(timed, prepare=lambda: None, settle=lambda out: None):
        samples = []
        for _ in range(repeats):
            arg = prepare()
            t0 = time.perf_counter()
            out = timed(arg)
            samples.append(time.perf_counter() - t0)
            settle(out)
        return round(float(np.median(samples)) * 1e6, 1)

    return {"repeats": repeats,
            "jit_submit_us": median_us(
                lambda _: step(x), settle=lambda out: out.block_until_ready()),
            "jit_dispatch_us": median_us(
                lambda _: step(x).block_until_ready()),
            "device_put_4_floats_us": median_us(
                lambda _: jax.device_put(host).block_until_ready()),
            # a fresh device value each time: jax keeps a fetched copy
            "scalar_fetch_us": median_us(
                np.asarray, prepare=lambda: step(x)[0].block_until_ready())}


# -- driver ------------------------------------------------------------------

def run_mesh(devices, clock, sizes, with_server):
    """Phases 1-2 (and 3) on a ``devices``-device table mesh."""
    import multiverso_tpu as mv

    # the kernel serves every table's row Adds, on one device or on every
    # shard's block
    expect = (True, False)
    mv.init(mesh_shape=str(devices), **_INIT_FLAGS)
    assert mv.num_servers() == devices, mv.num_servers()

    def report(phase, t0, checks):
        emit(phase=phase, mesh_devices=devices, checks=checks,
             setup={"seconds": round(time.perf_counter() - t0, 1),
                    "compile_seconds": clock.take()})

    t0 = time.perf_counter()
    table, checks = phase_kernels(*sizes["kernels"], expect_scatter=expect)
    if devices > 1:
        checks["shards"] = check_shards(table, devices)
    report("kernels", t0, checks)

    t0 = time.perf_counter()
    _, checks = phase_kernels(*sizes["kernels_wide"], expect_scatter=expect)
    report("kernels_wide", t0, checks)

    if devices == 1:
        # the lane kernel serves a table on one device alone
        t0 = time.perf_counter()
        report("keyed", t0, phase_keyed(*sizes["keyed"])[1])

    t0 = time.perf_counter()
    trainer, w_in, checks = phase_trainer(*sizes["trainer"],
                                          expect_scatter=expect)
    if devices > 1:
        checks["shards"] = check_shards(trainer.input_table, devices)
        check_shards(trainer.output_table, devices)
    report("trainer", t0, checks)

    if with_server:
        t0 = time.perf_counter()
        report("server", t0, phase_server(trainer.input_table, w_in,
                                          *sizes["server"]))
    mv.shutdown()
    return w_in


FULL_SIZES = {
    # rows, cols, ids             (upstream's Test/test_matrix_perf.cpp)
    "kernels": (1_000_000, 50, 1000),
    # the word-embedding width: three lane tiles a row (384 lanes)
    "kernels_wide": (300_000, 300, 1000),
    # keys, keys an Add, Adds   (the benchmark's `ftrlctr.step-keys`)
    "keyed": (882_774_573, 111_000, 6),
    # vocab, dim, batch_pairs, block_tokens, group, submissions
    "trainer": (100_000, 128, 32768, 8192, 64, 3),
    # rows per Add/Get, k, queries
    "server": (1024, 10, 16),
}


def main():
    import jax

    import multiverso_tpu as mv
    from multiverso_tpu.utils.quantization import native_available

    cache_dir = mv.configure_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {len(devices)} "
                 f"{devices[0].platform} device(s)")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit(platform=device["platform"], device_kind=device["kind"],
         device_count=device["count"], jax=jax.__version__,
         compile_cache_dir=cache_dir,
         wire_codec="native" if native_available() else "numpy")
    clock = CompileClock()
    emit(setup="host<->device medians, not a metric",
         **host_device_costs())

    w_one = run_mesh(1, clock, FULL_SIZES, with_server=True)
    if len(devices) >= 4:
        w_four = run_mesh(4, clock, FULL_SIZES, with_server=False)
        # same seeds, same blocks: the fused transaction over sharded
        # tables (XLA's partitioned scatter inside the trainer's jit) and
        # over one chip's (the kernel) must land on the same embeddings, up
        # to the order of float32 sums
        diff = float(np.abs(w_four - w_one).max())
        assert diff <= 1e-2 * float(np.abs(w_one).max()), diff
        emit(four_chip="ran", max_abs_diff_vs_one_chip=diff)
    else:
        emit(four_chip=f"not run, {len(devices)} device(s)")
    emit(ok=True, device=device)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--client":
        client_main(sys.argv[2], int(sys.argv[3]), sys.argv[4],
                    int(sys.argv[5]))
    else:
        main()
