"""Child process for tests/test_multihost.py: one JAX process of a
2-process lockstep PS world (reference analog: one MPI rank of the
multi-rank deployment, ``src/zoo.cpp:73-145``).

Usage: python multihost_child.py <rank> <world> <coord_port> <ctl_port>
       <scenario>

The parent sets JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=<n> so the two
processes form a 2n-device global mesh; MatrixTable/ArrayTable rows then
shard across BOTH processes' devices — the capability this validates is
exactly "tables bigger than one host".
"""

import os
import sys


def main() -> int:
    rank = int(sys.argv[1])
    world = int(sys.argv[2])
    coord_port = sys.argv[3]
    ctl_port = sys.argv[4]
    scenario = sys.argv[5]

    import jax
    from multiverso_tpu.runtime.multihost import init_distributed_cpu
    init_distributed_cpu(f"127.0.0.1:{coord_port}", world, rank)

    import numpy as np
    import multiverso_tpu as mv

    flags = dict(local_workers=2 if scenario in ("bsp2", "ma") else 1,
                 # remote slot expectations are part of num_workers and
                 # must MATCH across processes (table worker dims shape
                 # the collective programs)
                 remote_workers=1 if scenario == "remote" else 0,
                 multihost_endpoint=f"127.0.0.1:{ctl_port}",
                 ssp_staleness=1 if scenario == "ssp" else -1,
                 ma=scenario == "ma",
                 # flagmismatch: rank 1 deliberately diverges on `sync` —
                 # bring-up must fatal NAMING the flag, not desync later
                 sync=(scenario in ("bsp", "bsp2")
                       or (scenario == "flagmismatch" and rank == 1)))
    mv.init(**flags)
    assert jax.device_count() > jax.local_device_count(), \
        "mesh does not span processes"

    if scenario == "async":
        run_async(mv, np, rank, world)
    elif scenario == "bsp":
        run_bsp(mv, np, rank, world)
    elif scenario == "checkpoint":
        run_checkpoint(mv, np, rank, world)
    elif scenario == "w2v":
        run_w2v(mv, np, rank, world)
    elif scenario == "bsp2":
        run_bsp2(mv, np, rank, world)
    elif scenario == "remote":
        run_remote(mv, np, rank, world)
    elif scenario == "crash":
        run_crash(mv, np, rank, world)
    elif scenario == "kv":
        run_kv(mv, np, rank, world)
    elif scenario == "ssp":
        run_ssp(mv, np, rank, world)
    elif scenario == "asgd":
        run_asgd(mv, np, rank, world)
    elif scenario == "ma":
        run_ma(mv, np, rank, world)
    elif scenario == "leadercrash":
        run_leadercrash(mv, np, rank, world)
    elif scenario == "flagmismatch":
        run_flagmismatch(mv, np, rank, world)
    elif scenario == "badreq":
        run_badreq(mv, np, rank, world)
    elif scenario == "ctrlperf":
        run_ctrlperf(mv, np, rank, world)
    elif scenario == "namedtxn":
        run_namedtxn(mv, np, rank, world)
    else:
        raise SystemExit(f"unknown scenario {scenario}")
    mv.shutdown()
    print(f"MULTIHOST_CHILD_OK rank={rank} scenario={scenario}", flush=True)
    return 0


def run_async(mv, np, rank: int, world: int) -> None:
    """Plain async: every rank's sync add is visible after a barrier."""
    rows, cols = 64, 24
    mat = mv.create_table("matrix", num_row=rows, num_col=cols)
    arr = mv.create_table("array", size=100)
    with mv.worker(0):
        my_rows = np.arange(rank, rows, world, dtype=np.int32)
        mat.add(np.full((len(my_rows), cols), rank + 1.0, np.float32),
                row_ids=my_rows)  # sync add: applied when it returns
        arr.add(np.full(100, float(rank + 1), np.float32))
    mv.process_barrier()
    with mv.worker(0):
        got = mat.get()
        expect = np.zeros((rows, cols), np.float32)
        for r in range(world):
            expect[np.arange(r, rows, world)] = r + 1.0
        np.testing.assert_allclose(got, expect)
        # row-subset get crossing both processes' shards
        sel = np.array([0, 1, rows - 1], np.int32)
        np.testing.assert_allclose(mat.get(sel), expect[sel])
        np.testing.assert_allclose(
            arr.get(), np.full(100, sum(range(1, world + 1)), np.float32))


def run_checkpoint(mv, np, rank: int, world: int) -> None:
    """Live snapshot + live restore through the lockstep dispatcher: the
    leader's CheckpointDriver broadcasts the collective store read and
    the restore bytes; followers participate via replay only (a follower
    driving the checkpoint is rejected — tested too)."""
    import tempfile

    from multiverso_tpu.checkpoint import CheckpointDriver

    rows, cols = 48, 16
    mat = mv.create_table("matrix", num_row=rows, num_col=cols)
    with mv.worker(0):
        mat.add(np.full((rows, cols), float(rank + 1), np.float32))
    mv.process_barrier()
    base = float(sum(range(1, world + 1)))

    driver = None
    if rank == 0:
        driver = CheckpointDriver([mat], tempfile.mkdtemp(prefix="mvckpt_"))
        driver.snapshot()
    mv.process_barrier()

    with mv.worker(0):
        mat.add(np.full((rows, cols), 10.0, np.float32))  # every rank adds
    mv.process_barrier()
    with mv.worker(0):
        np.testing.assert_allclose(
            mat.get(),
            np.full((rows, cols), base + 10.0 * world, np.float32))
    mv.process_barrier()

    if rank == 0:
        assert driver.restore(), "no snapshot found"
    mv.process_barrier()
    with mv.worker(0):
        np.testing.assert_allclose(
            mat.get(), np.full((rows, cols), base, np.float32),
            err_msg="restore did not rebuild pre-snapshot state")
    mv.process_barrier()


def run_w2v(mv, np, rank: int, world: int) -> None:
    """A REAL app rides the multihost mesh: each process's PSTrainer
    trains its corpus shard against ONE pair of globally-sharded
    embedding tables (the reference's multi-rank WordEmbedding shape).
    Tables are created collectively by constructing identical trainers;
    the staged host pull/push path forwards through the leader."""
    from multiverso_tpu.models.vocab import Dictionary
    from multiverso_tpu.models.word2vec import PSTrainer, Word2VecConfig

    vocab = 120
    rng = np.random.default_rng(0)  # same corpus plan on every rank
    corpus = rng.integers(0, vocab, size=4000).astype(np.int32)
    d = Dictionary()
    d.words = [f"w{i}" for i in range(vocab)]
    d.word2id = {w: i for i, w in enumerate(d.words)}
    d.counts = np.maximum(np.bincount(corpus, minlength=vocab), 1)
    config = Word2VecConfig(vocab_size=vocab, dim=16, window=2, negatives=3,
                            batch_pairs=512, sample=0.0)
    trainer = PSTrainer(config, d)  # collective table creation
    # async multihost worlds must engage the NAMED fused-transaction path
    # (one lockstep descriptor per block, payload = program name + host
    # ids; table bytes ride the mesh) — not the staged host fallback
    assert trainer._can_transact(), "named-txn path not engaged"
    shard = corpus[rank::world]
    with mv.worker(0):
        for i in range(0, len(shard), 500):
            pend = trainer.submit_block(shard[i:i + 500])
            assert pend is None or "txn" in pend, sorted(pend)
            loss = trainer.finish_block(pend)
            assert np.isfinite(loss), loss
    mv.process_barrier()
    with mv.worker(0):
        emb = trainer.embeddings()
        assert emb.shape == (vocab, config.dim)
        assert np.isfinite(emb).all()
        # the shared word-count table saw EVERY rank's words
        total = trainer.count_table.get(0)
    expected = sum(len(corpus[r::world]) for r in range(world))
    assert total == expected, (total, expected)
    mv.process_barrier()


def run_asgd(mv, np, rank: int, world: int) -> None:
    """The ResNet-ASGD workflow shape across processes: each rank's
    PytreeWorkerSync pushes model deltas into ONE ArrayTable sharded over
    both processes' devices and pulls the merged model back (device IO
    auto-falls back to the host path under multihost). Both ranks' SGD
    work must land in the merged tree."""
    import jax.numpy as jnp

    from multiverso_tpu.ext import PytreeParamManager

    params = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((3,))}
    pm = PytreeParamManager(params)  # collective table creation
    view = pm.worker_view(device=True)  # multihost: host path, same API
    # every view must capture its zero baseline BEFORE any rank pushes:
    # a late view would absorb the peer's deltas into its baseline and
    # push short (confirmed flaky under injected scheduling skew)
    mv.process_barrier()
    with mv.worker(0):
        for step in range(3):
            new = {"w": params["w"] + (rank + 1.0),
                   "b": params["b"] + 0.5}
            params = view.sync(new)
    mv.process_barrier()
    with mv.worker(0):
        merged = view.sync(params)  # no-op delta: pull the global state
    # every rank contributed 3 steps of +(rank+1) on w and +0.5 on b;
    # syncs interleave, but the FINAL merged sums are exact
    want_w = 3.0 * sum(range(1, world + 1))
    want_b = 0.5 * 3 * world
    np.testing.assert_allclose(np.asarray(merged["w"]), want_w, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(merged["b"]), want_b, rtol=1e-5)
    mv.process_barrier()


def run_ssp(mv, np, rank: int, world: int) -> None:
    """SSP across processes: with staleness=1, every worker's round-i Get
    must reflect at least round i-1 of EVERY worker's Adds (gating runs
    on the leader; followers' gets forward and wait like any other
    gated mode)."""
    from multiverso_tpu.config import get_flag

    rows, cols, rounds = 16, 4, 5
    s = int(get_flag("ssp_staleness"))  # main() set it; don't drift
    assert s >= 0, "ssp scenario requires ssp_staleness"
    mat = mv.create_table("matrix", num_row=rows, num_col=cols)
    with mv.worker(0):
        for i in range(1, rounds + 1):
            mat.add(np.full((rows, cols), 1.0, np.float32))
            got = mat.get()
            lo = i + max(i - s, 0) * (world - 1)
            hi = rounds * world
            assert lo <= got[0, 0] <= hi, (rank, i, got[0, 0], lo, hi)
        mat.finish_train()
    mv.process_barrier()


def run_kv(mv, np, rank: int, world: int) -> None:
    """DeviceKV (the lightLDA-shaped sparse store) across processes: the
    shard_map hash kernels run as global collectives, and GROWTH — a
    collective rebuild + replay — happens in lockstep on every process."""
    kv = mv.create_table("kv", np.int32, capacity=64)  # tiny: forces growth
    cap0 = kv._server_table.capacity  # per-shard minimums inflate this
    n_keys = cap0  # enough unique keys that load>0.5 forces a rebuild
    with mv.worker(0):
        # overlapping keys accumulate across ranks
        kv.add(list(range(n_keys)), [rank + 1] * n_keys)
    mv.process_barrier()
    with mv.worker(0):
        got = kv.get([0, n_keys // 2, n_keys - 1])
        want = sum(range(1, world + 1))
        assert [int(x) for x in got] == [want] * 3, (got, want)
        assert kv._server_table.capacity > cap0, (
            f"never grew past {cap0}")
    mv.process_barrier()


def run_ma(mv, np, rank: int, world: int) -> None:
    """Model-averaging mode (``-ma=true``: no PS at all) across processes:
    ``mv.aggregate`` must hand EVERY worker on EVERY rank the all-workers
    sum — the reference's ``MV_Aggregate``/MPI_Allreduce contract, whose
    canonical test shape is aggregate(1) == MV_Size
    (``Test/test_allreduce.cpp:13-16``). Exercises all three value shapes
    (scalar-array, host leaf list, device array) over the 2-worker x
    world grid."""
    import threading

    import jax.numpy as jnp

    workers = 2 * world
    results: dict = {}
    errors: list = []

    def work(slot: int) -> None:
        try:
            with mv.worker(slot):
                wid = rank * 2 + slot
                # the reference contract shape: aggregate(ones) == #workers
                r1 = mv.aggregate(np.ones(8, np.float32))
                # host leaf-list (a model's leaves)
                r2 = mv.aggregate([
                    np.full(3, float(wid + 1), np.float32),
                    np.ones((2, 2), np.float32)])
                # device path: local jax.Arrays hop through the control
                # plane and come back on device
                r3 = mv.aggregate(jnp.full((4,), float(wid + 1)))
                results[slot] = (r1, r2, r3)
        except Exception as exc:  # surfaced by the assert below
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(s,)) for s in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads), "aggregate worker hung"
    wid_sum = float(sum(range(1, workers + 1)))
    for slot in range(2):
        r1, r2, r3 = results[slot]
        np.testing.assert_allclose(r1, np.full(8, float(workers)))
        np.testing.assert_allclose(r2[0], np.full(3, wid_sum))
        np.testing.assert_allclose(r2[1], np.full((2, 2), float(workers)))
        import jax
        assert isinstance(r3, jax.Array), type(r3)  # device in, device out
        np.testing.assert_allclose(np.asarray(r3), np.full(4, wid_sum))
    mv.process_barrier()


def run_leadercrash(mv, np, rank: int, world: int) -> None:
    """Leader (rank 0) dies abruptly mid-run: every follower must fail
    LOUDLY within the control-plane bound — the replay loop poisons the
    rank on leader-socket EOF, so the next table op raises instead of
    hanging (round-4 verdict: the one crash mode without a loud-failure
    test)."""
    import os as _os
    import threading
    import time

    from multiverso_tpu import config as mv_config

    mat = mv.create_table("matrix", num_row=16, num_col=4)
    with mv.worker(0):
        mat.add(np.ones((16, 4), np.float32))
        mat.get()
    mv.process_barrier()
    if rank == 0:
        _os._exit(42)  # simulated leader-host failure: no goodbye
    loud_bound = float(mv_config.get_flag("multihost_timeout")) + 30.0
    deadline = time.monotonic() + loud_bound + 60.0
    while time.monotonic() < deadline:
        outcome: dict = {}

        def attempt() -> None:
            try:
                with mv.worker(0):
                    mat.add(np.ones((16, 4), np.float32))
                    mat.get()
                outcome["ok"] = True
            except BaseException as exc:  # noqa: BLE001 — loud = pass
                outcome["exc"] = exc

        t = threading.Thread(target=attempt, daemon=True)
        t.start()
        t.join(timeout=loud_bound)
        if t.is_alive():
            print("FOLLOWER_DID_NOT_DETECT_LEADER_DEATH (op hung)",
                  flush=True)
            _os._exit(1)
        if "exc" in outcome:
            mv.shutdown()  # teardown on a poisoned rank must not raise
            print("FOLLOWER_DETECTED_LEADER_DEATH "
                  f"{type(outcome['exc']).__name__}", flush=True)
            _os._exit(0)
        time.sleep(0.5)  # leader still draining; retry
    print("FOLLOWER_DID_NOT_DETECT_LEADER_DEATH (no error before deadline)",
          flush=True)
    _os._exit(1)


def run_namedtxn(mv, np, rank: int, world: int) -> None:
    """Named device transaction across processes, exactness-pinned: a
    registered two-table fused program (scaled add into both tables +
    a device reply) submitted from a FOLLOWER must update every rank's
    replica exactly and hand the origin the device reply materialized
    at replay (payload rides the mesh, never TCP)."""
    import jax
    import jax.numpy as jnp

    rows, cols = 16, 8
    a = mv.create_table("matrix", num_row=rows, num_col=cols)
    b = mv.create_table("matrix", num_row=rows, num_col=cols)

    def fused(datas, states, ids, scale):
        # server state is 128-lane column-padded: touch (and sum) only
        # the logical columns
        da, db = datas
        delta = jnp.zeros((ids.shape[0], da.shape[1]),
                          da.dtype).at[:, :cols].set(scale)
        da = da.at[ids].add(delta)
        db = db.at[ids].add(2.0 * delta)
        return [da, db], states, (da[ids, :cols] + db[ids, :cols]).sum()

    mv.register_program("test.fused_pair", jax.jit(
        fused, donate_argnums=(0, 1)))
    ids = np.arange(4, dtype=np.int32)
    if rank == world - 1:  # follower origin: the full lockstep round
        with mv.worker(0):
            h = a.transact_device_async("test.fused_pair", [b],
                                        args=(ids, 2.5))
            reply = a.wait(h)
        assert isinstance(reply, jax.Array), type(reply)
        # a rows: 2.5 each; b rows: 5.0 each -> sum = 4*8*7.5
        np.testing.assert_allclose(float(reply), 4 * cols * 7.5)
    mv.process_barrier()
    with mv.worker(0):
        got_a, got_b = a.get(), b.get()  # every rank's replica
    expect_a = np.zeros((rows, cols), np.float32)
    expect_a[:4] = 2.5
    np.testing.assert_allclose(got_a, expect_a)
    np.testing.assert_allclose(got_b, 2.0 * expect_a)
    mv.process_barrier()
    # raw closures must still be rejected loudly under multihost
    with mv.worker(0):
        try:
            a.transact_device_async(lambda d, s: (d, s, None), [b])
            raise AssertionError("raw closure transact did not fail")
        except AssertionError:
            raise
        except Exception:
            pass
    mv.process_barrier()


def run_badreq(mv, np, rank: int, world: int) -> None:
    """A malformed request must fail ONLY its caller, not the world: the
    leader and every follower reject it identically, the leader absolves
    the followers' divergence reports, and traffic continues (refinement
    of the round-4 advisor's poison rule — unconditional poisoning let
    one bad request kill every follower rank)."""
    mat = mv.create_table("matrix", num_row=16, num_col=4)
    with mv.worker(0):
        mat.add(np.ones((16, 4), np.float32))
    mv.process_barrier()
    if rank == world - 1:  # a FOLLOWER sends the malformed add
        with mv.worker(0):
            try:
                mat.add(np.ones((2, 4), np.float32))  # wrong whole-table
                raise AssertionError("malformed add did not raise")
            except AssertionError:
                raise
            except Exception:
                pass  # the caller gets the failure; the world survives
    mv.process_barrier()
    with mv.worker(0):
        mat.add(np.ones((16, 4), np.float32))
    mv.process_barrier()
    with mv.worker(0):
        got = mat.get()
    np.testing.assert_allclose(
        got, np.full((16, 4), 2.0 * world, np.float32),
        err_msg="table corrupted or a rank was wrongly poisoned")
    mv.process_barrier()


def run_ctrlperf(mv, np, rank: int, world: int) -> None:
    """Bound + record the lockstep control plane's per-op cost: a sync
    row add from EVERY rank (followers pay the full forward -> leader
    execute -> broadcast -> replay -> ack round trip). The 250ms median
    bound is a broken-plane guard with a 50ms advisory print — medians
    of ~3ms were seen on a loaded CI host."""
    import time

    mat = mv.create_table("matrix", num_row=64, num_col=8)
    ones = np.ones((4, 8), np.float32)
    ids = np.arange(4, dtype=np.int32)
    with mv.worker(0):
        mat.add(ones, row_ids=ids)  # warm
        samples = []
        for _ in range(50):
            t0 = time.perf_counter()
            mat.add(ones, row_ids=ids)
            samples.append(time.perf_counter() - t0)
    med = sorted(samples)[len(samples) // 2]
    print(f"CTRL_OP_MEDIAN_US rank={rank} {med * 1e6:.1f}", flush=True)
    # 250ms is a broken-control-plane bound, not a perf target: medians
    # of ~3ms were seen, but an oversubscribed CI host can stall a whole
    # scheduling quantum mid-round-trip. Flag (don't fail) past 50ms.
    if med >= 0.05:
        print(f"CTRL_OP_SLOW rank={rank} median {med * 1e3:.2f}ms exceeds "
              "the 50ms advisory bound (loaded host?)", flush=True)
    assert med < 0.25, (
        f"lockstep ctrl op median {med * 1e3:.2f}ms exceeds the 250ms bound")
    mv.process_barrier()


def run_flagmismatch(mv, np, rank: int, world: int) -> None:
    # unreachable: main()'s mv.init must already have fataled on the
    # divergent `sync` flag during the handshake
    raise AssertionError(
        "flag-mismatch world initialized despite divergent sync flag")


def run_crash(mv, np, rank: int, world: int) -> None:
    """Failure detection: rank 1 dies abruptly mid-run; the leader's next
    collective must fail LOUDLY within the Gloo deadline instead of
    hanging forever (the reference had no failure detection at all —
    SURVEY §5 'a send failure is a CHECK/Fatal')."""
    import os as _os
    import time

    mat = mv.create_table("matrix", num_row=16, num_col=4)
    with mv.worker(0):
        mat.add(np.ones((16, 4), np.float32))
        mat.get()
    mv.process_barrier()
    if rank == 1:
        _os._exit(42)  # simulated host failure: no goodbye, no cleanup
    # observation-based, not sleep-based: keep issuing collectives until
    # the dead peer surfaces as an error. Each attempt runs on its own
    # watchdogged thread so a SILENTLY-HANGING collective — the exact
    # regression this test guards — is reported as non-detection within
    # the deadline instead of wedging until the harness kill
    import threading

    from multiverso_tpu import config as mv_config

    # the watchdog must OUTLAST the system's own loud-failure bound
    # (multihost_timeout governs every control-plane raise): expiring
    # first would misreport a legitimately loud-but-slow error as a hang
    loud_bound = float(mv_config.get_flag("multihost_timeout")) + 30.0
    deadline = time.monotonic() + loud_bound + 60.0
    while time.monotonic() < deadline:
        outcome = {}

        def attempt():
            try:
                with mv.worker(0):
                    mat.add(np.ones((16, 4), np.float32))
                    mat.get()
                outcome["ok"] = True
            except BaseException as exc:  # noqa: BLE001 — loud = pass
                outcome["exc"] = exc

        t = threading.Thread(target=attempt, daemon=True)
        t.start()
        t.join(timeout=loud_bound)
        if t.is_alive():
            print("LEADER_DID_NOT_DETECT_FAILURE (collective hung)",
                  flush=True)
            _os._exit(1)
        if "exc" in outcome:
            print("LEADER_DETECTED_FAILURE "
                  f"{type(outcome['exc']).__name__}", flush=True)
            _os._exit(0)
        time.sleep(0.5)  # peer still alive; retry
    print("LEADER_DID_NOT_DETECT_FAILURE (no error before deadline)",
          flush=True)
    _os._exit(1)


def run_remote(mv, np, rank: int, world: int) -> None:
    """The FULL scaling topology at once: a table sharded across BOTH
    processes' devices (multihost mesh) ALSO served to an off-mesh
    remote client over TCP from the leader — mesh workers, follower
    workers, and wire clients all hit the same lockstep dispatcher."""
    rows, cols = 24, 6
    expect = sum(range(1, world + 1)) + 10.0  # mesh adds + wire client add
    mat = mv.create_table("matrix", num_row=rows, num_col=cols)
    with mv.worker(0):
        mat.add(np.full((rows, cols), float(rank + 1), np.float32))
    mv.process_barrier()
    if rank == 0:
        endpoint = mv.serve("127.0.0.1:0")
        client = mv.remote_connect(endpoint)
        rt = client.table(mat.table_id)
        rt.add(np.full((rows, cols), 10.0, np.float32))
        got = np.asarray(rt.get())
        client.close()
        np.testing.assert_allclose(got, expect)
    mv.process_barrier()
    with mv.worker(0):
        got = mat.get()  # every mesh rank sees the wire client's add too
    np.testing.assert_allclose(got, expect)
    mv.process_barrier()


def run_bsp2(mv, np, rank: int, world: int) -> None:
    """BSP with TWO worker threads per process (4 global workers over 2
    processes): global worker ids are rank*local_workers+slot, and the
    round contract must hold across the full 2x2 worker grid."""
    import threading

    rows, cols = 16, 4
    mat = mv.create_table("matrix", num_row=rows, num_col=cols)
    rounds, workers = 3, 2 * world
    errors = []

    def work(slot):
        try:
            with mv.worker(slot):
                wid = rank * 2 + slot
                for i in range(1, rounds + 1):
                    mat.add(np.full((rows, cols), float(wid + 1),
                                    np.float32))
                    got = mat.get()
                    np.testing.assert_allclose(
                        got, np.full((rows, cols),
                                     i * sum(range(1, workers + 1)),
                                     np.float32),
                        err_msg=f"worker {wid} round {i}")
                mat.finish_train()
        except Exception as exc:  # surfaced by the parent assert
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(s,)) for s in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads), "worker thread hung"
    mv.process_barrier()


def run_bsp(mv, np, rank: int, world: int) -> None:
    """BSP contract across processes: worker w's round-i Get observes
    exactly i rounds of EVERY worker's Adds (the reference SyncServer
    contract, test_sync.cpp shape), with one worker per process."""
    rows, cols = 32, 8
    mat = mv.create_table("matrix", num_row=rows, num_col=cols)
    rounds = 4
    with mv.worker(0):
        for i in range(1, rounds + 1):
            mat.add(np.full((rows, cols), float(rank + 1), np.float32))
            got = mat.get()
            np.testing.assert_allclose(
                got, np.full((rows, cols),
                             i * sum(range(1, world + 1)), np.float32),
                err_msg=f"round {i} BSP contract violated")
        mat.finish_train()
    mv.process_barrier()


if __name__ == "__main__":
    raise SystemExit(main())
