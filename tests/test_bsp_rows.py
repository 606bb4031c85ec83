"""BSP at a table's width: remote clients that move in rounds over loopback on
a 2,000 x 128 matrix table, overlapping Zipf pools, every element of every
Get against the plain reference of `dlrm-mlperf-emb128-bsp` (its round
rule); what the round gate writes into the op trace and the counters; the
cell `emb128bsp.round-workers` as a rehearsal; the five per-layer readers
the cell brought.

Times here come from a CPU run: they check that records nest and carry the
right ids and counts, never how fast anything is."""

import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.runtime.zoo import Zoo

from benchmark import common, op_trace, rows_table

CELL = "emb128bsp.round-workers"
CONFIG = "dlrm-mlperf-emb128-bsp"
ROWS, COLS, PER_OP, POOL, WORKERS, SEED = 2000, 128, 64, 4, 3, 46
COUNTERS = ("SYNC_ROUNDS", "SYNC_SERVED_ADD", "SYNC_SERVED_GET",
            "SYNC_DEFERRED_ADD", "SYNC_DEFERRED_GET")

ref = common.load_module("reference", CONFIG)
row_ops_remote = common.load_module("drivers", "row_ops_remote")


@pytest.fixture
def tracing():
    mv.set_flag("profile_annotations", True)
    Dashboard.profile_annotations = True
    yield
    Dashboard.profile_annotations = False


def _traffic(workers=WORKERS):
    """(mirror, every worker's pool of (ids, float deltas)), drawn as the
    cell's driver draws them: Zipf(1.0) over a seeded permutation, a pool a
    worker, so that pools of different workers share the hot rows."""
    zipf = common.ZipfRows(ROWS, 1.0, SEED)
    mirror = ref.Mirror(COLS, SEED)
    pools = row_ops_remote.worker_pools(
        ref, mirror, zipf, SEED, workers,
        {"pool": POOL, "rows_per_op": PER_OP}, COLS)
    shared = set(pools[0][0][0]) & set(pools[1][0][0])
    assert shared, "the first sets of two workers share no row"
    return mirror, [[(ids, ref.to_float(dk)) for ids, dk in pool]
                    for pool in pools]


def _serve(workers=WORKERS, **flags):
    flags = dict(dict(sync=True), **flags)
    mv.init(ps_role="server", remote_workers=workers, **flags)
    init, _ = ref.init_table(ROWS, COLS, SEED)
    table = mv.create_table("matrix", ROWS, COLS, np.float32, init_value=init)
    return table, mv.serve("127.0.0.1:0")


def _run_rounds(endpoint, table_id, pools, rounds, before=None):
    """Every worker a thread with a client of its own: ``rounds[w]`` pairs of
    Add then Get of its pooled set k mod POOL, then `finish_train`.
    ``before(w)`` runs in the worker's thread before its first pair. Returns
    {worker: [its Gets]}."""
    got, errors = {}, []

    def work(w):
        try:
            client = mv.remote_connect(endpoint)
            rt = client.table(table_id)
            if before is not None:
                before(w)
            out = []
            for k in range(rounds[w]):
                ids, delta = pools[w][k % POOL]
                rt.add(delta, row_ids=ids)
                out.append(rt.get(ids).copy())
            rt.finish_train()
            got[w] = out
            client.close()
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(w,))
               for w in range(len(rounds))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for t in threads:
        assert not t.is_alive(), "BSP deadlock"
    assert not errors, errors
    return got


def _mismatches(mirror, pools, got, finals):
    """Elements of every Get that differ from the round rule's, and the
    elements compared."""
    bad = compared = 0
    for w, gets in got.items():
        for k, rows in enumerate(gets):
            want = mirror.rows_at_round(pools[w][k % POOL][0], k + 1, finals)
            bad += ref.mismatches(rows, want)
            compared += want.size
    return bad, compared


# -- the guarantee, to the bit -----------------------------------------------------

def test_every_element_of_every_get_for_six_rounds():
    mirror, pools = _traffic()
    table, endpoint = _serve()
    rounds = [6] * WORKERS
    got = _run_rounds(endpoint, table.table_id, pools, rounds)
    bad, compared = _mismatches(mirror, pools, got, rounds)
    assert compared == WORKERS * 6 * PER_OP * COLS
    assert bad == 0
    # guarantee (c): two workers' Gets of one round agree on the rows both name
    ids0, ids1 = pools[0][0][0], pools[1][0][0]
    both, at0, at1 = np.intersect1d(ids0, ids1, return_indices=True)
    assert len(both)
    np.testing.assert_array_equal(got[0][0][at0], got[1][0][at1])
    # and the table is what the final counts say, read as an administrator
    counts = mirror.round_counts(max(rounds), rounds)
    sample = np.arange(ROWS, dtype=np.int32)
    assert ref.mismatches(table.get(sample),
                          mirror.rows_k(sample, counts)) == 0
    mv.shutdown()


def test_a_ragged_end_is_served_by_the_finished_workers_final_counts():
    """One worker finishes two rounds early: the others' later Gets hold its
    n_v Adds and no more (guarantee (b)), and nobody hangs."""
    mirror, pools = _traffic()
    table, endpoint = _serve()
    rounds = [6, 4, 6]
    got = _run_rounds(endpoint, table.table_id, pools, rounds)
    assert _mismatches(mirror, pools, got, rounds)[0] == 0
    # the rule with the early worker counted on would read wrong
    assert _mismatches(mirror, pools, got, [6, 6, 6])[0] > 0
    mv.shutdown()


def test_the_same_traffic_ungated_fails_the_comparison():
    """`sync=False`: worker 0 makes its first pair before the others start,
    so its Get cannot hold their round-1 Adds of the rows they share."""
    mirror, pools = _traffic()
    table, endpoint = _serve(sync=False)
    first_done = threading.Event()

    def staggered(w):
        if w:
            first_done.wait(60)

    def release():
        # worker 0's first reply is on its way once a Get has been served
        limit = time.monotonic() + 60
        while (Dashboard.get("SERVER_PROCESS_GET_MSG").count < 1
               and time.monotonic() < limit):
            time.sleep(0.005)
        time.sleep(0.05)
        first_done.set()

    threading.Thread(target=release, daemon=True).start()
    rounds = [6] * WORKERS
    got = _run_rounds(endpoint, table.table_id, pools, rounds,
                      before=staggered)
    assert _mismatches(mirror, pools, got, rounds)[0] > 0
    mv.shutdown()


def test_the_round_rule_counts_by_hand():
    mirror = ref.Mirror(2, 1)
    for w in range(2):
        for e in range(3):
            mirror.add_pool(np.array([w]), np.array([[10 ** e, 0]]))
    assert mirror.round_counts(4, [None, None]) == [2, 1, 1, 2, 1, 1]
    assert mirror.round_counts(4, [2, 7]) == [1, 1, 0, 2, 1, 1]
    base = ref.init_k(np.array([0, 1, 5]), 2, 1).astype(np.int64)
    want = base + np.array([[11, 0], [112, 0], [0, 0]])
    np.testing.assert_array_equal(
        mirror.rows_at_round(np.array([0, 1, 5]), 4, [2, None]), want)


# -- what the gate records -----------------------------------------------------------

def _window(t0):
    """The op trace from t0 to now, closed behind the dispatcher and the
    finishing thread (tests/test_op_trace.py `_served_run`)."""
    zoo = Zoo.instance()
    zoo.server.run_serialized(lambda: None)
    limit = time.monotonic() + 10
    while zoo.remote_server is not None and zoo.remote_server._unfinished:
        assert time.monotonic() < limit, "a reply was never finished"
        time.sleep(0.001)
    run = SimpleNamespace(window=(t0, time.perf_counter()), result={})
    return run, op_trace.of(run)


def _one(records, **fields):
    found = [r for r in records
             if all(getattr(r, k) == v for k, v in fields.items())]
    assert len(found) == 1, (fields, found)
    return found[0]


def _ancestors(trace, record):
    by_id = {r.id: r for r in trace.records if r.id}
    out = []
    while record.parent in by_id:
        record = by_id[record.parent]
        out.append(record)
    return out


def _second_worker_starts_late():
    """A ``before`` for `_run_rounds`: worker 1 makes its first Add only once
    another worker's Get waits at the gate, so that Get is deferred and that
    Add releases it, whatever the threads' timing."""
    held = threading.Event()

    def before(w):
        if w == 1:
            held.wait(60)

    def let_go():
        limit = time.monotonic() + 60
        while (Dashboard.counter_value("SYNC_DEFERRED_GET") < 1
               and time.monotonic() < limit):
            time.sleep(0.005)
        held.set()

    threading.Thread(target=let_go, daemon=True).start()
    return before


def _deferred_get_scenario(**flags):
    """Two workers: worker 0's round-1 Get arrives while worker 1 has not
    added and waits at the gate; worker 1's Add releases it. Returns (trace,
    the Get's id, the releasing Add's id, the release's section)."""
    _, pools = _traffic(2)
    table, endpoint = _serve(2, **flags)
    t0 = time.perf_counter()
    _run_rounds(endpoint, table.table_id, pools, [1, 1],
                before=_second_worker_starts_late())
    _, trace = _window(t0)
    deferred = _one(trace.spans("gate_deferred"))
    release = _one(trace.spans("SYNC_RELEASE"))
    dispatch = _one([r for r in _ancestors(trace, release)
                     if r.stage == "SERVER_DISPATCH_MSG"])
    return trace, deferred.op, dispatch.op, release


def test_a_released_gets_records_carry_its_own_id(tracing):
    trace, get_op, add_op, release = _deferred_get_scenario()
    assert get_op and add_op and get_op != add_op
    assert release.n == 1
    # the releasing Add's own service, under its own id
    add = _one(trace.spans("SERVER_PROCESS_ADD_MSG"), op=add_op)
    _one(trace.spans("TABLE_PROCESS_ADD"), op=add_op, parent=add.id)
    _one(trace.spans("apply_add"), op=add_op)
    # the released Get's: served inside the Add's dispatch, under ITS id
    served = _one(trace.spans("SERVER_PROCESS_GET_MSG"), op=get_op)
    assert served.parent == release.id
    table_get = _one(trace.spans("TABLE_PROCESS_GET"), op=get_op,
                     parent=served.id)
    launch = _one(trace.spans("TABLE_ROW_LAUNCH"), parent=table_get.id)
    assert launch.op == get_op
    _one(trace.spans("serve_get"), op=get_op)
    # the gate's wait: from the deferral to the release, the round it waited
    # for, the parent the queue wait of the same request has
    wait = _one(trace.spans("SYNC_GATE_WAIT"))
    assert wait.op == get_op and wait.n == 1 and wait.id == 0
    queued = _one(trace.spans("SERVER_QUEUE_WAIT"), op=get_op)
    assert wait.parent == queued.parent != 0
    deferred = _one(trace.spans("gate_deferred"))
    assert abs(wait.start_ns - deferred.start_ns) < 5e6
    assert wait.start_ns + wait.dur_ns <= served.start_ns
    assert wait.dur_ns > 0
    # the reply's hand-over and its send join the Get, as under the async
    # server
    handed = _one(trace.spans("REPLY_FINISH_WAIT"), op=get_op)
    assert handed.parent == served.id
    _one(trace.spans("reply_sent"), op=get_op)
    _one(trace.spans("REPLY_FINISH"), op=get_op)
    # and nothing of the Get's service carries the Add's id
    under = [r for r in trace.records
             if served in _ancestors(trace, r) or r.parent == served.id]
    assert under and all(r.op == get_op for r in under)
    mv.shutdown()


def test_ssp_releases_under_the_requests_own_id_too(tracing):
    """`SSPServer` inherits the serving path: its deferred Get is released
    and recorded the same way (staleness 0: a BSP-like read gate)."""
    trace, get_op, add_op, release = _deferred_get_scenario(
        sync=False, ssp_staleness=0)
    served = _one(trace.spans("SERVER_PROCESS_GET_MSG"), op=get_op)
    assert served.parent == release.id and get_op != add_op
    _one(trace.spans("TABLE_PROCESS_GET"), op=get_op, parent=served.id)
    wait = _one(trace.spans("SYNC_GATE_WAIT"))
    assert wait.op == get_op and wait.n == 1
    assert Dashboard.counter_value("SYNC_DEFERRED_GET") == 1
    assert Dashboard.counter_value("SYNC_DEFERRED_ADD") == 0
    assert Dashboard.counter_value("SYNC_SERVED_GET") == 2
    assert Dashboard.counter_value("SYNC_ROUNDS") == 1
    mv.shutdown()


def test_rounds_and_counters_add_up(tracing):
    _, pools = _traffic()
    table, endpoint = _serve()
    t0 = time.perf_counter()
    rounds = [5, 3, 5]
    _run_rounds(endpoint, table.table_id, pools, rounds,
                before=_second_worker_starts_late())
    _, trace = _window(t0)
    made = sum(rounds)
    value = Dashboard.counter_value
    assert value("SYNC_SERVED_ADD") == value("SYNC_SERVED_GET") == made
    assert value("SYNC_ROUNDS") == max(rounds)
    for kind in ("ADD", "GET"):
        assert 0 <= value("SYNC_DEFERRED_" + kind) <= made
    # every worker request was served once under its own section, deferred
    # first or not; the administrator's read is no worker's
    assert len(trace.spans("SERVER_PROCESS_ADD_MSG")) == made
    assert len(trace.spans("SERVER_PROCESS_GET_MSG")) == made
    waits = trace.spans("SYNC_GATE_WAIT")
    assert len(waits) == value("SYNC_DEFERRED_ADD") \
        + value("SYNC_DEFERRED_GET")
    assert len(waits) == sum(r.n for r in trace.spans("SYNC_RELEASE"))
    assert all(r.n >= 1 for r in trace.spans("SYNC_RELEASE"))
    assert value("SYNC_DEFERRED_GET") >= 1   # the late starter's peers'
    ops = [r.op for r in trace.spans("SERVER_PROCESS_GET_MSG")]
    assert len(set(ops)) == made and {w.op for w in waits} <= set(
        ops + [r.op for r in trace.spans("SERVER_PROCESS_ADD_MSG")])
    # one SYNC_ROUND a round, in order, each ending where the next may begin
    rnds = trace.spans("SYNC_ROUND")
    assert [r.n for r in rnds] == list(range(1, max(rounds) + 1))
    assert all(r.op == table.table_id and r.dur_ns >= 0 for r in rnds)
    ends = [r.start_ns + r.dur_ns for r in rnds]
    assert ends == sorted(ends)
    table.get(np.arange(8, dtype=np.int32))
    assert value("SYNC_SERVED_GET") == made
    mv.shutdown()


def test_a_released_request_that_fails_fails_its_own_waiter():
    """A deferred Add whose apply raises at release: its own caller gets the
    error, and the request whose arrival released it is served."""
    from multiverso_tpu.runtime.message import Message, MsgType
    from multiverso_tpu.runtime.server import _ExecWaiter

    mv.init(sync=True, local_workers=2)
    table = mv.create_table("matrix", 16, COLS, np.float32)
    server = Zoo.instance().server
    tid = table.table_id
    ones = np.ones((1, COLS), np.float32)

    def send(kind, worker, request):
        waiter = _ExecWaiter()
        server.send(Message(src=worker, dst=-1, type=kind, table_id=tid,
                            msg_id=worker + 1, data=[request, waiter]))
        return waiter

    ids = np.zeros(1, np.int32)
    first = send(MsgType.Request_Add, 0, (ids, ones, None))
    first.wait(30)
    got0 = send(MsgType.Request_Get, 0, (ids, None))        # waits for worker 1
    bad = send(MsgType.Request_Add, 0,
               (np.array([99], np.int32), ones, None))   # round 2: deferred
    second = send(MsgType.Request_Add, 1, (ids, ones, None))
    second.wait(30)
    got0.wait(30)
    got1 = send(MsgType.Request_Get, 1, (ids, None))        # releases `bad`
    got1.wait(30)
    with pytest.raises(Exception):
        bad.wait(30)
    assert Dashboard.counter_value("SYNC_DEFERRED_ADD") == 1
    mv.shutdown()


# -- the cell, rehearsed -------------------------------------------------------------

# the first Get of round 3 is answered one round late: its clock steps at
# once, the rows are gathered only after the next Add (a round-4 Add of
# another worker) has been applied
LATE_GET = """
from multiverso_tpu.runtime import server as _server
from multiverso_tpu.runtime.message import MsgType as _MsgType
_serve = _server.SyncServer._serve
_held = []
def _late(self, msg):
    clock = self._get_clock[msg.table_id]
    if (msg.type == _MsgType.Request_Get and not _late.done
            and clock[msg.src] == 2):
        _late.done = True
        clock[msg.src] += 1
        _held.append(msg)
        return
    _serve(self, msg)
    if _held and msg.type == _MsgType.Request_Add:
        held = _held.pop()
        self._get_clock[held.table_id][held.src] -= 1
        _serve(self, held)
_late.done = False
_server.SyncServer._serve = _late
"""


def _rehearse(seed, prelude=""):
    root = common.ROOT
    args = ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    code = (prelude + "\nimport sys; from benchmark import run; "
            f"sys.exit(run.main({args!r}))")
    done = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, PYTHONPATH=root))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    compared = {c["compared"]: c for c in (
        json.loads(x) for x in lines if x.startswith('{"compared"'))}
    return json.loads(lines[-1]), compared


COMPARISONS = ["round_get_mismatch", "window_get_mismatch",
               "final_sample_mismatch", "checksum_mismatch_columns"]


def test_the_cell_rehearses():
    """`emb128bsp.round-workers` end to end at rehearsal sizes on the CPU
    (3 worker processes): every comparison exact, every element of the check
    rounds' and the kept Gets compared, every worker request gated or let
    through and none failed."""
    last, compared = _rehearse(2147546047)
    assert sorted(compared) == sorted(COMPARISONS)
    assert all(c["ok"] and c["limit"] == 0 for c in compared.values())
    assert last["correct"] is True and last["failed"] == 0
    counts = last["counts"]
    traffic = common.load_json("benchmark", "traffic", "round-workers.json")
    small = traffic["rehearse"]
    per_get = small["rows_per_op"] * COLS
    assert counts["round_elements_checked"] == \
        small["workers"] * traffic["check_rounds"] * per_get
    assert counts["gets_checked"] == small["workers"] * traffic["sampled_gets"]
    assert counts["window_elements_checked"] == \
        counts["gets_checked"] * per_get
    assert counts["ops"] == 2 * counts["adds"] == 2 * counts["gets"] \
        == last["attempted"]
    assert counts["rows"] == 2 * counts["add_rows"]


def test_a_get_answered_a_round_late_is_not_correct():
    last, compared = _rehearse(2147546048, prelude=LATE_GET)
    assert last["correct"] is False
    assert not compared["round_get_mismatch"]["ok"]
    # every Add was applied once all the same: the table's end state is sound
    assert compared["final_sample_mismatch"]["ok"]
    assert compared["checksum_mismatch_columns"]["ok"]


def test_the_ungated_control_reads_not_correct():
    """`benchmark/tests/control_bsp.py`: the cell with `sync` off reads not
    correct by the Gets' comparisons; with `sync` left on the same patch
    changes nothing."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "control_bsp", os.path.join(common.ROOT, "benchmark", "tests",
                                    "control_bsp.py"))
    control_bsp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control_bsp)
    sound = control_bsp.run_control(CELL, 2147546049, 1.0, sync=True,
                                    rehearse=True)
    assert sound["correct"] is True, sound
    report = control_bsp.run_control(CELL, 2147546049, 1.0, rehearse=True)
    assert report["correct"] is False, report
    failed = {c["compared"] for c in report["compared"] if not c["ok"]}
    assert failed and failed <= {"round_get_mismatch", "window_get_mismatch"}


# -- the five readers, on a recorded op trace ---------------------------------------

_recorded = {}


@pytest.fixture
def recorded():
    """One traced BSP run of three workers, recorded once for the readers:
    the run a reader is handed (window, result, the op trace) and the
    trace."""
    if not _recorded:
        mv.set_flag("profile_annotations", True)
        Dashboard.profile_annotations = True
        try:
            _, pools = _traffic()
            table, endpoint = _serve()
            before = [Dashboard.counter_value(c) for c in COUNTERS]
            t0 = time.perf_counter()
            _run_rounds(endpoint, table.table_id, pools, [6] * WORKERS,
                        before=_second_worker_starts_late())
            run, trace = _window(t0)
            run.result["sync_counters"] = {
                c: Dashboard.counter_value(c) - was
                for c, was in zip(COUNTERS, before)}
            mv.shutdown()
        finally:
            Dashboard.profile_annotations = False
        _recorded.update(run=run, trace=trace)
    return _recorded["run"], _recorded["trace"]


def _mean_ms(records):
    return sum(r.dur_ns for r in records) / len(records) / 1e6


def _expect_gate_wait(run, trace):
    return _mean_ms(trace.spans("SYNC_GATE_WAIT"))


def _expect_gated_share(run, trace):
    c = run.result["sync_counters"]
    assert c["SYNC_DEFERRED_GET"] >= 1   # 0 = the traffic is not gated
    return 100.0 * (c["SYNC_DEFERRED_ADD"] + c["SYNC_DEFERRED_GET"]) / (
        c["SYNC_SERVED_ADD"] + c["SYNC_SERVED_GET"])


def _expect_round(run, trace):
    ends = [r.start_ns + r.dur_ns for r in trace.spans("SYNC_ROUND")]
    assert len(ends) == 6
    return (max(ends) - min(ends)) / 5 / 1e6


def _expect_round_spread(run, trace):
    return _mean_ms(trace.spans("SYNC_ROUND"))


def _expect_release(run, trace):
    return _mean_ms(trace.spans("SYNC_RELEASE"))


@pytest.mark.parametrize("metric, expect", [
    ("sync_gate_wait_ms", _expect_gate_wait),
    ("sync_gated_share", _expect_gated_share),
    ("sync_round_ms", _expect_round),
    ("sync_round_spread_ms", _expect_round_spread),
    ("sync_release_ms", _expect_release)])
def test_reader_on_a_recorded_trace(recorded, capsys, metric, expect):
    run, trace = recorded
    reader = common.load_module("layers", metric)
    value = reader.read(run)
    assert value is not None and value > 0
    assert value == pytest.approx(expect(run, trace), rel=1e-9)
    if metric == "sync_release_ms":
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        passes = trace.spans("SYNC_RELEASE")
        assert line["sync_release"]["passes"] == len(passes)
        assert line["sync_release"]["mean_released"] == pytest.approx(
            sum(r.n for r in passes) / len(passes))
    # a program without the records or the counters (the parent of the PR
    # that brought them) gives nothing, and does not raise
    bare = SimpleNamespace(window=run.window, result={},
                           _op_trace=op_trace.Trace(
                               [r for r in trace.records
                                if not r.stage.startswith("SYNC_")],
                               trace.t0_ns, trace.t1_ns))
    assert reader.read(bare) is None
    assert reader.read(SimpleNamespace(window=run.window, result={},
                                       _op_trace=None)) is None


def test_rows_table_flags_are_the_drivers_base():
    """The driver starts the program with `rows_table.INIT_FLAGS` and the
    configuration's two changes; the configuration states them."""
    config = common.load_json("benchmark", "configs", CONFIG + ".json")
    assert config["server"] == {"sync": True, "ps_role": "server",
                                "workers": 8, "backup_worker_ratio": 0}
    assert rows_table.INIT_FLAGS["sync"] is False
    assert rows_table.INIT_FLAGS["ps_role"] == "default"
    first = common.load_json("benchmark", "configs",
                             "dlrm-mlperf-emb128.json")
    assert config["table"] == first["table"]
    assert config["row_popularity"] == first["row_popularity"]
    assert config["reduced"] == ["num_row"]
