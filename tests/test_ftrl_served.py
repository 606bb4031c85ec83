"""The keyed FTRL table served to remote clients (PR 50): the proxy
`mv.remote_connect(endpoint).table(id)` gives, the Add ordinal every reply
carries, and the reference that decides from the workers' records alone
whether those ordinals are a legal serial order and replays it
(`benchmark/reference/logreg-ftrl-criteo-tb-served.py`, which imports nothing
of the program). Results and counts from a CPU run, never a speed."""

import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark import common
from multiverso_tpu import dashboard
from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.log import FatalError
from multiverso_tpu.runtime import wire

OPT = dict(alpha=0.1, beta=1.0, lambda1=1.0, lambda2=1.0)
SIZE, SEED, CELL = 4000, 50, "ftrlctr8.remote-steps"
CLIENTS, ENTRIES, PAIRS = 3, 4, 10


@pytest.fixture(scope="module")
def ref():
    return common.load_module("reference", "logreg-ftrl-criteo-tb-served")


def _table(ref, size=SIZE):
    return mv.create_table(
        "ftrl", size, init=lambda lo, count: ref.init_zn(
            np.arange(lo, lo + count), SEED), **OPT)


def _pools(ref):
    """Every client's pooled minibatches, overlapping on the keys below
    200: ``({entry: keys}, {entry: gradient in units})``."""
    rng = np.random.default_rng(SEED)
    keys, gk = {}, {}
    for entry in range(CLIENTS * ENTRIES):
        hot = rng.choice(200, 120, replace=False)
        cold = 200 + rng.choice(SIZE - 200, 300, replace=False)
        keys[entry] = np.sort(np.concatenate([hot, cold])).astype(np.int32)
        gk[entry] = ref.grad_k(rng, len(keys[entry]))
    return keys, gk


def _client_loop(ref, endpoint, table_id, worker, pool_keys, pool_gk, out,
                 pairs=PAIRS):
    """A worker: Get then Add of its pooled entries in turn, its record of
    every op and every Get's weights kept."""
    client = mv.remote_connect(endpoint)
    try:
        table = client.table(table_id)
        ops = {c: [] for c in ("kind", "entry", "ordinal", "sent", "replied")}
        gets = []

        def note(kind, entry, sent):
            replied = time.perf_counter()
            for column, value in zip(ops, (kind, entry, table.last_ordinal,
                                           sent, replied)):
                ops[column].append(value)

        for j in range(pairs):
            entry = worker * ENTRIES + j % ENTRIES
            sent = time.perf_counter()
            got = table.get(pool_keys[entry])
            note(ref.GET, entry, sent)
            gets.append((len(ops["kind"]) - 1, entry, got))
            sent = time.perf_counter()
            if j % 2:
                table.add(pool_keys[entry], ref.to_float(pool_gk[entry]))
            else:   # the async form, waited for
                table.wait(table.add_async(pool_keys[entry],
                                           ref.to_float(pool_gk[entry])))
            note(ref.ADD, entry, sent)
        out[worker] = (ops, gets)
    finally:
        client.close()


def _serve_clients(ref, table, endpoint, pairs=PAIRS):
    pool_keys, pool_gk = _pools(ref)
    out = {}
    threads = [threading.Thread(
        target=_client_loop, args=(ref, endpoint, table.table_id, w,
                                   pool_keys, pool_gk, out, pairs))
        for w in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert sorted(out) == list(range(CLIENTS)), "a client did not finish"
    return pool_keys, pool_gk, out


# -- (i) three clients, every element of every Get, the final state ----------

def test_three_clients_follow_one_serial_order(ref, monkeypatch):
    """Every Get of three concurrent clients is the weights after exactly
    the Adds its reply counted, in the order the Adds' replies give; the
    final `(z, n)` is the state after all of them; the records of the served
    ops carry the ordinals; the new readers read the window."""
    assert ref.one_clock()
    before = {c: Dashboard.counter_value(c) for c in (
        "FTRL_SERVED_GET", "FTRL_SERVED_ADD", "ADDS_ORDERED")}
    mv.init(mesh_shape="1", remote_workers=CLIENTS, ps_role="server")
    table = _table(ref)
    endpoint = mv.serve("127.0.0.1:0")
    monkeypatch.setattr(Dashboard, "profile_annotations", True)
    t0 = time.perf_counter()
    pool_keys, pool_gk, out = _serve_clients(ref, table, endpoint)
    from multiverso_tpu.runtime.zoo import Zoo
    Zoo.instance().server.run_serialized(lambda: None)
    t1 = time.perf_counter()
    monkeypatch.setattr(Dashboard, "profile_annotations", False)

    records = [ref.Ops(**out[w][0]) for w in range(CLIENTS)]
    assert ref.order_faults(records) == {"a": 0, "b": 0, "c": 0}
    order = ref.serial_order(records)
    adds = CLIENTS * PAIRS
    assert len(order) == adds
    every = np.arange(SIZE)
    # every Get, every element, at rising counts
    followed = ref.OrderedReplay(every, pool_keys, pool_gk, SEED, OPT)
    gets = sorted(((ops["ordinal"][at], entry, got)
                   for ops, kept in out.values()
                   for at, entry, got in kept), key=lambda g: g[0])
    assert len(gets) == adds and gets[-1][0] > gets[0][0]
    for count, entry, got in gets:
        assert got.shape == pool_keys[entry].shape
        assert followed.get_error(order, count, pool_keys[entry], got,
                                  OPT) <= 1
    # the state after all of them, and nothing else touched
    replay = followed.after(order, adds)
    z = np.asarray(table.get_state_device("z"))
    n = np.asarray(table.get_state_device("n"))
    assert ref.n_mismatch(n[:SIZE], replay.n) == 0
    assert ref.z_error(z[:SIZE], replay.z, replay.steps) <= 1
    quiet = replay.steps == 0
    assert quiet.any() and ref.n_mismatch(z[:SIZE][quiet],
                                          replay.z[quiet]) == 0
    assert replay.steps.max() > PAIRS  # keys several clients stepped

    # always-on counters: the kind's ops over the wire, the replies stamped
    moved = {c: Dashboard.counter_value(c) - was for c, was in before.items()}
    assert moved == {"FTRL_SERVED_GET": adds, "FTRL_SERVED_ADD": adds,
                     "ADDS_ORDERED": 2 * adds}
    # the ordinal on the service records: the Adds' are 1..N in the order
    # they were applied, each Get's is the count before its launch
    window, lost = dashboard.RING.window(t0, t1)
    assert not lost
    served_adds = [r for r in window if r.stage == "SERVER_PROCESS_ADD_MSG"]
    assert [r.ordinal for r in served_adds] == list(range(1, adds + 1))
    served_gets = [r for r in window if r.stage == "SERVER_PROCESS_GET_MSG"]
    assert sorted(r.ordinal for r in served_gets) == sorted(
        g[0] for g in gets)
    # a served keyed op writes what a served matrix op writes
    stages = {r.stage for r in window}
    assert {"SERVE_HANDLE", "WIRE_DECODE", "TABLE_PROCESS_ADD",
            "TABLE_PROCESS_GET", "TABLE_ROW_PREP", "TABLE_ROW_LAUNCH",
            "REPLY_FINISH_WAIT", "REPLY_FINISH", "TABLE_HOST_READ",
            "reply_sent", "WIRE_REPLY"} <= stages
    preps = [r for r in window if r.stage == "TABLE_ROW_PREP"]
    assert {r.n for r in preps} == {420}
    launches = [r for r in window if r.stage == "TABLE_ROW_LAUNCH"]
    assert {r.ids_from for r in launches} == {"dispatcher"}

    class Run:
        window = (t0, t1)
        result = {"adds_acked": adds, "adds_ordered": int(sum(
            a.sum() for a in ref.exactly_once(records)[0]))}

    run = Run()
    read = lambda name: common.load_module("layers", name).read(run)  # noqa
    assert read("served_adds_per_launch") == 1.0
    assert 0 < read("keyed_prep_ms") < 1000
    assert read("adds_ordered_share") == 100.0
    assert read("replies_behind_share") == 100.0
    assert read("pallas_row_share.ftrlctr") == 100.0


# -- (ii) the order rule refuses, each fault by its own letter ----------------

def _legal(ref):
    """Two workers' records of a legal order: worker 0's ops alternate with
    worker 1's, every op acknowledged before the next is sent."""
    kind = [ref.GET, ref.ADD] * 3
    records, clock = [], 0.0
    rows = {0: [], 1: []}
    applied = 0
    for step in range(6):
        for w in (0, 1):
            k = kind[step]
            applied += k == ref.ADD
            rows[w].append((k, w * 10 + step, applied, clock, clock + 0.5))
            clock += 1.0
    for w in (0, 1):
        records.append(ref.Ops(*zip(*rows[w])))
    return records


def _with(ref, record, at, **changed):
    columns = {c: getattr(record, c).tolist() for c in record.__slots__}
    for column, value in changed.items():
        columns[column][at] = value
    return ref.Ops(**columns)


def _in_flight(ref, record):
    """``record`` with every op in flight over the whole run: real time
    then says nothing about it, nor about any op beside it."""
    return ref.Ops(record.kind, record.entry, record.ordinal,
                   0.0 * record.sent, 1000.0 + 0.0 * record.replied)


FAULTS = {
    # worker 1's second Add says the ordinal of worker 0's: one place twice
    "doubled": ("a", lambda ref, r: [r[0], _with(ref, r[1], 3, ordinal=3)]),
    # the last Add is lost on the way: its place is beyond the Adds counted
    "missing": ("a", lambda ref, r: [r[0], _with(ref, r[1], 5, ordinal=7)]),
    # a reply without an ordinal
    "unstamped": ("a", lambda ref, r: [r[0], _with(ref, r[1], 5,
                                                   ordinal=None)]),
    # worker 0's Adds fall: its second lies before its first (worker 1's
    # ops are in flight throughout, so only the program order is broken)
    "falling": ("b", lambda ref, r: [ref.Ops(
        r[0].kind, r[0].entry, [0, 3, 3, 1, 4, 5], r[0].sent, r[0].replied),
        _in_flight(ref, r[1])]),
    # a Get that has not read the worker's own acknowledged Add
    "stale read": ("b", lambda ref, r: [_with(ref, r[0], 2, ordinal=0),
                                        _in_flight(ref, r[1])]),
    # worker 1's first Add is ordered before worker 0's, which was
    # acknowledged before worker 1's was sent
    "real time": ("c", lambda ref, r: [_with(ref, r[0], 1, ordinal=2),
                                       _with(ref, r[1], 1, ordinal=1)]),
}


def test_a_legal_order_is_accepted(ref):
    records = _legal(ref)
    assert ref.order_faults(records) == {"a": 0, "b": 0, "c": 0}
    assert ref.serial_order(records).tolist() == [1, 11, 3, 13, 5, 15]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_order_rule_refuses(ref, fault):
    """Each fault is refused by its own rule, and by no other (a place held
    twice or by nobody leaves the other rules nothing sound to say, so
    rule (a)'s faults are asked of rule (a) alone)."""
    letter, broken = FAULTS[fault]
    faults = ref.order_faults(broken(ref, _legal(ref)))
    assert faults[letter] > 0, faults
    if letter == "a":
        with pytest.raises(ValueError):
            ref.serial_order(broken(ref, _legal(ref)))
    else:
        assert [k for k, v in faults.items() if v] == [letter], faults


def test_concurrent_ops_may_take_either_order(ref):
    """Two Adds in flight at once are legal in either order: real time says
    nothing about them, and only the replayed state can tell."""
    records = _legal(ref)
    for record in records:
        record.sent[1], record.replied[1] = 1.0, 3.0
    swapped = [_with(ref, records[0], 1, ordinal=2),
               _with(ref, records[1], 1, ordinal=1)]
    for both in (records, swapped):
        assert ref.order_faults(both) == {"a": 0, "b": 0, "c": 0}
    assert (ref.serial_order(records)[:2].tolist()
            == ref.serial_order(swapped)[1::-1].tolist())


# -- (iii) a retried Add is applied once and keeps its ordinal ----------------

@pytest.mark.parametrize("spec", ["drop:type=Reply_Add,first=1",
                                  "dup:type=Request_Add,first=1"])
def test_a_retried_add_keeps_its_ordinal(ref, spec):
    """The first Add's reply is lost (the client retransmits and is answered
    from the dedup store), or its frame arrives twice: the Add is applied
    once, and the stored reply, which is what every retry is answered with,
    carries the ordinal of that one application."""
    mv.set_flag("fault_spec", spec)
    mv.set_flag("fault_seed", SEED)
    mv.set_flag("request_retry_seconds", 0.3)
    mv.init(mesh_shape="1", remote_workers=1, ps_role="server")
    table = _table(ref)
    endpoint = mv.serve("127.0.0.1:0")
    hits = Dashboard.counter_value("SERVER_DEDUP_HITS")
    client = mv.remote_connect(endpoint)
    try:
        remote = client.table(table.table_id)
        keys = np.arange(0, 600, 2, dtype=np.int32)
        rng = np.random.default_rng(3)
        grads = [ref.to_float(ref.grad_k(rng, len(keys))) for _ in range(3)]
        ordinals = []
        for grad in grads:
            remote.add(keys, grad)
            ordinals.append(remote.last_ordinal)
        remote.get(keys)
        assert ordinals == [1, 2, 3] and remote.last_ordinal == 3
        deadline = time.monotonic() + 10
        while (Dashboard.counter_value("SERVER_DEDUP_HITS") == hits
               and time.monotonic() < deadline):
            time.sleep(0.05)   # the doubled frame may trail the first reply
        assert Dashboard.counter_value("SERVER_DEDUP_HITS") > hits
        # what a retry of each Add is answered with
        from multiverso_tpu.runtime.message import MsgType
        from multiverso_tpu.runtime.zoo import Zoo
        stored = [m for m in Zoo.instance().remote_server._dedup.values()
                  if getattr(m, "type", None) == MsgType.Reply_Add]
        assert [wire.decode(m.data).ordinal for m in stored] == [1, 2, 3]
        # applied once: `n` is equal only then
        replay = ref.Replay(np.arange(SIZE), SEED, OPT)
        for grad in grads:
            replay.add(replay.plan(keys), grad)
        n = np.asarray(table.get_state_device("n"))[:SIZE]
        assert ref.n_mismatch(n, replay.n) == 0
    finally:
        client.close()


# -- (iv) the cell, rehearsed, and its controls ---------------------------------

def _rehearse(seed):
    root = common.ROOT
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=root, capture_output=True,
        text=True, timeout=900, env=dict(os.environ, PYTHONPATH=root))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    compared = {c["compared"]: c for c in (
        json.loads(x) for x in lines if x.startswith('{"compared"'))}
    return json.loads(lines[-1]), compared, lines


COMPARISONS = ["created_state_mismatch", "warm_order_a_faults",
               "warm_order_b_faults", "warm_order_c_faults",
               "start_sample_w_error", "start_quiet_mismatch",
               "start_unnamed_mismatch", "order_a_faults", "order_b_faults",
               "order_c_faults", "window_get_error", "final_sample_w_error",
               "final_sample_z_error", "final_sample_n_mismatch",
               "final_quiet_mismatch", "unnamed_state_mismatch"]


def test_the_cell_rehearses():
    """`ftrlctr8.remote-steps` end to end at rehearsal sizes on the CPU (3
    worker processes): every comparison inside its limit, every Add of the
    run replayed in the server's order, every element of every kept Get
    compared, every acknowledged Add of the window ordered."""
    last, compared, lines = _rehearse(2147550047)
    assert sorted(compared) == sorted(COMPARISONS)
    assert all(c["ok"] for c in compared.values())
    assert last["correct"] is True and last["failed"] == 0
    counts = last["counts"]
    small = common.load_json("benchmark", "traffic",
                             "remote-steps.json")["rehearse"]
    assert counts["ops"] == 2 * counts["adds"] == last["attempted"]
    assert counts["adds_acked"] == counts["adds_ordered"] == counts["adds"]
    assert counts["adds_replayed"] == \
        counts["adds"] + small["workers"] * small["warmup_pairs"]
    # a pair keeps at most one Get, so a window of few pairs may keep fewer
    assert small["workers"] <= counts["gets_checked"] \
        <= small["workers"] * small["sampled_gets"]
    assert counts["get_elements_checked"] > 2000 * counts["gets_checked"]
    # the keys every sample names took every step
    assert counts["most_steps"] == counts["adds_replayed"]
    assert 2 * counts["keys_checked_shared"] >= counts["keys_checked"]
    served = next(json.loads(x) for x in lines
                  if x.startswith('{"pairs_by_second"'))["served_counters"]
    assert served["FTRL_SERVED_ADD"] == served["FTRL_SERVED_GET"] \
        == counts["adds"]
    assert served["ADDS_ORDERED"] == counts["ops"]


CAUGHT_BY = {
    "gradient": {"window_get_error", "final_sample_z_error",
                 "final_sample_n_mismatch"},
    "order": {"window_get_error", "final_sample_z_error"},
    "retry": {"order_a_faults"},
}


@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_the_controls_read_not_correct(fault):
    """`benchmark/tests/control_keys_remote.py`: the cell with its workers'
    gradients rounded to bfloat16, with two neighbouring Adds swapped in the
    replayed order, and with a server that applies a doubled Add twice,
    reads not correct, each by the comparisons that are there for it."""
    spec = importlib.util.spec_from_file_location(
        "control_keys_remote", os.path.join(
            common.ROOT, "benchmark", "tests", "control_keys_remote.py"))
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    report = controls.run_control(CELL, 2147550048, 1.0, fault,
                                  rehearse=True)
    assert report["correct"] is False, report
    failed = {c["compared"] for c in report["compared"] if not c["ok"]}
    assert CAUGHT_BY[fault] <= failed, report
    assert not {"created_state_mismatch", "unnamed_state_mismatch",
                "start_unnamed_mismatch", "order_b_faults",
                "order_c_faults"} & failed
    if fault == "order":   # the same Adds, each once: `n` cannot tell
        assert "final_sample_n_mismatch" not in failed


# -- (v) what the proxy refuses ------------------------------------------------

def test_the_proxy_refuses_by_name(ref):
    """Device IO and a key out of range fail on the client, before anything
    is sent; a table group is still refused by name."""
    mv.init(mesh_shape="1", remote_workers=1)
    table = _table(ref)
    group = mv.create_table("matrix_group", [3, 10], 8, np.float32)
    endpoint = mv.serve("127.0.0.1:0")
    client = mv.remote_connect(endpoint)
    try:
        remote = client.table(table.table_id)
        assert remote.size == SIZE and not remote.supports_device_io
        sent = Dashboard.counter_value("FTRL_SERVED_ADD") \
            + Dashboard.counter_value("FTRL_SERVED_GET")
        keys = np.arange(4, dtype=np.int32)
        for bad in (np.array([SIZE], np.int32), np.array([-1], np.int32)):
            with pytest.raises(FatalError, match="key out of range"):
                remote.add(bad, np.ones(1, np.float32))
            with pytest.raises(FatalError, match="key out of range"):
                remote.get_async(bad)
        with pytest.raises(FatalError, match="device IO is in-process"):
            remote.get_device_async(keys)
        with pytest.raises(FatalError, match="device IO is in-process"):
            remote.add_device_async(np.ones(4, np.float32), keys)
        with pytest.raises(RuntimeError, match="mesh residency"):
            remote.get_state_device("z")
        assert sent == Dashboard.counter_value("FTRL_SERVED_ADD") \
            + Dashboard.counter_value("FTRL_SERVED_GET")
        assert remote.last_ordinal is None
        # a key named twice in one Add takes one step from the sum
        remote.add(np.array([7, 7], np.int32),
                   np.array([0.25, 0.5], np.float32))
        assert remote.last_ordinal == 1
        replay = ref.Replay(np.arange(SIZE), SEED, OPT)
        replay.add(replay.plan(np.array([7])), np.array([0.75], np.float32))
        got = remote.get(np.array([7], np.int32))
        z, _, want, steps = replay.state(np.array([7]))
        assert ref.w_error(got, want, z, steps, OPT) <= 1
        n = np.asarray(table.get_state_device("n"))[:SIZE]
        assert ref.n_mismatch(n, replay.n) == 0
        with pytest.raises(KeyError, match="matrix_group.*not served"):
            client.table(group.table_id)
    finally:
        client.close()


def test_the_wire_carries_an_ordinal_in_the_tree():
    """`wire.Ordered`: the ordinal rides in the structure tree beside the
    payload's own tree, with no blob of its own."""
    weights = np.arange(5, dtype=np.float32)
    for payload in (None, weights):
        blobs = wire.encode(wire.Ordered(payload, 41))
        assert len(blobs) == len(wire.encode(payload))
        back = wire.decode(blobs)
        assert isinstance(back, wire.Ordered) and back.ordinal == 41
        if payload is None:
            assert back.value is None
        else:
            np.testing.assert_array_equal(back.value, payload)


def test_an_in_process_worker_reads_the_ordinal_too(ref):
    """The in-process proxy's replies are stamped by the same code; a
    matrix table under a linear rule, whose Adds merge, is not."""
    mv.init(mesh_shape="1")
    table = _table(ref)
    plain = mv.create_table("matrix", 64, 8, np.float32)
    keys = np.arange(10, dtype=np.int32)
    assert table.last_ordinal is None
    table.get(keys)
    assert table.last_ordinal == 0
    table.add(keys, np.ones(10, np.float32))
    table.wait(table.add_async(keys, np.ones(10, np.float32)))
    assert table.last_ordinal == 2
    table.get(keys)
    assert table.last_ordinal == 2
    assert not plain._server_table.orders_adds
    assert table._server_table.orders_adds


def test_host_adds_of_many_key_counts_share_one_program(ref):
    """A host gradient goes up at the slots its program works on, so Adds
    of different key counts under one step of a bucket compile once (a
    served trainer's minibatches each name another count of keys)."""
    mv.init(mesh_shape="1")
    table = _table(ref)
    replay = ref.Replay(np.arange(SIZE), SEED, OPT)
    rng = np.random.default_rng(9)
    clock = common.CompileClock()
    compiles = []
    for count in (290, 293, 297, 301, 304):   # one step of a bucket of 512
        keys = np.sort(rng.choice(SIZE, count, replace=False)).astype(
            np.int32)
        grad = ref.to_float(ref.grad_k(rng, count))
        table.add(keys, grad)
        table.get(keys)
        replay.add(replay.plan(keys), grad)
        compiles.append(len(clock.events))
    assert compiles[0] > 0 and compiles[-1] == compiles[0], compiles
    n = np.asarray(table.get_state_device("n"))[:SIZE]
    assert ref.n_mismatch(n, replay.n) == 0
