"""Micro-batched fused apply (runtime/server.py drain batching).

The apply-path contract under batching:
* the async server's final state is bit-identical to unbatched dispatch
  for commutative Adds (integer-valued float deltas make the sums exact,
  so the Downpour-tolerated reordering cannot blur the comparison);
* per-worker FIFO holds — a Get observes every Add the same worker queued
  before it on that table;
* non-Add messages (Server_Execute, transactions) are full barriers;
* deterministic/BSP servers are unaffected (they never fuse);
* the APPLY_* telemetry proves batching actually happened.

``tests/test_durable.py::test_crash_point_mid_batch_recovery_exactly_once``
covers the WAL half: a kill -9 between a batch's appends and its fused
apply loses zero acknowledged Adds.
"""

import threading
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.runtime.message import Message, MsgType
from multiverso_tpu.runtime.server import (DeterministicServer, Server,
                                           SSPServer, SyncServer,
                                           _ExecWaiter)
from multiverso_tpu.runtime.zoo import Zoo
from multiverso_tpu.tables.base import (RowOccurrences,
                                        merge_duplicate_rows,
                                        sum_duplicate_rows)
from multiverso_tpu.tables.matrix_table import RowPieces
from multiverso_tpu.utils import MtQueue


# -- the drain primitive ------------------------------------------------------

def test_pop_all_drains_in_arrival_order():
    q = MtQueue()
    for i in range(5):
        q.push(i)
    assert q.pop_all() == [0, 1, 2, 3, 4]
    assert q.empty()


def test_pop_all_blocks_until_item_and_exits_clean():
    q = MtQueue()
    got = []

    def consumer():
        while True:
            items = q.pop_all()
            if items is None:
                return
            got.extend(items)

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    q.push("a")
    q.push("b")
    time.sleep(0.05)
    q.exit()
    t.join(timeout=5)
    assert not t.is_alive() and got == ["a", "b"]


def test_pop_all_returns_leftovers_then_none_after_exit():
    q = MtQueue()
    q.push(1)
    q.push(2)
    q.exit()
    assert q.pop_all() == [1, 2]
    assert q.pop_all() is None


# -- forced-batch helpers -----------------------------------------------------

def _hold_dispatcher(server):
    """Block the dispatcher inside a Server_Execute until the returned
    event is set — everything queued behind it lands in ONE drain."""
    gate = threading.Event()
    waiter = _ExecWaiter()
    server.send(Message(src=-1, dst=-1, type=MsgType.Server_Execute,
                        data=[lambda: gate.wait(30), waiter]))
    time.sleep(0.05)  # let the dispatcher enter the gate
    return gate, waiter


# -- fused apply: telemetry + exactness ---------------------------------------

def test_forced_batch_fuses_matrix_adds_and_counts():
    mv.init()
    table = mv.create_table("matrix", num_row=64, num_col=8)
    server = Zoo.instance().server
    assert type(server) is Server and server.fuses_adds
    gate, _ = _hold_dispatcher(server)
    ids = np.array([1, 2, 3, 5], np.int32)
    vals = np.ones((4, 8), np.float32)
    handles = [table.add_async(vals, row_ids=ids) for _ in range(8)]
    gate.set()
    for h in handles:
        table.wait(h)
    assert Dashboard.counter_value("APPLY_FUSED_CALLS") == 1
    assert Dashboard.counter_value("APPLY_BATCHED_MSGS") == 8
    hist = Dashboard.histogram("APPLY_BATCH_ROWS")
    assert hist.count == 1 and hist.max == 32.0  # 8 msgs x 4 rows fused
    out = table.get(ids)
    np.testing.assert_array_equal(out, np.full((4, 8), 8.0, np.float32))
    mv.shutdown()


def _run_matrix_workload(batch: bool):
    """The same 24-message integer-delta workload, forced through one
    drain (batch=True) or dispatched per message (apply_batch_msgs=0)."""
    Dashboard.reset()  # isolate each leg's APPLY_* counters
    mv.set_flag("apply_batch_msgs", 64 if batch else 0)
    mv.init()
    table = mv.create_table("matrix", num_row=32, num_col=4)
    rng = np.random.default_rng(11)
    server = Zoo.instance().server
    gate = None
    if batch:
        gate, _ = _hold_dispatcher(server)
    handles = []
    for _ in range(24):
        ids = rng.choice(32, 6, replace=False).astype(np.int32)
        vals = rng.integers(-4, 5, size=(6, 4)).astype(np.float32)
        handles.append(table.add_async(vals, row_ids=ids))
    if gate is not None:
        gate.set()
    for h in handles:
        table.wait(h)
    final = np.asarray(table.get(), np.float32)
    fused = Dashboard.counter_value("APPLY_FUSED_CALLS")
    mv.shutdown()
    return final, fused


def test_batched_final_state_bit_identical_to_unbatched():
    batched, fused = _run_matrix_workload(batch=True)
    unbatched, fused_legacy = _run_matrix_workload(batch=False)
    assert fused >= 1, "the batched run never actually fused"
    assert fused_legacy == 0, "apply_batch_msgs=0 must disable fusing"
    np.testing.assert_array_equal(batched, unbatched)


def test_get_flushes_own_table_first_per_worker_fifo():
    mv.init()
    table_a = mv.create_table("matrix", num_row=16, num_col=4)
    table_b = mv.create_table("matrix", num_row=16, num_col=4)
    server = Zoo.instance().server
    gate, _ = _hold_dispatcher(server)
    ids = np.array([3], np.int32)
    add_a = table_a.add_async(np.full((1, 4), 7.0, np.float32), row_ids=ids)
    add_b = table_b.add_async(np.full((1, 4), 9.0, np.float32), row_ids=ids)
    get_a = table_a.get_async(ids)
    gate.set()
    # the Get drained behind the Adds must observe table A's add (its
    # group flushed first); table B's pending add flushes at drain end
    got = table_a.wait_get(get_a, ids)
    np.testing.assert_array_equal(got, np.full((1, 4), 7.0, np.float32))
    table_a.wait(add_a)
    table_b.wait(add_b)
    np.testing.assert_array_equal(table_b.get(ids),
                                  np.full((1, 4), 9.0, np.float32))
    mv.shutdown()


def test_server_execute_is_full_barrier():
    """A Server_Execute drained behind pending Adds must observe them all
    applied (checkpoint/multihost quiesce rides this message type)."""
    mv.init()
    table = mv.create_table("matrix", num_row=16, num_col=4)
    server = Zoo.instance().server
    gate, _ = _hold_dispatcher(server)
    ids = np.array([2, 4], np.int32)
    handles = [table.add_async(np.ones((2, 4), np.float32), row_ids=ids)
               for _ in range(5)]
    snap_waiter = _ExecWaiter()
    server_table = table._server_table

    def snap():
        return np.asarray(server_table.process_get((ids, None)), np.float32)

    server.send(Message(src=-1, dst=-1, type=MsgType.Server_Execute,
                        data=[snap, snap_waiter]))
    gate.set()
    observed = snap_waiter.wait(30)
    np.testing.assert_array_equal(observed, np.full((2, 4), 5.0, np.float32))
    for h in handles:
        table.wait(h)
    mv.shutdown()


# -- merge units --------------------------------------------------------------

def test_matrix_merge_refuses_incompatible_forms():
    mv.init()
    table = mv.create_table("matrix", num_row=16, num_col=4)
    st = table._server_table
    ids = np.array([1, 2], np.int32)
    vals = np.ones((2, 4), np.float32)
    ok = st.merge_add_requests([(ids, vals, None), (ids, vals, None)])
    assert ok is not None
    merged, rows, consumed = ok
    # the ids concatenated and the values handed on as they came, a piece
    # a request: no dedup (XLA's scatter handles duplicates natively, the
    # pallas path sums them inside process_add) and no copy of the values
    # — the merge itself must stay cheap
    assert rows == 4 and consumed == 2
    np.testing.assert_array_equal(merged[0], np.array([1, 2, 1, 2],
                                                      np.int32))
    assert isinstance(merged[1], RowPieces) and len(merged[1]) == 2
    assert all(np.shares_memory(piece, vals) for piece in merged[1])
    # a whole-table add FIRST refuses outright; an incompatible request
    # mid-group stops the scan — only the compatible prefix fuses
    assert st.merge_add_requests([(None, vals, None),
                                  (ids, vals, None)]) is None
    prefix = st.merge_add_requests([(ids, vals, None),
                                    (None, vals, None),
                                    (ids, vals, None)])
    assert prefix is not None and prefix[2] == 1
    assert len(prefix[0][1]) == 1
    # values that are not yet the table's rows are made so, piece by piece
    listed = st.merge_add_requests([([1, 2], vals.tolist(), None),
                                    (ids, vals.astype(np.float64), None)])
    assert listed is not None and listed[1:] == (4, 2)
    for piece in listed[0][1]:
        assert piece.dtype == np.float32 and piece.shape == (2, 4)
        np.testing.assert_array_equal(piece, vals)
    # the apply_batch_rows cap bounds the fused prefix
    mv.set_flag("apply_batch_rows", 3)
    capped = st.merge_add_requests([(ids, vals, None), (ids, vals, None),
                                    (ids, vals, None)])
    assert capped is not None and capped[1] == 2 and capped[2] == 1
    assert len(capped[0][0]) == 2 and len(capped[0][1]) == 1
    mv.shutdown()


def test_matrix_merge_refuses_stateful_updaters():
    mv.init()
    table = mv.create_table("matrix", num_row=16, num_col=4,
                            updater_type="adagrad")
    ids = np.array([1], np.int32)
    vals = np.ones((1, 4), np.float32)
    assert table._server_table.merge_add_requests(
        [(ids, vals, None), (ids, vals, None)]) is None
    mv.shutdown()


def test_array_and_kv_merge_semantics():
    mv.init()
    arr = mv.create_table("array", 8, np.float32)
    ok = arr._server_table.merge_add_requests(
        [(np.ones(8, np.float32), None), (np.full(8, 2.0, np.float32),
                                          None)])
    assert ok is not None
    (total, _opt), size, consumed = ok
    assert size == 8 and consumed == 2
    np.testing.assert_array_equal(total, np.full(8, 3.0, np.float32))
    # fused add+get (3-tuple) keeps per-request replies: refuse outright
    # when first, stop the prefix when later
    assert arr._server_table.merge_add_requests(
        [(np.ones(8, np.float32), None, True),
         (np.ones(8, np.float32), None)]) is None
    kv = mv.create_table("kv")
    ok = kv._server_table.merge_add_requests(
        [([1, 2], [1.0, 2.0], None), ([2, 3], [5.0, 7.0], None)])
    assert ok is not None
    (keys, values, _opt), n, consumed = ok
    assert n == 4 and consumed == 2
    assert keys == [1, 2, 2, 3] and values == [1.0, 2.0, 5.0, 7.0]
    assert kv._server_table.merge_add_requests(
        [([1], [1.0, 2.0], None)]) is None  # misaligned pair lists
    mv.shutdown()


# -- one pass from the requests' rows to the array that is uploaded -----------

def _row_loop_merge(ids, values):
    """The merge as it was before the one-pass form (a Python loop over
    every row named more than once), kept as the oracle: the distinct
    ids, sorted, and each one's rows summed in arrival order."""
    uniq, inverse, counts = np.unique(ids, return_inverse=True,
                                      return_counts=True)
    order = np.argsort(inverse, kind="stable")
    starts = np.cumsum(counts) - counts
    merged = values[order[starts]]
    for g in np.nonzero(counts > 1)[0]:
        s = starts[g]
        merged[g] = values[order[s:s + counts[g]]].sum(axis=0)
    return uniq.astype(ids.dtype, copy=False), merged


def _inexact_group(requests, sharing, order, cols, rows=24, seed=0):
    """A fused group's ``(ids, values)`` pieces: float32 values of mixed
    magnitudes, so a sum depends on its order in the last bits. ``sharing``
    says how the requests' ids overlap: ``none``, ``some`` (ids drawn from
    a pool twice a request's size; a lone request draws with repeats),
    ``all`` (every request names the same rows; a lone request names each
    twice) or ``every`` (distinct but for one row every request names)."""
    rng = np.random.default_rng([seed, requests, cols, len(sharing),
                                 len(order)])
    pool = rng.permutation(5000).astype(np.int32)
    ids_list = []
    for r in range(requests):
        if sharing == "none":
            ids = pool[r * rows:(r + 1) * rows]
        elif sharing == "some":
            ids = rng.choice(pool[:2 * rows], rows, replace=requests == 1)
        elif sharing == "all":
            ids = rng.permutation(pool[:rows])
            if requests == 1:
                ids = rng.permutation(np.concatenate([ids, ids]))
        else:
            ids = np.concatenate([pool[-1:],
                                  pool[r * rows:(r + 1) * rows - 1]])
            ids = rng.permutation(ids)
        if order != "drawn":
            ids = np.sort(ids)[::-1 if order == "reversed" else 1]
        ids_list.append(np.ascontiguousarray(ids, np.int32))
    pieces = [(rng.standard_normal((len(ids), cols))
               * 10.0 ** rng.integers(-4, 5, (len(ids), 1))
               ).astype(np.float32) for ids in ids_list]
    return ids_list, pieces


def _same_rows(got_ids, got_rows, want_ids, want_rows):
    """Bit for bit, whatever order the distinct ids came back in."""
    by_id = np.argsort(got_ids, kind="stable")
    np.testing.assert_array_equal(np.asarray(got_ids)[by_id], want_ids)
    assert np.asarray(got_rows)[by_id].tobytes() == want_rows.tobytes()


@pytest.mark.parametrize("cols", [128, 300])
@pytest.mark.parametrize("order", ["drawn", "sorted", "reversed"])
@pytest.mark.parametrize("sharing", ["none", "some", "all", "every"])
@pytest.mark.parametrize("requests", [1, 2, 3, 5, 8])
def test_one_pass_merge_is_the_row_loop_bit_for_bit(requests, sharing,
                                                    order, cols):
    ids_list, pieces = _inexact_group(requests, sharing, order, cols)
    ids, values = np.concatenate(ids_list), np.concatenate(pieces)
    want_ids, want_rows = _row_loop_merge(ids, values)
    shared = len(ids) - len(want_ids)
    assert (shared > 0) == (sharing != "none" and (
        requests > 1 or sharing != "every"))
    if sharing == "every":
        assert shared == requests - 1
    # the table's form: pieces into the first rows of a larger array of
    # whole lane tiles, nothing written past them
    found = RowOccurrences(ids)
    assert found.n == len(want_ids)
    lanes = -(-cols // 128) * 128
    staged = np.full((len(ids) + 3, lanes), 7.0, np.float32)
    got_ids = sum_duplicate_rows(ids, pieces, found,
                                 staged[:found.n, :cols])
    assert shared or got_ids is ids
    _same_rows(got_ids, staged[:found.n, :cols], want_ids, want_rows)
    assert (staged[found.n:] == 7.0).all() and (staged[:, cols:] == 7.0).all()
    # the clients' form: one array in, one out; distinct ids come back
    # as they were given, and nothing is copied
    out_ids, out_rows = merge_duplicate_rows(ids, values)
    if shared:
        _same_rows(out_ids, out_rows, want_ids, want_rows)
        np.testing.assert_array_equal(out_ids, got_ids)
        # ids that are not a table's int32 come back in their own dtype
        wide_ids, wide_rows = merge_duplicate_rows(ids.astype(np.int64),
                                                   values)
        assert wide_ids.dtype == np.int64
        np.testing.assert_array_equal(wide_ids, out_ids)
        assert wide_rows.tobytes() == out_rows.tobytes()
    else:
        assert out_ids is ids and out_rows is values


@pytest.mark.parametrize("requests", [1, 3])
def test_one_pass_merge_of_a_row_named_a_thousand_times(requests):
    """One request that names a row again and again (a block's commonest
    word): past ``_VECTOR_RANKS`` entries an id's rest is one ``sum``, not
    a turn an entry; the bits are still the sequential sum's."""
    rng = np.random.default_rng(1000 + requests)
    ids_list, pieces = [], []
    for r in range(requests):
        ids = np.concatenate([np.full(1000, 7), np.full(40, 9),
                              np.full(17, 11), np.full(18, 13),
                              rng.permutation(2000)[:300] + 100])
        ids_list.append(rng.permutation(ids).astype(np.int32))
        pieces.append((rng.standard_normal((len(ids), 128))
                       * 10.0 ** rng.integers(-4, 5, (len(ids), 1))
                       ).astype(np.float32))
    ids, values = np.concatenate(ids_list), np.concatenate(pieces)
    want_ids, want_rows = _row_loop_merge(ids, values)
    found = RowOccurrences(ids)
    staged = np.empty((found.n, 128), np.float32)
    got_ids = sum_duplicate_rows(ids, pieces, found, staged)
    _same_rows(got_ids, staged, want_ids, want_rows)
    out_ids, out_rows = merge_duplicate_rows(ids, values)
    _same_rows(out_ids, out_rows, want_ids, want_rows)


def _open_pallas_gate(monkeypatch):
    """A one-chip table takes the row kernel (interpreted here), so its
    host Adds need distinct ids, as on the TPU."""
    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables import matrix_table
    monkeypatch.setattr(matrix_table, "_use_pallas_scatter",
                        lambda platform, num_shards, *width: num_shards == 1)
    monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)
    mv.init(mesh_shape="1")


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_refilled_staging_cannot_change_an_applied_add(kernel, monkeypatch):
    """The padded ids and values a host Add uploads live in arrays the
    table keeps and refills. Adds of other sizes behind one, a bucket
    that grows between them and one that shrinks, must leave what it
    applied as it was: read back after every Add, and once more after
    the same Adds went in with no read between them."""
    if kernel == "pallas":
        _open_pallas_gate(monkeypatch)
    else:
        mv.init()
    rows, cols = 700, 100
    table = mv.create_table("matrix", num_row=rows, num_col=cols)
    st = table._server_table
    assert st.plan.kernel == (kernel == "pallas")
    rng = np.random.default_rng(31)
    model = np.zeros((rows, cols), np.float32)
    sizes = [5, 70, 9, 200, 130, 3, 64, 300, 1, 600, 2]
    adds = []
    for size in sizes:
        ids = rng.choice(rows, size, replace=False).astype(np.int32)
        # whole multiples of 1/64: the Adds that fuse below sum in another
        # order than the model's, and must not differ for it
        adds.append((ids, (rng.integers(-1024, 1024, (size, cols)) / 64
                           ).astype(np.float32)))
    held = []  # the slot's arrays, as each Add left them
    slot = st._stage
    for ids, vals in adds:
        table.add(vals, row_ids=ids)
        model[ids] += vals
        np.testing.assert_array_equal(np.asarray(table.get()), model)
        assert slot.read and slot.rows == len(ids)
        assert not slot.vals[len(ids):].any()
        assert not slot.vals[:, cols:].any()
        held.append(slot.vals)
    # one array as long as the bucket fits it, a larger one when it grows
    sizes_seen = [len(a) for a in held]
    assert sizes_seen == sorted(sizes_seen) and len(set(sizes_seen)) > 2
    for before, after in zip(held, held[1:]):
        assert (after is before) == (len(after) == len(before))
    handles = []
    for ids, vals in adds:
        handles.append(table.add_async(vals, row_ids=ids))
        model[ids] += vals
    for handle in handles:
        table.wait(handle)
    np.testing.assert_array_equal(np.asarray(table.get()), model)
    mv.shutdown()


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_fused_add_counts_the_rows_it_summed(kernel, monkeypatch):
    """``dups`` on a host Add's TABLE_ROW_PREP record and the counter
    ROW_ADD_DUPLICATES_SUMMED read what the row loop would have summed;
    where XLA's scatter serves the table the device sums and both read
    0. Either way the table holds the sum, to the last bit where one
    summation order is defined (the host's)."""
    from multiverso_tpu import dashboard
    if kernel == "pallas":
        _open_pallas_gate(monkeypatch)
    else:
        mv.init()
    monkeypatch.setattr(Dashboard, "profile_annotations", True)
    ids_list, pieces = _inexact_group(3, "some", "drawn", 128, rows=40)
    table = mv.create_table("matrix", num_row=5000, num_col=128)
    server = Zoo.instance().server
    gate, _ = _hold_dispatcher(server)
    t0 = time.perf_counter()
    handles = [table.add_async(vals, row_ids=ids)
               for ids, vals in zip(ids_list, pieces)]
    gate.set()
    for handle in handles:
        table.wait(handle)
    assert Dashboard.counter_value("APPLY_FUSED_CALLS") == 1
    ids = np.concatenate(ids_list)
    want_ids, want_rows = _row_loop_merge(ids, np.concatenate(pieces))
    summed = len(ids) - len(want_ids)
    assert summed > 0
    records, _ = dashboard.RING.window(t0, time.perf_counter())
    applies = {r.id for r in records if r.stage == "TABLE_PROCESS_ADD"}
    preps = [r for r in records
             if r.stage == "TABLE_ROW_PREP" and r.parent in applies]
    assert len(preps) == 1
    got = np.asarray(table.get(want_ids))
    if kernel == "pallas":
        assert (preps[0].n, preps[0].dups) == (len(want_ids), summed)
        assert Dashboard.counter_value("ROW_ADD_DUPLICATES_SUMMED") == summed
        assert got.tobytes() == want_rows.tobytes()
    else:
        assert (preps[0].n, preps[0].dups) == (len(ids), 0)
        assert Dashboard.counter_value("ROW_ADD_DUPLICATES_SUMMED") == 0
        np.testing.assert_allclose(got, want_rows, rtol=1e-5, atol=1e-5)
    assert Dashboard.counter_value("ROW_STAGE_WAITS") == 0
    mv.shutdown()


# -- gated servers stay per-message -------------------------------------------

def test_gated_servers_never_fuse():
    assert Server.fuses_adds
    assert not DeterministicServer.fuses_adds
    assert not SyncServer.fuses_adds
    assert not SSPServer.fuses_adds


def test_deterministic_server_unaffected_and_reproducible():
    def run():
        mv.set_flag("deterministic", True)
        mv.init()
        table = mv.create_table("matrix", num_row=16, num_col=4)
        rng = np.random.default_rng(3)
        for _ in range(6):
            ids = rng.choice(16, 4, replace=False).astype(np.int32)
            vals = rng.standard_normal((4, 4)).astype(np.float32)
            table.add(vals, row_ids=ids)
        table.finish_train()
        final = np.asarray(table.get(), np.float32)
        fused = Dashboard.counter_value("APPLY_FUSED_CALLS")
        mv.shutdown()
        return final, fused

    final1, fused1 = run()
    final2, fused2 = run()
    assert fused1 == 0 and fused2 == 0
    np.testing.assert_array_equal(final1, final2)


# -- remote end-to-end under multi-producer load ------------------------------

def test_remote_multi_producer_adds_fuse_and_sum_exactly():
    mv.init(remote_workers=2, heartbeat_seconds=0)
    table = mv.create_table("matrix", num_row=64, num_col=8)
    endpoint = mv.serve("127.0.0.1:0")
    client = mv.remote_connect(endpoint)
    rt = client.table(table.table_id)
    ids = np.arange(16, dtype=np.int32)
    vals = np.ones((16, 8), np.float32)
    n_producers, per = 4, 30

    def push():
        handles = []
        for _ in range(per):
            handles.append(rt.add_async(vals, row_ids=ids))
            if len(handles) >= 16:
                rt.wait(handles.pop(0))
        for h in handles:
            rt.wait(h)

    threads = [threading.Thread(target=push) for _ in range(n_producers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_producers * per
    out = np.asarray(rt.get(ids), np.float32)
    np.testing.assert_array_equal(out, np.full((16, 8), float(total),
                                               np.float32))
    assert Dashboard.counter_value("APPLY_BATCHED_MSGS") > 0, \
        "concurrent wire adds never fused"
    client.close()
    mv.shutdown()
