"""The table group (`tables/group_table.py`): N matrix tables of one width in
one slab, a member a table by its own ids, and one Get and one Add for the
rows of all members; against the benchmark's plain reference
(`benchmark/reference/dlrm-mlperf-26tables-emb128.py`, which imports nothing
of the program and hashes the table's index into every value, so a row read
from the wrong member is a wrong value)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark import common
from multiverso_tpu import dashboard
from multiverso_tpu.log import FatalError

ROWS = [3, 10, 300, 5000]
COLS, SEED = 128, 38
CELL = "dlrm26.step-rows"


@pytest.fixture(scope="module")
def ref():
    return common.load_module("reference", "dlrm-mlperf-26tables-emb128")


def _interpreted_kernel(monkeypatch, group=8):
    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables import matrix_table

    monkeypatch.setattr(matrix_table, "_use_pallas_scatter",
                        lambda platform, num_shards, *width: num_shards == 1)
    monkeypatch.setattr(pallas_rows, "ROW_GROUP", group)


def _group(ref, rows=ROWS, cols=COLS, **kw):
    """(group, one mirror a member) from the reference's initial values."""
    init = [ref.init_table(n, cols, SEED, t)[0] for t, n in enumerate(rows)]
    group = mv.create_table("matrix_group", rows, cols, np.float32,
                            updater_type=kw.pop("updater_type", "default"),
                            init_values=init, **kw)
    return group, [ref.Mirror(cols, SEED, t) for t in range(len(rows))]


def _step(ref, rng, mirrors, rows=ROWS, cols=COLS, most=40):
    """One pooled step: distinct ids a member, its delta in units, the
    segments' offsets; registered with every member's mirror."""
    parts = [rng.choice(n, min(n, most), replace=False).astype(np.int32)
             for n in rows]
    at = np.concatenate([[0], np.cumsum([len(p) for p in parts])])
    dk = ref.delta_k(rng, int(at[-1]), cols)
    for t, mirror in enumerate(mirrors):
        mirror.add_pool(parts[t], dk[at[t]:at[t + 1]])
    return parts, at, dk


def _slab_wrong(ref, group, mirrors, counts, rows=ROWS, cols=COLS):
    """Elements of the whole slab that differ from the members' mirrors;
    the scratch rows and the lanes past the columns must be zero."""
    data = np.asarray(group.get_device())
    bases = np.concatenate([[0], np.cumsum(rows)])
    assert not data[bases[-1]:].any() and not data[:, cols:].any()
    return sum(ref.mismatches(
        data[bases[t]:bases[t + 1], :cols],
        mirrors[t].rows_k(np.arange(n), counts))
        for t, n in enumerate(rows))


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_group_and_member_ops_against_the_reference(ref, kernel,
                                                    monkeypatch):
    """Members of 3, 10, 300 and 5,000 rows: the group's device and host
    ops and the members' own, every element against the reference's mirror
    of the member it belongs to; the Get's offsets; rows no op names keep
    their bits (the whole slab is compared); one message and ONE launch a
    group op, its `TABLE_ROW_PREP` counting the rows of all members."""
    import jax

    if kernel == "pallas":
        _interpreted_kernel(monkeypatch)
    mv.init(mesh_shape="1")
    group, mirrors = _group(ref)
    server = group._server_table
    assert server.plan.kernel == (kernel == "pallas")
    assert group.num_rows == ROWS and group.num_row == sum(ROWS)
    assert [m.num_row for m in group.tables] == ROWS
    rng = np.random.default_rng(SEED)
    parts, at, dk = _step(ref, rng, mirrors)
    ids, lengths = np.concatenate(parts), np.diff(at)
    assert _slab_wrong(ref, group, mirrors, [0]) == 0

    monkeypatch.setattr(dashboard.Dashboard, "profile_annotations", True)
    t0 = time.perf_counter()
    group.wait(group.add_device_async(jax.device_put(ref.to_float(dk)), ids,
                                      lengths))
    out, offsets = group.wait_device(group.get_device_async(parts))
    records, _ = dashboard.RING.window(t0, time.perf_counter())
    monkeypatch.setattr(dashboard.Dashboard, "profile_annotations", False)
    np.testing.assert_array_equal(offsets, at)
    assert out.shape == (256 if kernel == "xla" else 128, 128)
    got = np.asarray(out)
    for t, mirror in enumerate(mirrors):
        assert ref.mismatches(got[at[t]:at[t + 1]],
                              mirror.rows_k(parts[t], [1])) == 0
    launches = [r for r in records if r.stage == "TABLE_ROW_LAUNCH"]
    assert len(launches) == 2
    assert [r.n for r in records
            if r.stage == "TABLE_ROW_PREP"] == [at[-1]] * 2
    assert launches[0].path == ("pallas" if kernel == "pallas" else "xla")
    # the Get names the Add's rows: it launches on the ids the Add sent up
    # and checks no segment again
    checked = [r for r in records if r.stage == "WORKER_GROUP_IDS"]
    assert [r.n for r in checked] == [at[-1]]
    assert [r.bytes for r in records
            if r.stage == "WORKER_ROW_IDS"] == [4 * out.shape[0], 0]
    by_id = {r.id: r for r in records if r.id}
    assert all(by_id[r.parent].stage == "WORKER_ROW_IDS" for r in checked)
    for stage in ("TABLE_PROCESS_ADD", "TABLE_PROCESS_GET"):
        assert sum(r.stage == stage for r in records) == 1

    # the host forms: (ids, lengths) and a list of one id array a member
    group.add(ref.to_float(dk), parts)
    rows, offsets = group.get(ids, lengths)
    np.testing.assert_array_equal(offsets, at)
    assert rows.shape == (at[-1], COLS)
    for t, mirror in enumerate(mirrors):
        assert ref.mismatches(rows[at[t]:at[t + 1]],
                              mirror.rows_k(parts[t], [2])) == 0

    # a member is a table by its own ids: host and device, Get and Add
    for t, member in enumerate(group.tables):
        mine = dk[at[t]:at[t + 1]]
        member.add(ref.to_float(mine), parts[t])
        member.wait(member.add_device_async(
            jax.device_put(ref.to_float(mine)), parts[t]))
        assert ref.mismatches(member.get(parts[t]),
                              mirrors[t].rows_k(parts[t], [4])) == 0
        held = member.wait_device(member.get_device_async(parts[t]),
                                  parts[t])
        assert ref.mismatches(np.asarray(held)[:len(parts[t]), :COLS],
                              mirrors[t].rows_k(parts[t], [4])) == 0
        # the whole member, as a row range of the slab
        assert ref.mismatches(member.get(), mirrors[t].rows_k(
            np.arange(ROWS[t]), [4])) == 0
    assert _slab_wrong(ref, group, mirrors, [4]) == 0
    count = dashboard.Dashboard.counter_value
    assert (count("GROUP_OPS_ADD"), count("GROUP_OPS_GET")) == (2, 2)
    assert count("GROUP_MEMBER_OPS") == 5 * len(ROWS)


# who sends the id that is one past member 1's end: its own rows are
# [0, 10), and row 10 of the slab's member 1 is member 2's first row
PAST_THE_END = {
    "group host get": lambda g, d: g.get([[], [10], [], []]),
    "group host add": lambda g, d: g.add(d, [[], [10], [], []]),
    "group device get": lambda g, d: g.wait_device(
        g.get_device_async([[], [10], [], []])),
    "group device add": lambda g, d: g.wait(
        g.add_device_async(d, [10], [0, 1, 0, 0])),
    "group negative id": lambda g, d: g.add(d, [[], [], [-1], []]),
    "member host get": lambda g, d: g.tables[1].get(np.array([10])),
    "member host add": lambda g, d: g.tables[1].add(d, np.array([10])),
    "member device get": lambda g, d: g.tables[1].get_device_async(
        np.array([10])),
    "member device add": lambda g, d: g.tables[1].add_device_async(
        d, np.array([10])),
    "member whole add": lambda g, d: g.tables[1].add(np.ones((10, COLS))),
}


@pytest.mark.parametrize("sender", sorted(PAST_THE_END))
def test_an_id_past_a_members_end_is_refused(ref, sender, monkeypatch):
    """An id equal to a member's `num_row` (a matrix table's device path
    lets pads aim there: here it is the next member's first row), a
    negative id, a whole-table Add: refused by name on every path, and the
    slab is bit for bit what it was."""
    import jax

    _interpreted_kernel(monkeypatch)
    mv.init(mesh_shape="1")
    group, mirrors = _group(ref)
    before = np.asarray(group.get_device()).copy()
    delta = np.ones((1, COLS), np.float32)
    if "device" in sender:
        delta = jax.device_put(delta)
    with pytest.raises(FatalError, match="matrix_group"):
        PAST_THE_END[sender](group, delta)
    np.testing.assert_array_equal(np.asarray(group.get_device()), before)
    # and the group still serves
    rows, _ = group.get([[2], [9], [0], [4999]])
    assert ref.mismatches(rows[1:2], mirrors[1].rows_k([9], [0])) == 0


def test_empty_segments_and_a_step_in_one_member(ref, monkeypatch):
    """A step that names no row of some members, and one whose rows are all
    in one member: the offsets repeat where a segment is empty."""
    import jax

    _interpreted_kernel(monkeypatch)
    mv.init(mesh_shape="1")
    group, mirrors = _group(ref)
    rng = np.random.default_rng(1)
    parts = [np.zeros(0, np.int32), np.array([9, 0], np.int32),
             np.zeros(0, np.int32), np.array([4999, 7, 0], np.int32)]
    dk = ref.delta_k(rng, 5, COLS)
    mirrors[1].add_pool(parts[1], dk[:2])
    mirrors[3].add_pool(parts[3], dk[2:])
    monkeypatch.setattr(dashboard.Dashboard, "profile_annotations", True)
    t0 = time.perf_counter()
    group.wait(group.add_device_async(jax.device_put(ref.to_float(dk)),
                                      parts))
    only = [np.zeros(0, np.int32)] * 3 + [np.arange(40, dtype=np.int32)]
    out, offsets = group.wait_device(group.get_device_async(only))
    records, _ = dashboard.RING.window(t0, time.perf_counter())
    assert [r.n for r in records if r.stage == "TABLE_ROW_PREP"] == [5, 40]
    np.testing.assert_array_equal(offsets, [0, 0, 0, 0, 40])
    assert ref.mismatches(np.asarray(out)[:40, :COLS],
                          mirrors[3].rows_k(only[3], [1, 1])) == 0
    rows, offsets = group.get(parts)
    np.testing.assert_array_equal(offsets, [0, 0, 2, 2, 5])
    assert _slab_wrong(ref, group, mirrors, [1, 1]) == 0
    # a step that names nothing at all is served too
    rows, offsets = group.get([[], [], [], []])
    assert rows.shape == (0, COLS) and not offsets.any()


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_a_delta_longer_than_its_ids_is_one_program(ref, kernel,
                                                    monkeypatch):
    """A trainer's delta has ONE shape (the device Get's bucket) and every
    step another count of rows: the delta's rows past the ids are not
    applied, whatever they hold (the scratch rows stay zero: `_slab_wrong`),
    and the steps share one device program; a delta of exactly the ids'
    rows compiles a program a count. Through the group, a member and a
    plain matrix table."""
    import jax
    from multiverso_tpu.ops import pallas_rows

    if kernel == "pallas":
        _interpreted_kernel(monkeypatch)
    mv.init(mesh_shape="1")
    group, mirrors = _group(ref)
    server = group._server_table
    program = (pallas_rows._scatter_add_call if kernel == "pallas"
               else server.plan.scatter_add)
    rng = np.random.default_rng(SEED + 1)
    held = 128      # the delta's rows, every step
    # the first count warms the program; it serves the counts that follow
    for j, most in enumerate([40, 17, 31, 5]):
        parts, at, dk = _step(ref, rng, mirrors, most=most)
        delta = np.full((held, COLS), 1e9, np.float32)   # garbage past n
        delta[:at[-1]] = ref.to_float(dk)
        if j == 1:
            programs = program._cache_size()
        group.wait(group.add_device_async(jax.device_put(delta), parts))
        assert _slab_wrong(ref, group, mirrors, [1] * (j + 1)) == 0
    assert program._cache_size() == programs
    # a delta of exactly the ids' rows: another program
    parts, at, dk = _step(ref, rng, mirrors, most=9)
    group.wait(group.add_device_async(jax.device_put(ref.to_float(dk)),
                                      parts))
    assert program._cache_size() == programs + 1
    counts = [1] * 5
    # a member, by its own ids, and a plain table
    parts, at, dk = _step(ref, rng, mirrors, most=12)
    counts.append(1)
    for t, member in enumerate(group.tables):
        delta = np.full((64, COLS), -1e9, np.float32)
        delta[:len(parts[t])] = ref.to_float(dk[at[t]:at[t + 1]])
        member.wait(member.add_device_async(jax.device_put(delta),
                                            parts[t]))
    assert _slab_wrong(ref, group, mirrors, counts) == 0
    plain = mv.create_table("matrix", 50, COLS, np.float32)
    delta = np.full((32, COLS), 7.0, np.float32)
    plain.wait(plain.add_device_async(jax.device_put(delta),
                                      np.array([49, 3, 11])))
    want = np.zeros((50, COLS), np.float32)
    want[[49, 3, 11]] = 7.0
    np.testing.assert_array_equal(plain.get(), want)
    assert not np.asarray(plain.get_device())[50:].any()


LONGER_REFUSED = {
    "a stateful updater": (dict(mesh_shape="1"), "rowwise_adagrad",
                           "longer than its ids"),
    # the row kernel on every shard's block, the Add routed to its owners
    "four devices": (dict(mesh_shape="4"), "default", "longer than its ids"),
}


@pytest.mark.parametrize("where", sorted(LONGER_REFUSED))
def test_a_longer_delta_is_refused_by_name_elsewhere(where, monkeypatch):
    import jax
    from multiverso_tpu.updaters import AddOption

    flags, updater, why = LONGER_REFUSED[where]
    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables import matrix_table
    monkeypatch.setattr(matrix_table, "_use_pallas_scatter",
                        lambda *shape: True)
    monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)
    mv.init(**flags)
    plain = mv.create_table("matrix", 64, COLS, np.float32,
                            updater_type=updater)
    delta = jax.device_put(np.ones((8, COLS), np.float32))
    with pytest.raises(FatalError, match=why):
        plain.wait(plain.add_device_async(
            delta, np.arange(5), AddOption(learning_rate=0.1, rho=1e-8)))
    plain.wait(plain.add_device_async(
        delta[:5], np.arange(5), AddOption(learning_rate=0.1, rho=1e-8)))


def test_an_op_of_131072_rows_is_refused_by_name(ref):
    mv.init(mesh_shape="1")
    group = mv.create_table("matrix_group", [3, 200_000], 8, np.float32)
    ids = np.arange(131_072 - 3, dtype=np.int32)
    with pytest.raises(FatalError, match="131072.*Queue 2 item 2"):
        group.get([np.arange(3), ids])
    rows, _ = group.get([np.arange(3), ids[:-1]])     # 131,071: served
    assert rows.shape == (131_071, 8) and not rows.any()
    with pytest.raises(FatalError, match="one length a member"):
        group.get(ids[:10], [10])
    import jax.numpy as jnp
    with pytest.raises(FatalError, match="delta of 131073.*Queue 2 item 2"):
        group.add_device_async(jnp.zeros((131_073, 8)), [[0], [5]])


@pytest.mark.parametrize("updater", ["adagrad", "momentum_sgd", "dcasgd",
                                     "rowwise_adagrad"])
def test_a_stateful_updater_is_refused_by_name(updater):
    mv.init(mesh_shape="1")
    with pytest.raises(FatalError, match=f"matrix_group.*{updater}"):
        mv.create_table("matrix_group", [3, 10], 8, np.float32,
                        updater_type=updater)


def test_sgd_group_subtracts(ref):
    mv.init(mesh_shape="1")
    group, _ = _group(ref, rows=[3, 10], updater_type="sgd")
    before, _ = group.get([[1], [9]])
    group.add(np.full((2, COLS), 0.5, np.float32), [[1], [9]])
    after, _ = group.get([[1], [9]])
    np.testing.assert_array_equal(after, before - 0.5)


def test_store_load_round_trip_and_another_layout_refused(ref, tmp_path):
    """The slab's file carries the members' row counts: it loads into a
    group of the same layout, and a group of other members refuses it by
    name (the same rows in all, cut elsewhere)."""
    from multiverso_tpu.checkpoint import load_table, store_table

    mv.init(mesh_shape="1")
    group, mirrors = _group(ref)
    rng = np.random.default_rng(3)
    parts, at, dk = _step(ref, rng, mirrors)
    group.add(ref.to_float(dk), parts)
    path = str(tmp_path / "group.mvckpt")
    store_table(group, path)
    fresh = mv.create_table("matrix_group", ROWS, COLS, np.float32)
    load_table(fresh, path)
    assert _slab_wrong(ref, fresh, mirrors, [1]) == 0
    other = mv.create_table("matrix_group", [3, 10, 301, 4999], COLS,
                            np.float32)
    with pytest.raises(FatalError, match="layout it was stored under"):
        load_table(other, path)
    assert not np.asarray(other.get_device()).any()


def test_four_devices_serve_the_group(ref):
    """On a mesh of four (virtual) devices the slab is the sharded matrix
    table as it stands: the dispatcher sends the ids up, the group's ops and
    the members' agree with the reference."""
    import jax

    mv.init(mesh_shape="4")
    group, mirrors = _group(ref)
    assert not group._server_table.ids_at_submit
    rng = np.random.default_rng(4)
    parts, at, dk = _step(ref, rng, mirrors)
    group.wait(group.add_device_async(jax.device_put(ref.to_float(dk)),
                                      parts))
    out, offsets = group.wait_device(group.get_device_async(parts))
    got = np.asarray(out)
    for t, mirror in enumerate(mirrors):
        assert ref.mismatches(got[at[t]:at[t + 1], :COLS],
                              mirror.rows_k(parts[t], [1])) == 0
    group.add(ref.to_float(dk), parts)
    assert ref.mismatches(group.tables[2].get(), mirrors[2].rows_k(
        np.arange(ROWS[2]), [2])) == 0
    # XLA's partitioned scatter takes a delta longer than its ids too
    longer = np.full((128, COLS), 1e9, np.float32)
    longer[:at[-1]] = -ref.to_float(dk)
    group.wait(group.add_device_async(jax.device_put(longer), parts))
    group.add(ref.to_float(dk), parts)
    with pytest.raises(FatalError, match="member 0 row id out of range"):
        group.wait(group.add_device_async(
            jax.device_put(np.ones((1, COLS), np.float32)),
            [[3], [], [], []]))
    assert _slab_wrong(ref, group, mirrors, [2]) == 0


@pytest.mark.parametrize("flags", [dict(sync=True), dict(deterministic=True),
                                   dict(sync=True, ssp_staleness=1)],
                         ids=["sync", "deterministic", "ssp"])
def test_clocked_servers_serve_the_group(ref, flags):
    """The BSP, SSP and deterministic servers see one matrix table: a group
    op and a member op are each one message on the slab's table id (a BSP
    worker alternates Add and Get, so the rounds here do)."""
    mv.init(mesh_shape="1", **flags)
    group, mirrors = _group(ref)
    rng = np.random.default_rng(5)
    parts, at, dk = _step(ref, rng, mirrors)
    group.add(ref.to_float(dk), parts)
    rows, _ = group.get(parts)
    for t, mirror in enumerate(mirrors):
        assert ref.mismatches(rows[at[t]:at[t + 1]],
                              mirror.rows_k(parts[t], [1])) == 0
    group.tables[3].add(ref.to_float(dk[at[3]:]), parts[3])
    mirrors[3].add_pool(parts[3], dk[at[3]:])
    assert ref.mismatches(group.tables[3].get(parts[3]),
                          mirrors[3].rows_k(parts[3], [1, 1])) == 0


def test_a_remote_client_refuses_the_group_by_name():
    mv.init(mesh_shape="1", remote_workers=1)
    group = mv.create_table("matrix_group", [3, 10], 8, np.float32)
    plain = mv.create_table("matrix", 4, 8, np.float32)
    client = mv.remote_connect(mv.serve("127.0.0.1:0"))
    try:
        assert sorted(s["kind"] for s in client.directory) == [
            "matrix", "matrix_group"]
        with pytest.raises(KeyError, match="matrix_group.*not served to "
                                           "remote workers"):
            client.table(group.table_id)
        assert client.table(plain.table_id).get().shape == (4, 8)
    finally:
        client.close()


@pytest.mark.parametrize("kind", ["group", "matrix", "matrix on four"])
def test_a_table_goes_up_piece_by_piece_from_a_block_source(ref, kind,
                                                            monkeypatch):
    """The slab and a plain matrix table take their initial values from a
    block source through the same lines: asked in row order, never for
    more rows than a piece holds, every row once; the table equals what
    the source gave. Four devices: a shard piece by piece."""
    from multiverso_tpu.parallel import mesh as mesh_lib

    monkeypatch.setattr(mesh_lib, "PIECE_BYTES", 100 * COLS * 4)
    mv.init(mesh_shape="4" if kind.endswith("four") else "1")
    asked = []

    def source(table):
        def rows(lo, n):
            asked.append((table, lo, n))
            return ref.init_rows(lo, n, COLS, SEED, table)[0]
        return rows

    if kind == "group":
        made = mv.create_table("matrix_group", ROWS, COLS, np.float32,
                               init_values=[source(t) for t in range(4)])
        mirrors = [ref.Mirror(COLS, SEED, t) for t in range(4)]
        assert _slab_wrong(ref, made, mirrors, []) == 0
        for t, n in enumerate(ROWS):
            mine = [(lo, k) for table, lo, k in asked if table == t]
            assert [lo for lo, _ in mine] == list(np.cumsum(
                [0] + [k for _, k in mine[:-1]]))
            assert sum(k for _, k in mine) == n
    else:
        made = mv.create_table("matrix", 1003, COLS, np.float32,
                               init_value=source(0))
        assert ref.mismatches(made.get(), ref.init_k(
            np.arange(1003), COLS, SEED, 0)) == 0
        assert sum(n for *_, n in asked) == 1003
        assert [lo for _, lo, _ in asked] == sorted(lo for _, lo, _ in asked)
    assert max(n for *_, n in asked) <= 100 and len(asked) > 10


def _fake_run(records, t0, t1):
    from benchmark import op_trace

    class Run:
        window = (t0, t1)
        _op_trace = op_trace.Trace(records, int(t0 * 1e9), int(t1 * 1e9))
    return Run()


def test_the_groups_per_layer_readers(ref, monkeypatch):
    """`group_ids_ms`, `group_launches_per_op` and `group_slots_share` over a
    window of group ops (interpreted kernel, row group 8): one launch an op,
    the Adds' slots the row groups of the rows named under a delta of the
    Get's bucket; None over a window of plain matrix ops, which holds no
    `WORKER_GROUP_IDS`."""
    import jax

    _interpreted_kernel(monkeypatch)
    mv.init(mesh_shape="1")
    group, mirrors = _group(ref)
    plain = mv.create_table("matrix", 50, COLS, np.float32)
    rng = np.random.default_rng(6)
    steps = [_step(ref, rng, mirrors) for _ in range(3)]
    monkeypatch.setattr(dashboard.Dashboard, "profile_annotations", True)
    t0 = time.perf_counter()
    named = 0
    for parts, at, dk in steps:
        delta = jax.device_put(np.concatenate(
            [ref.to_float(dk), np.ones((128 - at[-1], COLS), np.float32)]))
        group.wait(group.add_device_async(delta, parts))
        group.wait_device(group.get_device_async(parts))
        named += -(-int(at[-1]) // 8) * 8
    t1 = time.perf_counter()
    plain.wait(plain.add_device_async(delta[:5], np.arange(5)))
    plain.get(np.arange(5))
    t2 = time.perf_counter()
    records, _ = dashboard.RING.window(t0, t2)
    mine = [r for r in records if r.start_ns < t1 * 1e9]
    run = _fake_run(mine, t0, t1)
    read = {name: common.load_module("layers", name).read
            for name in ("group_ids_ms", "group_launches_per_op",
                         "group_slots_share")}
    # the reader counts group ops by their WORKER_GROUP_IDS, and a step's
    # Get launches on its Add's ids without one (PR 39): two launches a
    # span where every op is one launch
    assert read["group_launches_per_op"](run) == 2.0
    assert read["group_slots_share"](run) == pytest.approx(
        100.0 * named / sum(int(at[-1]) for _, at, _ in steps))
    assert 0 < read["group_ids_ms"](run) < 50
    other = _fake_run([r for r in records if r.start_ns >= t1 * 1e9], t1, t2)
    assert [fn(other) for fn in read.values()] == [None, None, None]


def _rehearse(prelude="", seed=2147538011):
    code = (prelude + "\nimport sys; from benchmark import run; "
            f"sys.exit(run.main(['--workload', {CELL!r}, '--seed', "
            f"'{seed}', '--seconds', '1', '--trace', '0', '--rehearse']))")
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=common.ROOT, capture_output=True,
        text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=common.ROOT, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    compared = {c["compared"]: c for c in map(json.loads, (
        x for x in lines if x.startswith('{"compared"')))}
    named = [json.loads(x) for x in lines if x.startswith('{"members_wrong"')]
    return json.loads(lines[-1]), compared, named


COMPARISONS = ["replay_mismatch", "window_get_mismatch",
               "final_sample_mismatch", "member_edge_mismatch",
               "small_member_mismatch", "checksum_mismatch_columns"]


def test_the_cell_rehearses_correct():
    """`dlrm26.step-rows` end to end at rehearsal sizes on the CPU: 26
    members, every op a group op, every comparison 0 beside its limit."""
    last, compared, named = _rehearse()
    assert last["correct"] is True and last["failed"] == 0
    assert sorted(compared) == sorted(COMPARISONS) and not named
    assert all(c["ok"] and c["limit"] == 0 for c in compared.values())
    counts = last["counts"]
    assert counts["tables"] == 26 and counts["adds"] == counts["gets"]
    assert counts["rows"] == 2 * counts["add_rows"]


# member 7's base one row too far in the group's own table of bases: its
# segment of every group op lands one row on, its last row in member 8
WRONG_BASE = """
from multiverso_tpu.tables import group_table
_init = group_table.MatrixGroupWorker.__init__
def _shifted(self, *args, **kwargs):
    _init(self, *args, **kwargs)
    self._bases[7] += 1
group_table.MatrixGroupWorker.__init__ = _shifted
"""


def test_a_wrong_base_fails_the_cells_comparisons():
    """One member's ids shifted by one row: the group op reads and writes
    its neighbour rows, consistently, so only values tell; the reference's
    hash of (table, row) does. `correct` is false and the member is
    named."""
    last, compared, named = _rehearse(WRONG_BASE)
    assert last["correct"] is False
    wrong = {name for name, c in compared.items() if not c["ok"]}
    assert {"replay_mismatch", "window_get_mismatch",
            "final_sample_mismatch"} <= wrong
    # the sums do not move: the same deltas landed, one row on
    assert compared["checksum_mismatch_columns"]["ok"]
    # member 7's rows, and member 8's first where a step named member 7's
    # last row
    tables = named[0]["members_wrong"]
    assert set().union(*tables.values()) <= {7, 8}
    assert all(7 in tables[name] for name in (
        "replay_mismatch", "window_get_mismatch", "final_sample_mismatch"))
