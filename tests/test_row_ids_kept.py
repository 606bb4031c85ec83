"""A trainer's push names the rows of its pull: a proxy keeps the ids its
last in-process device-path op sent up, and the next op of that proxy that
names the same rows launches on them (`MatrixWorker._ids_at_submit`,
`KeptIds`); on a table sharded over the chips of one process the row plan
keeps the routed ids (`RowPlan.launch_ids`). Through `mv.create_table` and
the public ops on a table on one device and on four, results against numpy;
counts and bytes from a CPU run, never a speed."""

import threading
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark import common, op_trace
from multiverso_tpu import dashboard
from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.log import FatalError

ROWS, COLS = 3000, 128


def _kept():
    return Dashboard.counter_value("ROW_IDS_KEPT")


def _init(rows=ROWS, cols=COLS):
    """Row r holds r + column / 1024: a row read from the wrong place is a
    wrong value, and whole sixty-fourths add exactly."""
    return (np.arange(rows, dtype=np.float32)[:, None]
            + np.arange(cols, dtype=np.float32)[None, :] / 1024)


def _table(rows=ROWS, cols=COLS, **kw):
    return mv.create_table("matrix", rows, cols, np.float32,
                           init_value=_init(rows, cols), **kw)


def _ids(rng, n, rows=ROWS):
    return rng.choice(rows, n, replace=False).astype(np.int32)


def _delta(rng, n, cols=COLS):
    return rng.integers(-64, 64, (n, cols)).astype(np.float32) / 64


def _kernel(monkeypatch, kernel):
    """`pallas`: the row kernel serves the table, interpreted, at a row
    group of 8 (`routed`: on every shard of a mesh too, the ops routed to
    it); `xla`: what a CPU mesh runs."""
    if kernel == "xla":
        return
    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables import matrix_table

    monkeypatch.setattr(
        matrix_table, "_use_pallas_scatter",
        lambda platform, num_shards, *width: (kernel == "routed"
                                              or num_shards == 1))
    monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)


def _get(table, ids):
    return np.asarray(table.wait_device(table.get_device_async(ids), ids))


def _add(table, delta, ids, option=None):
    import jax

    table.wait(table.add_device_async(jax.device_put(delta), ids, option))


class _Window:
    """The op trace's records of the ops made inside the block."""

    def __init__(self, monkeypatch):
        self._patch = monkeypatch

    def __enter__(self):
        self._patch.setattr(Dashboard, "profile_annotations", True)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        from multiverso_tpu.runtime.zoo import Zoo

        Zoo.instance().server.run_serialized(lambda: None)
        self.t1 = time.perf_counter()
        self.records, _ = dashboard.RING.window(self.t0, self.t1)
        self._patch.setattr(Dashboard, "profile_annotations", False)

    def of(self, stage):
        return [r for r in self.records if r.stage == stage]


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("order", ["add-get", "get-add"])
def test_the_same_rows_launch_on_the_ids_already_up(order, kernel,
                                                    monkeypatch):
    """An Add then a Get of the same ids, and a Get then an Add: the second
    op sends nothing up (`ROW_IDS_KEPT`, its `WORKER_ROW_IDS` has `bytes`
    0), its launch says `caller` and found its ids landed, the rows come
    back and land right, and no program has deleted the kept array."""
    _kernel(monkeypatch, kernel)
    mv.init(mesh_shape="1")
    table = _table()
    rng = np.random.default_rng(39)
    ids, delta = _ids(rng, 700), _delta(rng, 700)
    want = _init()
    before = _kept()
    with _Window(monkeypatch) as window:
        if order == "add-get":
            _add(table, delta, ids)
            want[ids] += delta
            got = _get(table, ids)
        else:
            got = _get(table, ids)
            np.testing.assert_array_equal(got[:700], want[ids])
            _add(table, delta, ids)
            want[ids] += delta
    assert _kept() == before + 1
    bucket = got.shape[0]
    assert bucket == 1024
    assert [(r.n, r.bytes) for r in window.of("WORKER_ROW_IDS")] == [
        (700, 4 * bucket), (700, 0)]
    launches = window.of("TABLE_ROW_LAUNCH")
    assert [r.ids_from for r in launches] == ["caller"] * 2
    assert launches[1].ids_ready == 1
    if order == "add-get":
        np.testing.assert_array_equal(got[:700], want[ids])
        # the slots past the ids are the sentinel's copies, as ever
        assert not got[700:].any()
    np.testing.assert_array_equal(table.get(), want)
    took = table._kept.took
    assert not took.ids.is_deleted()
    np.testing.assert_array_equal(np.asarray(took.ids)[:700], ids)
    np.testing.assert_array_equal(np.asarray(took.ids)[700:],
                                  table.sentinel_row)


def test_ids_overwritten_in_place_miss_and_read_the_new_rows():
    """The caller may write its id array again as soon as an op has
    returned: the SAME array object holding other ids is another op, by
    the proxy's own copy of what went up."""
    mv.init(mesh_shape="1")
    table = _table()
    rng = np.random.default_rng(1)
    ids, delta = _ids(rng, 500), _delta(rng, 500)
    first = ids.copy()
    _add(table, delta, ids)
    before = _kept()
    ids[:] = _ids(rng, 500)
    assert (ids != first).any()
    got = _get(table, ids)
    assert _kept() == before
    want = _init()
    want[first] += delta
    np.testing.assert_array_equal(got[:500], want[ids])
    # and written back, the first rows again: what is kept is the second
    ids[:] = first
    np.testing.assert_array_equal(_get(table, ids)[:500], want[first])
    assert _kept() == before


@pytest.mark.parametrize("other", ["shorter", "longer", "first id",
                                   "last id", "an id in the middle",
                                   "another table", "another bucket"])
def test_other_rows_miss(other):
    """What differs from the kept op in its count, its first, its last or
    any one id, a Get whose bucket is not the Add's, or the same ids named
    to another table's proxy: a miss, and the rows asked for."""
    mv.init(mesh_shape="1")
    table, second = _table(), _table()
    rng = np.random.default_rng(2)
    n = 512 if other == "another bucket" else 400
    ids = np.sort(_ids(rng, n + 1))[:n]
    _add(table, np.zeros((n, COLS), np.float32), ids)
    asked, reader = ids.copy(), table
    spare = int(np.setdiff1d(np.arange(ROWS), ids)[7])
    if other == "shorter":
        asked = ids[:-1]
    elif other == "longer":
        asked = np.append(ids, np.int32(spare))
    elif other == "first id":
        asked[0] = spare
    elif other == "last id":
        asked[-1] = spare
    elif other == "an id in the middle":
        asked[n // 2] = spare
    elif other == "another table":
        reader = second
    before = _kept()
    got = _get(reader, asked)
    assert _kept() == before
    # 512 ids: the Add's bucket is 512 and the Get's, which keeps a
    # sentinel slot, 1,024
    assert got.shape[0] == (1024 if other == "another bucket" else 512)
    np.testing.assert_array_equal(got[:len(asked)], _init()[asked])


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("n,hits", [(600, True), (1000, False)])
def test_a_count_in_the_last_slot_is_never_gathered(n, hits, kernel,
                                                    monkeypatch):
    """An Add whose delta has more rows than ids puts the count of ids in
    the bucket's last slot. The Get of the same ids takes that array where
    it gathers less than the bucket (600 ids: 616 slots of 1,024) and sends
    its own where it gathers the whole bucket (1,000 ids); right rows both
    ways, and the Add after a Get, which needs the count, sends its own."""
    from multiverso_tpu.tables.matrix_table import _live_slots

    _kernel(monkeypatch, kernel)
    mv.init(mesh_shape="1")
    table = _table()
    rng = np.random.default_rng(n)
    ids, delta = _ids(rng, n), _delta(rng, 1024)
    assert (_live_slots(n, 1024) < 1024) == hits
    want = _init()
    before = _kept()
    _add(table, delta, ids)
    assert table._kept.took.counted
    assert int(np.asarray(table._kept.took.ids)[-1]) == n
    want[ids] += delta[:n]
    got = _get(table, ids)
    assert _kept() == before + hits
    np.testing.assert_array_equal(got[:n], want[ids])
    assert not got[n:].any()
    # the same Add again: after a hit the kept array is the counted one
    # still; after a miss it is the Get's, whose last slot is the sentinel
    _add(table, delta, ids)
    want[ids] += delta[:n]
    assert _kept() == before + 2 * hits
    assert table._kept.took.counted
    np.testing.assert_array_equal(table.get(), want)
    # and a delta of the ids' own rows wants the sentinel there
    _add(table, delta[:n], ids)
    want[ids] += delta[:n]
    assert _kept() == before + 2 * hits
    np.testing.assert_array_equal(table.get(), want)


def test_depth_one_a_third_op_replaces_what_is_kept():
    """A, B, A: the second A finds B kept and misses; A, A hits."""
    mv.init(mesh_shape="1")
    table = _table()
    rng = np.random.default_rng(3)
    a, b = _ids(rng, 300), _ids(rng, 300)
    before = _kept()
    for ids in (a, b, a):
        np.testing.assert_array_equal(_get(table, ids)[:300], _init()[ids])
    assert _kept() == before
    np.testing.assert_array_equal(_get(table, a)[:300], _init()[a])
    assert _kept() == before + 1


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_the_get_after_a_stateful_add_hits(kernel, monkeypatch):
    """Under `rowwise_adagrad` the Add is the state step and the scatter
    in one program that donates the table and the state, not the ids: the
    Get after it launches on them and reads the stepped rows."""
    _kernel(monkeypatch, kernel)
    mv.init(mesh_shape="1")
    table = _table(updater_type="rowwise_adagrad")
    rng = np.random.default_rng(4)
    ids, grad = _ids(rng, 300), _delta(rng, 300)
    option = mv.AddOption(learning_rate=0.01, rho=1e-10)
    before = _kept()
    for _ in range(2):
        _add(table, grad, ids, option)
        got = _get(table, ids)
        np.testing.assert_array_equal(got[:300], table.get(ids))
    # the Get after each Add, and the second Add after the first Get
    assert _kept() == before + 3
    s = np.asarray(table.get_state_device("s"))[ids]
    np.testing.assert_allclose(s, 2 * np.mean(grad * grad, axis=1),
                               rtol=1e-6)
    assert (got[:300] != _init()[ids]).any()
    assert not table._kept.took.ids.is_deleted()


GROUP = [3, 10, 300, 2000]


def _group():
    init = [_init(n) + 10000 * t for t, n in enumerate(GROUP)]
    return mv.create_table("matrix_group", GROUP, COLS, np.float32,
                           init_values=init), init


def _parts(rng, most=40):
    return [_ids(rng, min(n, most), n) for n in GROUP]


def _group_rows(init, parts):
    return np.concatenate([init[t][p] for t, p in enumerate(parts)])


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_a_group_op_of_the_same_segments_skips_its_checks(kernel,
                                                          monkeypatch):
    """A group Get then a group Add of the same ids under the same lengths:
    the Add sends nothing up and runs no `WORKER_GROUP_IDS` (the segments
    were checked and given their bases once), and adds to the right rows of
    every member."""
    _kernel(monkeypatch, kernel)
    mv.init(mesh_shape="1")
    group, init = _group()
    rng = np.random.default_rng(5)
    parts = _parts(rng)
    n = sum(len(p) for p in parts)
    delta = _delta(rng, n)
    before = _kept()
    with _Window(monkeypatch) as window:
        import jax

        rows, offsets = group.wait_device(group.get_device_async(parts))
        group.wait(group.add_device_async(jax.device_put(delta), parts))
    assert _kept() == before + 1
    assert [r.n for r in window.of("WORKER_GROUP_IDS")] == [n]
    assert [r.bytes > 0 for r in window.of("WORKER_ROW_IDS")] == [True,
                                                                  False]
    assert len(window.of("TABLE_ROW_LAUNCH")) == 2
    np.testing.assert_array_equal(np.asarray(rows)[:n],
                                  _group_rows(init, parts))
    for t, member in enumerate(group.tables):
        want = init[t].copy()
        want[parts[t]] += delta[offsets[t]:offsets[t + 1]]
        np.testing.assert_array_equal(member.get(), want)


@pytest.mark.parametrize("case", ["other lengths", "lengths written again",
                                  "past a member's end", "a member's own"])
def test_a_group_compares_what_its_caller_named(case):
    """The same flat ids under other lengths are other rows of the slab: a
    miss, read right, also where the caller's one `lengths` array was
    written in place between the ops; under lengths that put an id past
    its member's end the op is still refused; a member's proxy keeps its
    own ids, which a group op between two of its ops does not touch."""
    mv.init(mesh_shape="1")
    group, init = _group()
    # [0] | [1, 2, 5] | [1, 250] | [9]: distinct within a segment
    flat = np.array([0, 1, 2, 5, 1, 250, 9], np.int32)
    lengths = np.array([1, 3, 2, 1], np.int64)

    def want(lens):
        at = np.concatenate([[0], np.cumsum(lens)])
        return _group_rows(init, [flat[at[t]:at[t + 1]] for t in range(4)])

    rows, _ = group.wait_device(group.get_device_async(flat, lengths))
    np.testing.assert_array_equal(np.asarray(rows)[:7], want(lengths))
    before = _kept()
    if case == "other lengths":
        other = np.array([1, 2, 3, 1], np.int64)
        rows, _ = group.wait_device(group.get_device_async(flat, other))
        np.testing.assert_array_equal(np.asarray(rows)[:7], want(other))
        assert _kept() == before
        rows, _ = group.wait_device(group.get_device_async(flat, other))
        np.testing.assert_array_equal(np.asarray(rows)[:7], want(other))
        assert _kept() == before + 1
    elif case == "lengths written again":
        lengths[:] = [1, 2, 3, 1]
        rows, _ = group.wait_device(group.get_device_async(flat, lengths))
        np.testing.assert_array_equal(np.asarray(rows)[:7], want(lengths))
        assert _kept() == before
    elif case == "past a member's end":
        # 250 in member 1 (10 rows): the flat ids are the kept op's own
        with pytest.raises(FatalError, match="member 1 row id out of range"):
            group.get_device_async(flat, np.array([1, 5, 0, 1], np.int64))
        assert _kept() == before
    else:
        member = group.tables[2]
        mine = np.array([7, 250, 3], np.int32)
        np.testing.assert_array_equal(_get(member, mine)[:3], init[2][mine])
        group.wait_device(group.get_device_async(flat, lengths))
        assert _kept() == before + 1        # the group's own, kept before
        _add(member, np.ones((3, COLS), np.float32), mine)
        assert _kept() == before + 2        # the member's, untouched
        np.testing.assert_array_equal(member.get(mine), init[2][mine] + 1)
        # member 3 naming member 2's ids keeps its own
        np.testing.assert_array_equal(_get(group.tables[3], mine)[:3],
                                      init[3][mine])
        assert _kept() == before + 2


def test_host_ops_keep_nothing(monkeypatch):
    """Numpy Gets and Adds never enter `_ids_at_submit`: the same rows
    twice leave `ROW_IDS_KEPT` where it was and no `WORKER_ROW_IDS`."""
    mv.init(mesh_shape="1")
    table = _table()
    rng = np.random.default_rng(6)
    ids, delta = _ids(rng, 200), _delta(rng, 200)
    want = _init()
    before = _kept()
    with _Window(monkeypatch) as window:
        for _ in range(2):
            table.add(delta, ids)
            want[ids] += delta
            np.testing.assert_array_equal(table.get(ids), want[ids])
    assert _kept() == before
    assert table._kept is None
    assert not window.of("WORKER_ROW_IDS")
    assert len(window.of("TABLE_ROW_LAUNCH")) == 4


# -- a table sharded over the chips of one process: the row plan keeps ---------

def _mesh(monkeypatch, **kw):
    """A table row-sharded over four devices whose ops are routed to the
    interpreted row kernel on every shard's block (row groups of 8), and
    its row plan."""
    _kernel(monkeypatch, "routed")
    mv.init(mesh_shape="4")
    table = _table(**kw)
    return table, table._server_table.plan


def _preps(window):
    """`(rows named, bytes of ids sent up)` of the window's TABLE_ROW_PREP
    records."""
    return [(r.n, r.bytes) for r in window.of("TABLE_ROW_PREP")]


@pytest.mark.parametrize("order", ["add-get", "get-add"])
def test_on_a_mesh_the_same_rows_launch_on_the_routed_ids_kept(order,
                                                               monkeypatch):
    """On a mesh the dispatcher routes a device op's ids and sends them to
    the first chip, an Add's in the form of a Get's, and the row plan keeps
    them: the second op of a pair that names the same rows counts
    `ROW_IDS_KEPT`, its `TABLE_ROW_PREP` says `bytes` 0 and holds no
    `TABLE_ROW_ROUTE`, the proxy keeps nothing (no `WORKER_ROW_IDS`), and
    the rows come back and land right."""
    from multiverso_tpu.tables.matrix_table import _live_slots

    table, plan = _mesh(monkeypatch)
    assert plan.routed == ("get", "add")
    rng = np.random.default_rng(53)
    ids, delta = _ids(rng, 700), _delta(rng, 700)
    want = _init()
    before = _kept()
    with _Window(monkeypatch) as window:
        if order == "add-get":
            _add(table, delta, ids)
            want[ids] += delta
            got = _get(table, ids)
        else:
            got = _get(table, ids)
            np.testing.assert_array_equal(got[:700], want[ids])
            _add(table, delta, ids)
            want[ids] += delta
    assert _kept() == before + 1
    assert got.shape[0] == 1024
    live = _live_slots(700, 1024)
    assert _preps(window) == [(700, 4 * live), (700, 0)]
    routes = window.of("TABLE_ROW_ROUTE")
    assert [r.n for r in routes] == [700]
    assert routes[0].parent == window.of("TABLE_ROW_PREP")[0].id
    assert not window.of("WORKER_ROW_IDS") and table._kept is None
    launches = window.of("TABLE_ROW_LAUNCH")
    assert [r.ids_from for r in launches] == ["dispatcher"] * 2
    assert [r.shards for r in launches] == [4] * 2
    if order == "add-get":
        np.testing.assert_array_equal(got[:700], want[ids])
        assert not got[700:].any()
    np.testing.assert_array_equal(table.get(), want)
    # one form for both ops: the ids named, ids past the table, which no
    # shard owns, and the sentinel last; no program has deleted it
    up = plan._kept.took.ids
    assert not up.is_deleted()
    first = np.asarray(up.addressable_shards[0].data)
    assert first.shape == (live,)
    np.testing.assert_array_equal(first[:700], ids)
    assert (first[700:-1] == table._server_table.padded_rows).all()
    assert first[-1] == table.sentinel_row


def test_on_a_mesh_ids_overwritten_in_place_miss_and_read_the_new_rows(
        monkeypatch):
    """The plan compares with its own copy of what went up, never with the
    caller's array: the SAME array object holding other ids is another op."""
    table, plan = _mesh(monkeypatch)
    rng = np.random.default_rng(1)
    ids, delta = _ids(rng, 500), _delta(rng, 500)
    first = ids.copy()
    _add(table, delta, ids)
    assert not np.shares_memory(plan._kept.named, ids)
    before = _kept()
    ids[:] = _ids(rng, 500)
    assert (ids != first).any()
    got = _get(table, ids)
    assert _kept() == before
    want = _init()
    want[first] += delta
    np.testing.assert_array_equal(got[:500], want[ids])
    ids[:] = first
    np.testing.assert_array_equal(_get(table, ids)[:500], want[first])
    assert _kept() == before


@pytest.mark.parametrize("other", ["shorter", "longer", "first id",
                                   "last id", "an id in the middle",
                                   "another table", "another bucket"])
def test_on_a_mesh_other_rows_miss(other, monkeypatch):
    """What differs from the routed op kept in its count, its first, its
    last or any one id, a Get whose bucket is not the Add's (512 ids: the
    Add's is 512 and the Get's, which keeps a sentinel slot, 1,024), or the
    same ids named to another table (a plan a table): a miss, and right."""
    table, _ = _mesh(monkeypatch)
    second = _table()
    rng = np.random.default_rng(2)
    n = 512 if other == "another bucket" else 400
    ids = np.sort(_ids(rng, n + 1))[:n]
    delta = _delta(rng, n)
    _add(table, delta, ids)
    want = _init()
    want[ids] += delta
    asked, reader = ids.copy(), table
    spare = int(np.setdiff1d(np.arange(ROWS), ids)[7])
    if other == "shorter":
        asked = ids[:-1]
    elif other == "longer":
        asked = np.append(ids, np.int32(spare))
    elif other == "first id":
        asked[0] = spare
    elif other == "last id":
        asked[-1] = spare
    elif other == "an id in the middle":
        asked[n // 2] = spare
    elif other == "another table":
        reader, want = second, _init()
    before = _kept()
    got = _get(reader, asked)
    assert _kept() == before
    assert got.shape[0] == (1024 if other == "another bucket" else 512)
    np.testing.assert_array_equal(got[:len(asked)], want[asked])
    # what is kept now is the Get's: the same Get again hits
    np.testing.assert_array_equal(_get(reader, asked)[:len(asked)],
                                  want[asked])
    assert _kept() == before + 1


def test_on_a_mesh_depth_one_a_third_id_set_replaces_what_is_kept(
        monkeypatch):
    """A, B, A: the second A finds B kept and misses; A, A hits."""
    table, _ = _mesh(monkeypatch)
    rng = np.random.default_rng(3)
    a, b = _ids(rng, 300), _ids(rng, 300)
    before = _kept()
    for ids in (a, b, a):
        np.testing.assert_array_equal(_get(table, ids)[:300], _init()[ids])
    assert _kept() == before
    np.testing.assert_array_equal(_get(table, a)[:300], _init()[a])
    assert _kept() == before + 1


LAUNCH_FIELDS = ("n", "path", "shards", "max_shard_n", "exchange_bytes",
                 "descriptors", "waits", "bytes", "ids_from")


@pytest.mark.parametrize("op", ["add", "get"])
def test_on_a_mesh_a_hit_launches_what_a_miss_would(op, monkeypatch):
    """A hit's `TABLE_ROW_LAUNCH` record equals, field for field, the
    record of the same op on a table that kept nothing: the counts by shard
    and a segment's capacity are worked out from the kept counts of the ids
    named (a Get's with the sentinel at its owner) as a miss counts them, so
    the program launched is the one a miss compiles."""
    table, plan = _mesh(monkeypatch)
    fresh = _table()
    rng = np.random.default_rng(8)
    ids, delta = _ids(rng, 900), _delta(rng, 900)
    before = _kept()
    with _Window(monkeypatch) as window:
        if op == "add":
            _get(table, ids)
            _add(table, delta, ids)
            _add(fresh, delta, ids)
        else:
            _add(table, delta, ids)
            _get(table, ids)
            _get(fresh, ids)
    assert _kept() == before + 1
    assert [nbytes > 0 for _, nbytes in _preps(window)] == [True, False,
                                                            True]
    _, hit, miss = window.of("TABLE_ROW_LAUNCH")
    assert hit.shards == 4 and hit.n > 900
    for field in LAUNCH_FIELDS:
        assert getattr(hit, field) == getattr(miss, field), field
    took, other = plan._kept.took, fresh._server_table.plan._kept.took
    assert took.ids.shape == other.ids.shape
    np.testing.assert_array_equal(took.counts, other.counts)


def test_on_a_mesh_ids_that_all_fall_in_one_shard_hit(monkeypatch):
    """Ids of the third shard alone (a segment as long as the op, another
    program): Add then Get hits and reads the new rows; the Get's counts
    put the sentinel at its owner, the last shard."""
    table, plan = _mesh(monkeypatch)
    block = table._server_table._block_rows
    rng = np.random.default_rng(9)
    ids = (2 * block + rng.choice(block, 300, replace=False)).astype(np.int32)
    delta = _delta(rng, 300)
    before = _kept()
    with _Window(monkeypatch) as window:
        _add(table, delta, ids)
        got = _get(table, ids)
    assert _kept() == before + 1
    want = _init()
    want[ids] += delta
    np.testing.assert_array_equal(got[:300], want[ids])
    np.testing.assert_array_equal(table.get(), want)
    np.testing.assert_array_equal(plan._kept.took.counts, [0, 0, 300, 0])
    add, get = window.of("TABLE_ROW_LAUNCH")
    assert add.max_shard_n == 304 and add.n == 304
    assert get.max_shard_n >= 300 and get.n == 4 * get.max_shard_n


def test_on_a_mesh_a_get_only_routed_table_hits_on_the_same_pull_again(
        monkeypatch):
    """Under an updater with state only the Get is routed (the Add takes
    XLA's partitioned programs and the table's own id form): the same pull
    again hits, the Add between two pulls neither hits nor replaces what
    is kept, and the pull after it reads the stepped rows."""
    table, plan = _mesh(monkeypatch, updater_type="rowwise_adagrad")
    assert plan.routed == ("get",)
    rng = np.random.default_rng(10)
    ids, grad = _ids(rng, 300), _delta(rng, 300)
    option = mv.AddOption(learning_rate=0.01, rho=1e-10)
    before = _kept()
    np.testing.assert_array_equal(_get(table, ids)[:300], _init()[ids])
    np.testing.assert_array_equal(_get(table, ids)[:300], _init()[ids])
    assert _kept() == before + 1
    kept = plan._kept
    _add(table, grad, ids, option)
    assert _kept() == before + 1 and plan._kept is kept
    got = _get(table, ids)
    assert _kept() == before + 2
    np.testing.assert_array_equal(got[:300], table.get(ids))
    assert (got[:300] != _init()[ids]).any()


def test_on_a_mesh_a_routed_host_add_keeps_its_distinct_ids(monkeypatch):
    """A numpy Add is routed like a device Add once its repeated ids are
    summed: the ids that go up are the distinct ones, in the routed ops'
    one form, and a Get of them, numpy or device, launches on that array."""
    table, plan = _mesh(monkeypatch)
    rng = np.random.default_rng(11)
    ids = _ids(rng, 250)
    ids[100:150] = ids[:50]
    delta = _delta(rng, 250)
    want = _init()
    np.add.at(want, ids, delta)
    before = _kept()
    with _Window(monkeypatch) as window:
        table.add(delta, ids)
        named = plan._kept.named.copy()
        assert sorted(named) == sorted(set(ids.tolist()))
        np.testing.assert_array_equal(table.get(named), want[named])
        np.testing.assert_array_equal(_get(table, named)[:200], want[named])
    assert _kept() == before + 2
    assert [nbytes > 0 for _, nbytes in _preps(window)] == [True, False,
                                                            False]
    assert len(window.of("TABLE_ROW_ROUTE")) == 1
    assert [r.dups for r in window.of("TABLE_ROW_PREP")] == [50, 0, 0]
    np.testing.assert_array_equal(table.get(), want)


def test_four_threads_on_one_proxy_take_the_ids_they_compared():
    """Four workers read the same proxy with their own ids, each twice in
    a row, the interpreter switching as often as it can: whichever array a
    Get launches on holds the ids that Get named (a row's value is its
    id), hit or miss."""
    import sys

    workers = 4
    mv.init(mesh_shape="1", local_workers=workers)
    table = _table()
    failures, rounds = [], 40
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def work(slot):
        rng = np.random.default_rng(slot)
        try:
            with mv.worker(slot):
                for _ in range(rounds):
                    ids = _ids(rng, 100)
                    for _ in range(2):
                        got = _get(table, ids)
                        assert (got[:100, 0] == ids).all()
        except BaseException as exc:  # reported by the test's thread
            failures.append(exc)

    before = _kept()
    try:
        threads = [threading.Thread(target=work, args=(slot,))
                   for slot in range(workers)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in threads)
    assert not failures, failures
    assert 0 < _kept() - before <= workers * rounds


def test_the_kept_share_reader(monkeypatch):
    """`row_ids_kept_share` over a window of a trainer's pairs, each of
    other rows: every second op sent nothing up, 50; None over a window
    that holds no `WORKER_ROW_IDS`."""
    from types import SimpleNamespace

    mv.init(mesh_shape="1")
    table = _table()
    rng = np.random.default_rng(7)
    read = common.load_module("layers", "row_ids_kept_share").read
    with _Window(monkeypatch) as window:
        for _ in range(4):
            ids = _ids(rng, 100)
            _add(table, _delta(rng, 100), ids)
            _get(table, ids)
    assert read(SimpleNamespace(window=(window.t0, window.t1))) == 50.0
    with _Window(monkeypatch) as window:
        table.get(np.arange(5, dtype=np.int32))
    assert read(SimpleNamespace(window=(window.t0, window.t1))) is None


def test_the_routed_kept_share_reader(monkeypatch):
    """`shard_ids_kept_share` over a window of a trainer's pairs on a mesh,
    each of other rows: every second routed op sent nothing up, 50; None
    over a window whose routed ops' TABLE_ROW_PREP records say no `bytes`
    (a program from before the field) and over one without a routed op."""
    from types import SimpleNamespace

    table, _ = _mesh(monkeypatch)
    rng = np.random.default_rng(12)
    read = common.load_module("layers", "shard_ids_kept_share").read
    with _Window(monkeypatch) as window:
        for _ in range(4):
            ids = _ids(rng, 100)
            _add(table, _delta(rng, 100), ids)
            _get(table, ids)
    run = SimpleNamespace(window=(window.t0, window.t1))
    assert read(run) == 50.0
    trace = run._op_trace
    run._op_trace = op_trace.Trace(
        [r._replace(bytes=0) if r.stage == "TABLE_ROW_PREP" else r
         for r in trace.records], trace.t0_ns, trace.t1_ns)
    assert read(run) is None
    mv.shutdown()
    mv.init(mesh_shape="1")
    one = _table()
    with _Window(monkeypatch) as window:
        one.add(_delta(rng, 5), np.arange(5, dtype=np.int32))
    assert read(SimpleNamespace(window=(window.t0, window.t1))) is None

