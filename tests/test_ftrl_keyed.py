"""The keyed FTRL table (`mv.create_table("ftrl", key_space, ...)`: `(z, n)`
a key on the device, Get and Add by key) against the benchmark's plain
reference (`benchmark/reference/logreg-ftrl-criteo-tb.py`, which imports
nothing of the program), on seeded state at small key spaces; the ids' way
up that it shares with the matrix table (`tables/device_ids.py`); what it
refuses. Results and counts from a CPU run, never a speed."""

import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark import common
from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.io import MemoryStream
from multiverso_tpu.log import FatalError

OPT = dict(alpha=0.1, beta=1.0, lambda1=1.0, lambda2=1.0)
SIZE, SEED = 5000, 40


@pytest.fixture(scope="module")
def ref():
    return common.load_module("reference", "logreg-ftrl-criteo-tb")


def _table(ref, size=SIZE, seed=SEED, **kw):
    """A table on seeded state, from a block source."""
    return mv.create_table(
        "ftrl", size, init=lambda lo, count: ref.init_zn(
            np.arange(lo, lo + count), seed), **dict(OPT, **kw))


def _state(table, size=SIZE):
    return (np.asarray(table.get_state_device("z")),
            np.asarray(table.get_state_device("n")))


def _keys(rng, n, size=SIZE, hot=()):
    keys = np.unique(np.concatenate(
        [np.asarray(hot, np.int64), rng.choice(size, n, replace=False)]))
    rng.shuffle(keys)
    return keys.astype(np.int32)


def _get_device(table, keys):
    return np.asarray(table.wait_device(table.get_device_async(keys)))


def _check(ref, table, replay, size=SIZE, weights=True):
    """The whole state and (``weights``) every weight against the replay:
    `n` to the bit, `z` and `w` inside the reference's tolerance, keys that
    took no step (and the scratch entries) to the bit."""
    z, n = _state(table)
    assert ref.n_mismatch(n[:size], replay.n) == 0
    assert ref.z_error(z[:size], replay.z, replay.steps) <= 1
    quiet = replay.steps == 0
    assert ref.n_mismatch(z[:size][quiet], replay.z[quiet]) == 0
    assert not z[size:].any() and not n[size:].any()
    if weights:
        want = ref.weights(replay.z, replay.n, **replay.opt)
        assert ref.w_error(table.get(), want, replay.z, replay.steps,
                           OPT) <= 1


@pytest.mark.parametrize("mesh", ["1", "4"])
def test_keyed_ops_against_the_reference(ref, mesh):
    """Sequences of keyed Gets and Adds, device and host forms mixed, on one
    device and on a mesh of four (XLA's partitioned programs): three hot
    keys stepped at every op (300 times), a gradient longer than its keys
    whose tail is not zero, keys never named left to the bit, the scratch
    entries left at zero."""
    import jax
    import jax.numpy as jnp

    mv.init(mesh_shape=mesh)
    table = _table(ref)
    replay = ref.Replay(np.arange(SIZE), SEED, OPT)
    rng = np.random.default_rng(1)
    hot = [0, 1, SIZE - 1]
    never = np.setdiff1d(np.arange(SIZE), hot)[::7]   # never named
    quiet_free = np.setdiff1d(np.arange(SIZE), np.concatenate([hot, never]))
    for step in range(300):
        # three counts of keys (a count is a program), two buckets
        keys = np.concatenate([hot, rng.choice(
            quiet_free, (47, 117, 257)[step % 7 % 3], replace=False)])
        rng.shuffle(keys)
        keys = keys.astype(np.int32)
        grad = ref.to_float(ref.grad_k(rng, len(keys)))
        form = step % 3
        if form == 0:
            got = _get_device(table, keys)
            z, _, want, steps = replay.state(keys)
            assert ref.w_error(got[:len(keys)], want, z, steps, OPT) <= 1
            # a trainer's buffer at the Get's bucket: sevens past the keys
            held = jnp.full(got.shape[0], 7.0, jnp.float32).at[
                :len(keys)].set(grad)
            table.wait(table.add_device_async(held, keys))
        elif form == 1:
            table.wait(table.add_device_async(jax.device_put(grad), keys))
        else:
            table.add(keys, grad)
        replay.add(replay.plan(keys), grad)
    assert replay.steps[hot].tolist() == [300] * 3
    assert not replay.steps[never].any()
    _check(ref, table, replay)
    got = table.get(np.array(hot, np.int32))
    z, _, want, steps = replay.state(np.array(hot))
    assert ref.w_error(got, want, z, steps, OPT) <= 1


def test_a_weight_under_lambda1_is_exactly_zero(ref):
    """Keys with `|z| <= lambda1` have a weight of exactly 0, from the
    device Get and the host Get alike, and their first step is `z + g` to
    the bit (`sigma * 0`)."""
    mv.init(mesh_shape="1")
    table = _table(ref)
    z0, n0 = ref.init_zn(np.arange(SIZE), SEED)
    under = np.flatnonzero(np.abs(z0) <= OPT["lambda1"]).astype(np.int32)
    assert 0.4 < len(under) / SIZE < 0.6
    keys = under[:500]
    assert not _get_device(table, keys)[:len(keys)].any()
    assert not table.get(keys).any()
    assert table.get(np.setdiff1d(np.arange(SIZE), under)[:100]
                     .astype(np.int32)).all()
    grad = ref.to_float(ref.grad_k(np.random.default_rng(2), len(keys)))
    table.add(keys, grad)
    z, n = _state(table)
    np.testing.assert_array_equal(z[keys], z0[keys] + grad)
    np.testing.assert_array_equal(n[keys], n0[keys] + grad * grad)


def test_a_repeated_key_takes_one_step_from_the_sum(ref):
    """A key named twice or more in one Add takes ONE step from the sum of
    its gradients (never the last writer's), through the device form and
    the host form; the other keys of the Add step as ever."""
    import jax

    mv.init(mesh_shape="1")
    table = _table(ref)
    replay = ref.Replay(np.arange(SIZE), SEED, OPT)
    keys = np.array([5, 900, 5, 9, 5, 900], np.int32)
    grad = np.array([0.25, -0.5, 0.5, -0.125, 0.125, 0.25], np.float32)
    distinct = np.array([5, 9, 900])
    summed = np.array([0.875, -0.125, -0.25], np.float32)
    for form in ("device", "host"):
        if form == "device":
            table.wait(table.add_device_async(jax.device_put(grad), keys))
        else:
            table.add(keys, grad)
        replay.add(replay.plan(distinct), summed)
        _check(ref, table, replay)
    assert replay.steps[distinct].tolist() == [2, 2, 2]


@pytest.mark.parametrize("mesh", ["1", "4"])
def test_a_repeated_key_of_a_long_add_takes_one_step_from_the_sum(ref, mesh):
    """The same through an Add of more slots than a grid step of the row
    kernel holds (`pallas_rows.LANE_GROUP`), so that the slots of some keys
    lie either side of a step's boundary once sorted: on one device (the
    kernel, interpreted) and on a mesh (XLA's scatters), against the
    replay."""
    from multiverso_tpu.ops.pallas_rows import LANE_GROUP

    mv.init(mesh_shape=mesh)
    table = _table(ref)
    replay = ref.Replay(np.arange(SIZE), SEED, OPT)
    rng = np.random.default_rng(41)
    keys = (2000 + rng.integers(0, 150, 600)).astype(np.int32)
    # gradients on a binary grid: their sums are exact in any order
    grad = (rng.integers(-8, 9, 600) / 16).astype(np.float32)
    slots = np.sort(keys)
    assert sum(slots[edge - 1] == slots[edge]
               for edge in range(LANE_GROUP, 600, LANE_GROUP)) >= 2
    distinct, back = np.unique(keys, return_inverse=True)
    summed = np.bincount(back, grad.astype(np.float64)).astype(np.float32)
    for _ in range(2):
        table.add(keys, grad)
        replay.add(replay.plan(distinct), summed)
        _check(ref, table, replay)
    assert set(replay.steps[distinct].tolist()) == {2}


def test_keys_outside_the_table_and_short_gradients_are_refused(ref):
    import jax

    mv.init(mesh_shape="1")
    table = _table(ref)
    before = _state(table)
    good = np.array([1, 2, 3], np.int32)
    grad = np.ones(3, np.float32)
    for bad in ([1, SIZE, 3], [-1, 2, 3]):
        bad = np.array(bad, np.int32)
        for call in (lambda: table.get(bad),
                     lambda: table.add(bad, grad),
                     lambda: table.get_device_async(bad),
                     lambda: table.add_device_async(jax.device_put(grad),
                                                    bad)):
            with pytest.raises(FatalError, match="out of range"):
                call()
    with pytest.raises(FatalError, match="3 keys but 2 gradient values"):
        table.add(good, grad[:2])
    with pytest.raises(FatalError, match="3 keys but 2 gradient values"):
        table.wait(table.add_device_async(jax.device_put(grad[:2]), good))
    for was, now in zip(before, _state(table)):
        np.testing.assert_array_equal(was, now)


def test_three_ftrl_tables_agree(ref):
    """The keyed table, its whole-array ops (the keyed op over every key)
    and the host dictionaries of `SparseFTRLServer` take the same ops from
    a state of zeros and hold the same weights."""
    from multiverso_tpu.tables.sparse_table import make_sparse_ftrl

    mv.init(mesh_shape="1")
    mv.register_table_type("sparse_ftrl", make_sparse_ftrl)
    size = 300
    opt = dict(alpha=0.3, beta=1.0, lambda1=0.1, lambda2=0.5)
    keyed = mv.create_table("ftrl", size, **opt)
    whole = mv.create_table("ftrl", size, **opt)
    sparse = mv.create_table("sparse_ftrl", size, width=1, **opt)
    rng = np.random.default_rng(3)
    for _ in range(40):
        keys = _keys(rng, 60, size)
        grad = ref.to_float(ref.grad_k(rng, len(keys)))
        keyed.add(keys, grad)
        dense = np.zeros(size, np.float32)
        dense[keys] = grad
        whole.add(dense)       # a zero gradient leaves a key's (z, n) alone
        sparse.add(keys, grad[:, None])
    every = np.arange(size, dtype=np.int32)
    want = sparse.get(every)[:, 0]
    assert np.abs(want).max() > 0.1
    for got in (keyed.get(every), keyed.get(), whole.get()):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)
    for a, b in zip(_state(keyed), _state(whole)):
        np.testing.assert_array_equal(a, b)


def test_store_and_load_round_trip(ref):
    mv.init(mesh_shape="1")
    table = _table(ref)
    rng = np.random.default_rng(4)
    for _ in range(5):
        keys = _keys(rng, 200)
        table.add(keys, ref.to_float(ref.grad_k(rng, len(keys))))
    buf = MemoryStream()
    table._server_table.store(buf)
    buf.seek(0)
    other = mv.create_table("ftrl", SIZE, **OPT)
    other._server_table.load(buf)
    for a, b in zip(_state(table), _state(other)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(table.get(), other.get())
    small = mv.create_table("ftrl", SIZE - 1, **OPT)
    buf.seek(0)
    with pytest.raises(FatalError, match="a state of 5000 keys"):
        small._server_table.load(buf)


def test_a_state_of_zeros_and_a_block_source_in_pieces(ref, monkeypatch):
    """No source: zeros made on the device. A source is asked piece by
    piece, in key order, for no more keys than a piece holds; one that
    gives the wrong count is refused."""
    from multiverso_tpu.tables import ftrl_table

    mv.init(mesh_shape="1")
    z, n = _state(mv.create_table("ftrl", 100, **OPT))
    assert len(z) == 1024 and not z.any() and not n.any()
    monkeypatch.setattr(ftrl_table, "_PIECE_KEYS", 1200)
    asked = []

    def source(lo, count):
        asked.append((lo, count))
        return ref.init_zn(np.arange(lo, lo + count), SEED)

    table = mv.create_table("ftrl", SIZE, init=source, **OPT)
    assert asked == [(0, 1200), (1200, 1200), (2400, 1200), (3600, 1200),
                     (4800, 200)]
    z, n = _state(table)
    z0, n0 = ref.init_zn(np.arange(SIZE), SEED)
    np.testing.assert_array_equal(z[:SIZE], z0)
    np.testing.assert_array_equal(n[:SIZE], n0)
    with pytest.raises(FatalError, match="block source gave"):
        mv.create_table("ftrl", SIZE, init=lambda lo, count: (
            np.zeros(count - 1, np.float32), np.zeros(count, np.float32)))


def _count(name):
    return Dashboard.counter_value(name)


def test_the_kept_ids_hit_and_miss_as_on_a_matrix_table(ref):
    """A trainer's push names the keys of its pull: the Add launches on the
    ids the Get left on the device (`ROW_IDS_KEPT`), whatever its
    gradient's length; another set of keys, or the same count of other
    keys, is a miss; the host forms and a mesh keep nothing."""
    import jax
    import jax.numpy as jnp

    mv.init(mesh_shape="1")
    table = _table(ref)
    rng = np.random.default_rng(5)
    keys, other = _keys(rng, 700), _keys(rng, 700)
    counts = {name: _count(name) for name in (
        "ROW_IDS_KEPT", "ROW_IDS_FROM_CALLER", "ROW_IDS_FROM_DISPATCHER",
        "FTRL_KEYS_GET", "FTRL_KEYS_ADD", "ROW_LAUNCH_XLA_GET",
        "ROW_LAUNCH_XLA_ADD", "ROW_LAUNCH_PALLAS_ADD")}

    def grew(name):
        return _count(name) - counts[name]

    got = _get_device(table, keys)                      # miss: first op
    assert got.shape == (1024,) and grew("ROW_IDS_KEPT") == 0
    table.wait(table.add_device_async(                  # hit, longer grad
        jnp.ones(1024, jnp.float32), keys))
    assert grew("ROW_IDS_KEPT") == 1
    table.wait(table.add_device_async(                  # hit, exact grad
        jnp.ones(700, jnp.float32), keys))
    assert grew("ROW_IDS_KEPT") == 2
    _get_device(table, keys)                            # hit: a Get again
    assert grew("ROW_IDS_KEPT") == 3
    _get_device(table, other)                           # miss: other keys
    assert grew("ROW_IDS_KEPT") == 3
    took = table._kept.took
    np.testing.assert_array_equal(np.asarray(took.ids)[:700], other)
    np.testing.assert_array_equal(np.asarray(took.ids)[700:],
                                  table.scratch_key)
    table.add(other, np.ones(700, np.float32))          # host form: no part
    table.get(other)
    assert grew("ROW_IDS_KEPT") == 3
    assert grew("ROW_IDS_FROM_CALLER") == 5
    assert grew("ROW_IDS_FROM_DISPATCHER") == 2
    # on one device the row kernel writes an Add back (here interpreted)
    assert grew("ROW_LAUNCH_XLA_GET") == 4 and grew("ROW_LAUNCH_XLA_ADD") == 0
    assert grew("ROW_LAUNCH_PALLAS_ADD") == 3
    assert grew("FTRL_KEYS_GET") == 700 * 4 and grew("FTRL_KEYS_ADD") == 2100
    # the caller's array may change as soon as the call returns
    mine = keys.copy()
    msg = table.get_device_async(mine)
    mine[:] = 0
    np.testing.assert_array_equal(
        np.asarray(table.wait_device(msg))[:700], table.get(keys))
    del jax


def test_the_records_of_a_keyed_op_carry_the_matrix_ops_fields(
        ref, monkeypatch):
    """The spans of a keyed Get and Add, under the names and fields the
    matrix ops give theirs, and the benchmark's `ftrl_slots_share` over
    them."""
    import time

    import jax.numpy as jnp

    from multiverso_tpu import dashboard
    from multiverso_tpu.ops.pallas_rows import LANE_GROUP
    from multiverso_tpu.runtime.zoo import Zoo
    from multiverso_tpu.tables.device_ids import live_slots

    mv.init(mesh_shape="1")
    table = _table(ref)
    keys = _keys(np.random.default_rng(6), 700)
    monkeypatch.setattr(Dashboard, "profile_annotations", True)
    t0 = time.perf_counter()
    _get_device(table, keys)
    table.wait(table.add_device_async(jnp.ones(1024, jnp.float32), keys))
    Zoo.instance().server.run_serialized(lambda: None)
    t1 = time.perf_counter()
    monkeypatch.setattr(Dashboard, "profile_annotations", False)
    records, _ = dashboard.RING.window(t0, t1)
    by = {}
    for r in records:
        by.setdefault(r.stage, []).append(r)
    for stage in ("WORKER_SUBMIT", "WORKER_ROW_IDS", "SERVER_QUEUE_WAIT",
                  "TABLE_ROW_PREP", "TABLE_ROW_LAUNCH", "WORKER_WAIT"):
        assert len(by[stage]) == 2, stage
    assert len(by["TABLE_PROCESS_GET"]) == len(by["TABLE_PROCESS_ADD"]) == 1
    assert [(r.n, r.bytes) for r in by["WORKER_ROW_IDS"]] == [
        (700, 4096), (700, 0)]
    assert [r.n for r in by["TABLE_ROW_PREP"]] == [700, 700]
    live = live_slots(700, 1024)
    get, add = by["TABLE_ROW_LAUNCH"]
    for launch, path, nbytes in ((get, "xla", 8 * live),
                                 (add, "pallas", 16 * live)):
        assert (launch.n, launch.path, launch.updater, launch.ids_from,
                launch.bytes, launch.state_bytes) == (
            live, path, "ftrl", "caller", nbytes, nbytes)
    assert add.ids_ready == 1
    # four descriptors a slot of the kernel's whole groups (a row of `z`
    # and of `n`, read and written back), four waits a group
    groups = -(-live // LANE_GROUP)
    assert (add.descriptors, add.waits) == (4 * LANE_GROUP * groups,
                                            4 * groups)
    assert (get.descriptors, get.waits) == (0, 0)

    class Run:
        window = (t0, t1)

    read = common.load_module("layers", "ftrl_slots_share").read
    assert read(Run()) == pytest.approx(100.0 * live / 700)
    # the Add's launch is the kernel's: the cell's counter of the mechanism
    assert common.load_module(
        "layers", "pallas_row_share.ftrlctr").read(Run()) == 100.0
    # the kernel walks the keys' distinct rows (PR 51), fewer than the slots
    # its record's descriptors bound. Only the device knows how many: a
    # traced launch leaves the count there beside its record's id
    # (`row_plan.ROWS_WALKED`) and fetches nothing; an untraced one keeps
    # nothing; the reader joins them to the window's records
    from multiverso_tpu.tables.row_plan import ROWS_WALKED
    rows = len(np.unique(np.append(keys, table.scratch_key) >> 7))
    assert [(launch, int(count)) for launch, count in ROWS_WALKED
            if launch in (get.id, add.id)] == [(add.id, rows)]
    rows_share = common.load_module("layers", "ftrl_rows_share").read
    assert rows_share(Run()) == pytest.approx(100.0 * rows / live)
    kept = len(ROWS_WALKED)
    t2 = time.perf_counter()
    table.wait(table.add_device_async(jnp.ones(1024, jnp.float32), keys))
    Zoo.instance().server.run_serialized(lambda: None)
    Run.window = (t2, time.perf_counter())
    assert len(ROWS_WALKED) == kept
    assert rows_share(Run()) is None


def test_a_remote_client_is_served_the_host_forms(ref):
    """`RemoteClient.table` gives the kind's proxy (until PR 50 it refused
    the kind by name); `tests/test_ftrl_served.py` has the served path."""
    mv.init(mesh_shape="1", remote_workers=1)
    table = _table(ref)
    endpoint = mv.serve("127.0.0.1:0")
    client = mv.remote_connect(endpoint)
    try:
        remote = client.table(table.table_id)
        replay = ref.Replay(np.arange(SIZE), SEED, OPT)
        rng = np.random.default_rng(11)
        keys = _keys(rng, 200)
        grad = ref.to_float(ref.grad_k(rng, len(keys)))
        remote.add(keys, grad)
        replay.add(replay.plan(keys), grad)
        z, _, want, steps = replay.state(keys)
        assert ref.w_error(remote.get(keys), want, z, steps, OPT) <= 1
        _check(ref, table, replay)
    finally:
        client.close()


@pytest.mark.parametrize("server", ["sync", "ssp", "deterministic"])
def test_the_gated_servers_serve_the_keyed_ops(ref, server):
    """Under the `sync`, SSP and deterministic servers the keyed ops are
    served message by message, as under the async server."""
    flags = {"sync": dict(sync=True), "ssp": dict(ssp_staleness=2),
             "deterministic": dict(deterministic=True)}[server]
    mv.init(mesh_shape="1", **flags)
    table = _table(ref)
    replay = ref.Replay(np.arange(SIZE), SEED, OPT)
    rng = np.random.default_rng(7)
    for _ in range(6):
        keys = _keys(rng, 150)
        grad = ref.to_float(ref.grad_k(rng, len(keys)))
        table.add(keys, grad)
        replay.add(replay.plan(keys), grad)
        got = table.get(keys)
        z, _, want, steps = replay.state(keys)
        assert ref.w_error(got, want, z, steps, OPT) <= 1
    # a Get more in the same round would wait for the round's end
    _check(ref, table, replay, weights=server != "sync")


def test_adds_of_two_workers_never_fuse(ref):
    """An FTRL step is not linear: the table hands the dispatcher no merged
    Add, whatever it drained."""
    mv.init(mesh_shape="1")
    table = _table(ref)
    requests = [(np.array([1, 2], np.int32), np.ones(2, np.float32))] * 3
    assert table._server_table.merge_add_requests(requests) is None


# -- the reference itself ------------------------------------------------------

def test_the_reference_in_float32_tracks_a_float64_replay(ref):
    """The reference's float32 arithmetic against the same rule in float64,
    over 600 steps of hot keys: inside its own tolerance with room, and `n`
    (multiples of 2**-18 that outgrow 24 bits) within a float32's rounding
    of the float64 sum."""
    rng = np.random.default_rng(8)
    keys = np.arange(200)
    single, double = (ref.Replay(keys, SEED, OPT, dtype)
                      for dtype in (np.float32, np.float64))
    plan = single.plan(keys)
    for _ in range(600):
        grad = ref.to_float(ref.grad_k(rng, len(keys)))
        single.add(plan, grad)
        double.add(plan, grad)
    assert ref.z_error(single.z, double.z, single.steps) < 0.5
    assert np.abs(single.n / double.n - 1).max() < 600 * 2.0 ** -24


def test_the_reference_fails_a_bfloat16_gradient(ref):
    """The control: one Add whose gradient was rounded to bfloat16 moves a
    quarter of the values by 1/512: `n` differs in about a quarter of the
    keys and `z` reads a thousand times the limit."""
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    keys = np.arange(4000)
    grad = ref.to_float(ref.grad_k(rng, len(keys)))
    lowered = np.asarray(jnp.asarray(grad).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    assert 0.2 < (lowered != grad).mean() < 0.3
    sound, wrong = (ref.Replay(keys, SEED, OPT) for _ in range(2))
    sound.add(sound.plan(keys), grad)
    wrong.add(wrong.plan(keys), lowered)
    assert ref.n_mismatch(wrong.n, sound.n) > 800
    assert ref.z_error(wrong.z, sound.z, sound.steps) > 500
    # a key that took no step is allowed nothing
    assert ref.z_error(sound.z + np.float32(2.0 ** -20), sound.z,
                       np.zeros(len(keys))) == float("inf")


@pytest.mark.parametrize("fault", ["lost", "twice"])
@pytest.mark.parametrize("at_step", [2, 300, 3000])
def test_one_faulty_step_of_a_hot_key_is_seen(ref, fault, at_step):
    """A key takes 3,200 steps (a window's every-step key) and one of them,
    early or late, is lost or applied twice. `n` is compared for equality
    and sees the fault from then on, at any step, by the square of the
    gradient in question; `z` reads outside its limit too while the fault
    is early, and at a late step, where the limit has grown like the
    steps, need not: `n` is the comparison that catches a lost or a doubled
    Add."""
    seed, steps = 45, 3200
    rng = np.random.default_rng(seed)
    keys = np.array([7], np.int32)
    grads = [ref.to_float(ref.grad_k(rng, 1)) for _ in range(8)]
    assert all(abs(float(g[0])) > 0.05 for g in grads)
    sound, faulty = (ref.Replay(keys, seed, OPT) for _ in range(2))
    plan = sound.plan(keys)
    for step in range(1, steps + 1):
        grad = grads[step % len(grads)]  # pooled: each returns in turn
        sound.add(plan, grad)
        for _ in range({"lost": 0, "twice": 2}[fault]
                       if step == at_step else 1):
            faulty.add(plan, grad)
    assert ref.n_mismatch(faulty.n, sound.n) == 1
    assert abs(float(faulty.n[0] - sound.n[0])) > 0.002
    z_err = ref.z_error(faulty.z, sound.z, sound.steps)
    if at_step <= 300:
        assert z_err > 1


def test_the_gradient_grid_keeps_n_exact(ref):
    """The grid `n`'s equality rests on: 9 bits in [-1, 1), so a square has
    18 bits and is exact in float32, and `n + g^2` is one addition of two
    exact numbers on either side."""
    rng = np.random.default_rng(10)
    k = ref.grad_k(rng, 1 << 16)
    assert k.min() == -512 and k.max() == 511 and k.dtype == np.int16
    g = ref.to_float(k)
    np.testing.assert_array_equal(
        (g * g).astype(np.float64) * 512.0 ** 2, k.astype(np.int64) ** 2)
    z0, n0 = ref.init_zn(np.arange(1 << 16), SEED)
    assert z0.min() == -2.0 and z0.max() == 2.0 - 1 / 64
    assert n0.min() == 0.0 and n0.max() == 16.0 - 1 / 64
    zk, nk = ref.init_k(np.arange(1 << 16), SEED)
    assert len(np.unique(zk)) == 256 and len(np.unique(nk)) == 1024


def test_the_hash_is_the_same_on_a_device(ref):
    """The benchmark makes the initial state on the device from the
    reference's own hash, handed `jax.numpy`: the same bits."""
    import jax.numpy as jnp

    keys = np.concatenate([np.arange(5000), [882774572, 2 ** 31 - 1]])
    for seed in (0, 7, 2147539911):
        want = ref.init_zn(keys, seed)
        got = ref.init_zn(jnp.asarray(keys, jnp.int32), seed, jnp)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), b)


# -- the benchmark's cell ------------------------------------------------------

def test_the_trace_readers_find_the_two_programs(monkeypatch):
    """`ftrl_*_device_ms` and `ftrl_*_roofline` over a synthetic trace: the
    modules that lie wholly in the window, by name; a trace without them
    (the parent's) reads None and does not raise."""
    from benchmark import ftrl_bytes, ftrl_trace, rws_trace

    def event(name, start_us, dur_us):
        return [name, start_us * 1000, dur_us * 1000]

    modules = [event("jit__ftrl_keyed_get(7)", 100, 50),
               event("jit__ftrl_keyed_add(8)", 200, 400),
               event("jit__ftrl_keyed_get(7)", 700, 70),
               event("jit__ftrl_keyed_add(8)", 800, 600),
               event("jit__row_gather(5)", 1450, 10),
               event("jit__ftrl_keyed_add(8)", 1480, 400)]  # past the end
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [event("bench.window", 0, 1500)]}]}]}

    class Run:
        trace = True
        cell = {"name": "x"}
        result = {"adds": 4, "add_rows": 4000, "gets": 4, "get_rows": 4000}
        peaks = {"hbm_bytes_per_s": 819e9}

    monkeypatch.setattr(rws_trace, "_raw", lambda run: trace)
    run = Run()
    assert ftrl_trace.programs(run, "add") == (2, pytest.approx(1000e-6))
    assert ftrl_trace.programs(run, "get") == (2, pytest.approx(120e-6))
    read = {name: common.load_module("layers", name).read for name in (
        "ftrl_add_device_ms", "ftrl_get_device_ms", "ftrl_add_roofline",
        "ftrl_get_roofline")}
    assert read["ftrl_add_device_ms"](run) == pytest.approx(0.5)
    assert read["ftrl_get_device_ms"](run) == pytest.approx(0.06)
    assert read["ftrl_add_roofline"](run) == pytest.approx(
        100.0 * 2000 * 20 / 1000e-6 / 819e9)
    assert read["ftrl_get_roofline"](run) == pytest.approx(
        100.0 * 2000 * 12 / 120e-6 / 819e9)
    assert ftrl_bytes.add_bytes(115080) == 115080 * 20
    assert ftrl_bytes.get_bytes(115080) == 115080 * 12
    with pytest.raises(ValueError, match="more than the chip can move"):
        ftrl_bytes.share_of_peak(ftrl_bytes.add_bytes(10 ** 9), 1e-3, 819e9)
    plain = Run()
    monkeypatch.setattr(rws_trace, "_raw", lambda run: {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": [
            event("jit__row_gather(5)", 100, 10)]}]}, trace["planes"][1]]})
    assert all(reader(plain) is None for reader in read.values())


def test_the_zipf_draw_builds_nothing_of_a_features_size():
    """The driver's draw: ranks by the inverse of the cumulative weights
    (tabulated to 65,536, the logarithm beyond), ids by an affine bijection
    of the feature's ids. The bijection is one; the head of the law has its
    weights; the tail past the table is reached; the arrays held are the
    table's 65,536 floats whatever the feature's count."""
    driver = common.load_module("drivers", "key_updates_local")
    rng = np.random.default_rng(11)
    small = driver.ZipfValues(1543, rng)
    ids = (small._a * np.arange(1543) + small._b) % 1543
    assert len(np.unique(ids)) == 1543
    draws = small.draw(rng, 200_000)
    assert draws.min() >= 0 and draws.max() < 1543
    counts = np.bincount(draws, minlength=1543)
    harmonic = (1.0 / np.arange(1, 1544)).sum()
    for rank in (0, 1, 9):       # the law's head, within five deviations
        want = 200_000 / (rank + 1) / harmonic
        assert abs(counts[ids[rank]] - want) < 5 * np.sqrt(want)
    large = driver.ZipfValues(292775614, rng)
    assert len(large._head) == 65536
    draws = large.draw(rng, 16384)
    assert draws.min() >= 0 and draws.max() < 292775614
    ranks = (draws - large._b) * pow(large._a, -1, 292775614) % 292775614
    share_late = (ranks >= 65536).mean()
    want_late = 1 - large._head[-1] / large._total
    assert abs(share_late - want_late) < 0.02 and ranks.max() > 10 ** 7
    # about 11,000 distinct values of such a feature in a 16,384-sample step
    assert 9000 < len(np.unique(draws)) < 13000


BREAK = """
from multiverso_tpu.tables import ftrl_table as ft
_orig = ft.FTRLWorker.add_device_async
def _altered(self, grads, keys):
    _altered.calls += 1
    if _altered.calls == 15:   # the fourth Add of the window
        grads = grads.at[len(keys) - 1].add(1.0 / 512)   # the bias
    return _orig(self, grads, keys)
_altered.calls = 0
ft.FTRLWorker.add_device_async = _altered
"""


def _rehearse(seed, prelude=""):
    import json
    import os
    import subprocess
    import sys

    root = common.ROOT
    args = ["--workload", "ftrlctr.step-keys", "--seed", str(seed),
            "--seconds", "1", "--trace", "0", "--rehearse"]
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


COMPARISONS = ["created_state_mismatch", "replay_quiet_mismatch",
               "replay_w_error", "replay_z_error", "replay_n_mismatch",
               "start_sample_w_error", "start_unnamed_mismatch",
               "window_get_error",
               "final_sample_w_error", "final_sample_z_error",
               "final_sample_n_mismatch", "unnamed_state_mismatch"]


def test_the_cell_rehearses():
    """`ftrlctr.step-keys` end to end at rehearsal sizes on the CPU: every
    comparison inside its limit, every Add of the run replayed, every
    step's keys named twice (its Get and its Add)."""
    last, compared = _rehearse(2147530047)
    assert sorted(compared) == sorted(COMPARISONS)
    assert all(c["ok"] for c in compared.values())
    assert last["correct"] is True and last["failed"] == 0
    counts = last["counts"]
    assert counts["ops"] == 2 * counts["adds"] == last["attempted"]
    assert counts["rows"] == 2 * counts["add_rows"] == 2 * counts["get_rows"]
    assert counts["adds_replayed"] == counts["adds"] + 11
    # the keys every step names took every step
    assert counts["most_steps"] == counts["adds_replayed"]


def test_a_broken_timed_path_is_not_correct():
    """The bias's gradient altered by 1/512 in one Add of the window, where
    the worker hands it over: `n` of that key differs from then on, and the
    run reads not correct by the comparisons after the fault, not by those
    before it."""
    last, compared = _rehearse(3, prelude=BREAK)
    assert last["correct"] is False
    assert compared["replay_n_mismatch"]["ok"]
    assert compared["final_sample_n_mismatch"]["value"] == 1
    assert compared["unnamed_state_mismatch"]["ok"]


def test_the_bfloat16_gradient_control_reads_not_correct():
    """`benchmark/tests/control_keys.py`: the cell with every Add's gradient
    rounded to bfloat16 where the server's table takes it reads not
    correct; in float32 the same patch changes nothing."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "control_keys", os.path.join(common.ROOT, "benchmark", "tests",
                                     "control_keys.py"))
    control_keys = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control_keys)
    sound = control_keys.run_control("ftrlctr.step-keys", 2147530048, 1.0,
                                     dtype="float32", rehearse=True)
    assert sound["correct"] is True, sound
    report = control_keys.run_control("ftrlctr.step-keys", 2147530048, 1.0,
                                      rehearse=True)
    assert report["correct"] is False, report
    failed = {c["compared"] for c in report["compared"] if not c["ok"]}
    assert {"replay_n_mismatch", "replay_z_error",
            "final_sample_n_mismatch"} <= failed
    assert not {"created_state_mismatch", "start_unnamed_mismatch",
                "unnamed_state_mismatch"} & failed


# -- the row kernel's step and write-back against XLA's gathers and scatters
# (PR 41, PR 42; since PR 49 the kernel works the step out itself) -----------
_KSIZE = 60_000          # 469 rows of 128; the scratch key shares the last


def _row_keys(case, rng, group):
    """(keys as a caller names them, gradient values past the keys);
    ``group``: the slots a grid step of the kernel walks (G in a case's
    name)."""
    one_a_row = np.arange(0, 400) * 128 + rng.integers(0, 128, 400)
    if case == "distinct keys one a row":
        return one_a_row, 0
    if case == "300 consecutive keys":
        return np.arange(1000, 1300), 0
    if case == "the 14 keys before the scratch key and pads":
        return np.arange(_KSIZE - 14, _KSIZE), 0
    if case == "a key thrice among others of its row":
        return np.concatenate([np.arange(640, 700), [650, 650],
                               one_a_row[:50]]), 0
    if case == "a row's run across two groups":
        # G - 56 keys a row each sort first; row 300's 128 keys take the
        # next slots, 56 before slot G and 72 from it
        return np.concatenate([one_a_row[:group - 56],
                               300 * 128 + np.arange(128)]), 0
    if case == "NaN in the gradient past the keys":
        return np.concatenate([one_a_row[:100], np.arange(2000, 2100)]), 324
    if case == "600 draws of 150 keys":
        # every key some four times, in several groups
        return 1100 + rng.integers(0, 150, 600), 0
    if case in _STRADDLES:
        # the key's slots lie either side of slot G, the boundary of two
        # grid steps of the kernel, among other keys of its row on both
        before, times = _STRADDLES[case]
        key = 300 * 128 + 64
        return np.concatenate([
            one_a_row[:group - before - 3], key - np.arange(1, 4),
            [key] * times, key + np.arange(1, 4), one_a_row[301:351]]), 0
    raise ValueError(case)


# a repeated key across two groups: (its slots before slot G, its slots)
_STRADDLES = {"a key twice at slots G-1 and G": (1, 2),
              "a key thrice at slots G-2 to G": (2, 3),
              "a key thrice at slots G-1 to G+1": (1, 3),
              "a key four times at slots G-2 to G+1": (2, 4)}


@pytest.mark.parametrize("case", [
    "distinct keys one a row", "300 consecutive keys",
    "the 14 keys before the scratch key and pads",
    "a key thrice among others of its row",
    "a row's run across two groups",
    "-0.0, inf and a denormal in unnamed lanes",
    "NaN in the gradient past the keys",
    "two live-slot counts of one bucket", *_STRADDLES,
    "600 draws of 150 keys",
    "n = 0, |z| <= lambda1, inf and NaN in unnamed lanes",
    "four Adds of one row's keys in a row",
    "gradients off the binary grid"])
def test_the_row_kernel_writes_back_what_the_scatter_writes(ref, case):
    """The keyed Add's two programs on one state: the row kernel's
    (interpreted), which since PR 49 computes the FTRL step on the rows it
    read, and XLA's (gathers, the same `ftrl_step`, scatters) give `z` and
    `n` equal in EVERY bit, the keys named and every other entry, the
    scratch entries too: interpreted on the CPU the rule's operations are
    XLA's on both sides. A key several slots name steps once, wherever the
    boundary of two grid steps (slot G) falls among them. The kernel
    computes the rule on every lane of a row it read and writes the lanes
    named alone: what the rule makes of `n = 0`, of `|z| <= lambda1`, of
    `inf` and of NaN in a lane nobody names is never written. Several Adds
    on one state: each step reads what the one before wrote."""
    import jax.numpy as jnp

    from multiverso_tpu.ops.pallas_rows import LANE_GROUP as G
    from multiverso_tpu.tables import ftrl_table as ft
    from multiverso_tpu.tables.device_ids import live_slots
    from multiverso_tpu.utils import next_pow2

    rng = np.random.default_rng(41)
    padded = -(-(_KSIZE + 1) // 1024) * 1024
    z0, n0 = (np.zeros(padded, np.float32) for _ in range(2))
    z0[:_KSIZE], n0[:_KSIZE] = ref.init_zn(np.arange(_KSIZE), SEED)
    if case == "two live-slot counts of one bucket":
        ops = [(np.arange(3000, 3600), 0), (np.arange(3300, 4200), 0)]
    elif case == "-0.0, inf and a denormal in unnamed lanes":
        ops = [(np.arange(1000, 1300, 3), 0)]
        # rows 7-10 are named, a key in three; these lanes are not
        z0[[1001, 1004, 1007]] = -0.0, np.inf, 1e-42
        n0[[1002, 1005, 1008]] = -0.0, np.inf, 1e-42
    elif case == "n = 0, |z| <= lambda1, inf and NaN in unnamed lanes":
        ops = [(np.arange(1000, 1300, 3), 0)]
        # rows 7-10 again; the rule divides by what these make
        odd = np.array([0.5, -1.0, np.inf, -np.inf, np.nan], np.float32)
        z0[[1001, 1004, 1007, 1010, 1013]] = odd
        n0[[1001, 1004, 1007, 1010, 1013]] = 0.0
        n0[[1016, 1019]] = np.nan, -1.0       # sqrt of a negative too
        # a payload no arithmetic makes: it must stand as well
        z0[1022:1023].view(np.uint32)[:] = 0x7fc12345
    elif case == "four Adds of one row's keys in a row":
        # a whole row, its neighbours' halves, the same keys every time
        ops = [(np.arange(300 * 128 - 64, 301 * 128 + 64), 0)] * 4
    elif case == "gradients off the binary grid":
        ops = [(np.arange(1000, 1300), 0), (np.arange(1100, 1500), 0)]
    else:
        ops = [_row_keys(case, rng, G)]
    _, add = ft._make_programs(scratch=_KSIZE, **OPT)
    states = [[jnp.asarray(z0), jnp.asarray(n0)] for _ in range(2)]
    lives = set()
    for keys, longer in ops:
        count = len(keys)
        bucket = max(next_pow2(count + 1), 128)
        live = live_slots(count, bucket)
        lives.add((bucket, live))
        ids = np.full(bucket, _KSIZE, np.int32)
        ids[:count] = keys
        grad = np.full(count + longer, np.nan, np.float32)
        grad[:count] = ref.to_float(ref.grad_k(rng, count))
        if case == "gradients off the binary grid":
            # n + g*g rounds here: a contracted multiply-add would differ
            grad[:count] = rng.standard_normal(count).astype(np.float32) * 3
        for state, rows in zip(states, (None, True)):
            # the third result is the rows the kernel walked (PR 51)
            *state[:], walked = add(*state, jnp.asarray(ids),
                                    jnp.asarray(grad), live=live, rows=rows)
            assert walked is None if rows is None else int(walked) == len(
                np.unique(np.append(keys, _KSIZE) >> 7))
    for want, got, was in zip(states[0], states[1], (z0, n0)):
        want, got = (np.asarray(s).view(np.uint32) for s in (want, got))
        np.testing.assert_array_equal(got, want)
        assert (want != was.view(np.uint32)).any()
    if case == "two live-slot counts of one bucket":
        assert len(lives) == 2 and len({b for b, _ in lives}) == 1
    if case == "a row's run across two groups":
        slots = np.sort(ops[0][0]) >> 7
        assert slots[G - 1] == slots[G] == 300
    if case == "600 draws of 150 keys":
        slots = np.sort(ops[0][0])
        assert sum(slots[edge - 1] == slots[edge]
                   for edge in range(G, 600, G)) >= 2
    if case in _STRADDLES:
        before, times = _STRADDLES[case]
        slots = np.sort(ops[0][0])
        assert 0 < before < times
        assert (slots[G - before:G - before + times] == slots[G]).all()
        assert (slots == slots[G]).sum() == times


def test_a_mesh_of_four_keeps_xlas_scatter_and_says_so(ref, monkeypatch):
    """On several devices the keyed Add is XLA's partitioned program, and
    its launch record and counters say `xla`; on one device `pallas`. A
    bucket past the kernel's scalar prefetch says `xla` too. All three
    leave `z` and `n` with the same bits."""
    import time

    from multiverso_tpu import dashboard
    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.runtime.zoo import Zoo

    keys = _keys(np.random.default_rng(7), 300)
    grads = np.ones(300, np.float32)
    paths, states = {}, []
    for mesh, limit in (("4", None), ("1", None), ("1", 256)):
        mv.init(mesh_shape=mesh)
        if limit:
            monkeypatch.setattr(pallas_rows, "PREFETCH_SLOTS", limit)
        table = _table(ref)
        counts = {path: _count("ROW_LAUNCH_%s_ADD" % path)
                  for path in ("XLA", "PALLAS")}
        monkeypatch.setattr(Dashboard, "profile_annotations", True)
        t0 = time.perf_counter()
        table.add(keys, grads)
        Zoo.instance().server.run_serialized(lambda: None)
        t1 = time.perf_counter()
        monkeypatch.setattr(Dashboard, "profile_annotations", False)
        launches = [r for r in dashboard.RING.window(t0, t1)[0]
                    if r.stage == "TABLE_ROW_LAUNCH"]
        paths[mesh, limit] = (
            [r.path for r in launches],
            {path: _count("ROW_LAUNCH_%s_ADD" % path) - was
             for path, was in counts.items()})
        states.append([np.asarray(table.get_state_device(name))[:SIZE + 1]
                       .view(np.uint32) for name in "zn"])
        mv.shutdown()
    assert paths["4", None] == (["xla"], {"XLA": 1, "PALLAS": 0})
    assert paths["1", None] == (["pallas"], {"XLA": 0, "PALLAS": 1})
    # 300 keys take a bucket of 512
    assert paths["1", 256] == (["xla"], {"XLA": 1, "PALLAS": 0})
    for other in states[1:]:
        for want, got in zip(states[0], other):
            np.testing.assert_array_equal(got, want)


_WHO_LOADS_THE_KERNEL = """
import json, sys, threading
import numpy as np
import multiverso_tpu as mv

def loaded():
    return "jax.experimental.pallas" in sys.modules

seen = {"import": loaded()}
for mesh in ("4", "1"):
    mv.init(mesh_shape=mesh)
    table = mv.create_table(
        "ftrl", 3000, init=lambda lo, count: (np.ones(count, np.float32),
                                              np.ones(count, np.float32)))
    seen["threads " + mesh] = sorted(
        t.name for t in threading.enumerate() if "kernel" in t.name)
    table.add(np.arange(5, dtype=np.int32), np.ones(5, np.float32))
    seen["mesh " + mesh] = loaded()
    seen["z " + mesh] = float(np.asarray(table.get_state_device("z"))[4])
    mv.shutdown()
print("SEEN", json.dumps(seen))
"""


def test_only_a_table_the_row_kernel_serves_loads_its_module():
    """`import multiverso_tpu` and an FTRL table on a mesh of four load no
    `jax.experimental.pallas` (a second of module code every process that
    imports the package would pay: PR 41 did); a table on one device does,
    under the fill of its state, and its constructor leaves no thread."""
    import json
    import os
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable, "-c", _WHO_LOADS_THE_KERNEL], cwd=common.ROOT,
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=common.ROOT))
    assert done.returncode == 0, done.stderr[-2000:]
    seen = json.loads(
        [x for x in done.stdout.splitlines() if x.startswith("SEEN ")][-1][5:])
    assert seen["import"] is False
    assert seen["mesh 4"] is False and seen["mesh 1"] is True
    assert seen["threads 4"] == seen["threads 1"] == []
    # both programs stepped the key
    assert seen["z 4"] == seen["z 1"] != 1.0
