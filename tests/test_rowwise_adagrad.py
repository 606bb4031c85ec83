"""The `rowwise_adagrad` updater (one float32 of state a row, an Add that is an
optimizer step) against the benchmark's plain reference
(`benchmark/reference/dlrm-rwsadagrad-emb128.py`, which imports nothing of
the program), at small sizes on the CPU: device and host Adds, the
interpreted Pallas row kernel and XLA's scatter, one device and four."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark import common
from multiverso_tpu.updaters import AddOption

LR, EPS = 0.01, 1e-10
OPTION = AddOption(learning_rate=LR, rho=EPS)
NAME = "rowwise_adagrad"


@pytest.fixture(scope="module")
def ref():
    return common.load_module("reference", "dlrm-rwsadagrad-emb128")


def _open_gate(monkeypatch, kernel, shards=1):
    """`pallas`: the interpreted row kernel in groups of 8 on one CPU
    device; `xla`: XLA's scatter on the default mesh."""
    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables import matrix_table
    if kernel == "pallas":
        monkeypatch.setattr(
            matrix_table, "_use_pallas_scatter",
            lambda platform, num_shards, *width: num_shards == 1)
        monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)
    mv.init(mesh_shape=str(shards))


def _table(ref, rows, cols, seed):
    init, _ = ref.init_table(rows, cols, seed)
    return mv.create_table("matrix", rows, cols, np.float32,
                           updater_type=NAME, init_value=init)


def _ops(ref, rng, rows, cols, count, n):
    """`count` Adds of `n` distinct rows each, overlapping on a hot head."""
    ops = []
    for _ in range(count):
        hot = rng.choice(rows // 8, n // 2, replace=False)
        cold = rows // 8 + rng.choice(rows - rows // 8, n - n // 2,
                                      replace=False)
        ids = rng.permutation(np.concatenate([hot, cold])).astype(np.int32)
        ops.append((ids, ref.to_float(ref.grad_k(rng, n, cols))))
    return ops


def _send(table, ids, grad, form):
    import jax
    if form == "device":
        table.wait(table.add_device_async(jax.device_put(grad), ids, OPTION))
    else:
        table.add(grad, ids, OPTION)


def _replayed(ref, rows, cols, seed, ops, dtype=np.float32):
    replay = ref.Replay(np.arange(rows), cols, seed, LR, EPS, dtype)
    for ids, grad in ops:
        replay.add(replay.plan(ids), grad)
    return replay


def _state(table, rows):
    return np.asarray(table.get_state_device("s"))[:rows]


@pytest.mark.parametrize("form", ["device", "host"])
@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_updater_against_the_reference(ref, monkeypatch, kernel, form):
    """Six Adds that overlap on hot rows, each an optimizer step in the
    order acknowledged: every element of the table and every row's state
    inside the reference's tolerance, rows no Add names (table and state)
    to the last bit, and each launch record says whose rule ran on which
    program and what state it read and wrote."""
    from multiverso_tpu import dashboard

    rows, cols, n, seed = 600, 128, 41, 33
    _open_gate(monkeypatch, kernel)
    monkeypatch.setattr(dashboard.Dashboard, "profile_annotations", True)
    table = _table(ref, rows, cols, seed)
    server = table._server_table
    assert (server.plan.path == "pallas") == (kernel == "pallas")
    assert server.states["s"].shape == (1024,)  # whole lane tiles
    ops = _ops(ref, np.random.default_rng(seed), rows, cols, 6, n)
    t0 = time.perf_counter()
    for ids, grad in ops:
        _send(table, ids, grad, form)
    records, _ = dashboard.RING.window(t0, time.perf_counter())
    want = _replayed(ref, rows, cols, seed, ops)
    got_w, got_s = table.get(), _state(table, rows)
    assert ref.w_error(got_w, want.w, want.steps) <= 1.0
    assert ref.s_mismatch(got_s, want.s) == 0
    quiet = want.steps == 0
    assert quiet.any() and (want.steps > 2).any()
    np.testing.assert_array_equal(got_w[quiet], want.w[quiet])
    assert not got_s[quiet].any()
    launches = [r for r in records if r.stage == "TABLE_ROW_LAUNCH"
                and r.updater]
    assert len(launches) == 6
    for launch in launches:
        assert launch.updater == NAME and launch.path == kernel
        assert launch.state_rows == launch.n >= n
        assert launch.state_bytes == 8 * launch.n
    path = kernel.upper()
    assert dashboard.Dashboard.counter_value(
        f"ROW_LAUNCH_{path}_STATEFUL_ADD") == 6
    assert dashboard.Dashboard.counter_value(f"ROW_LAUNCH_{path}_ADD") == 6


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_two_adds_that_name_one_row_do_not_commute(ref, monkeypatch, kernel):
    """A then B and B then A give different tables, and each is the
    reference's for its order."""
    rows, cols, seed = 64, 16, 34
    rng = np.random.default_rng(seed)
    ids = np.arange(8, dtype=np.int32)
    a = (ids, ref.to_float(ref.grad_k(rng, 8, cols)))
    b = (ids[::-1].copy(), 4 * ref.to_float(ref.grad_k(rng, 8, cols)))
    _open_gate(monkeypatch, kernel)
    got = {}
    for order in ("ab", "ba"):
        table = _table(ref, rows, cols, seed)
        ops = [a, b] if order == "ab" else [b, a]
        _send(table, *ops[0], "device")
        _send(table, *ops[1], "host")
        want = _replayed(ref, rows, cols, seed, ops)
        got[order] = table.get()
        assert ref.w_error(got[order], want.w, want.steps) <= 1.0
        assert ref.s_mismatch(_state(table, rows), want.s) == 0
    assert np.abs(got["ab"][:8] - got["ba"][:8]).max() > 1e-4


def test_no_fused_host_group_is_formed(ref):
    """Queued host Adds that name one row are two optimizer steps, never
    one step of a summed gradient: the table offers the dispatcher no
    merge, and the row's state counts both means."""
    rows, cols, seed = 32, 16, 35
    mv.init()
    table = _table(ref, rows, cols, seed)
    rng = np.random.default_rng(seed)
    ids = np.array([3, 5], np.int32)
    grads = [ref.to_float(ref.grad_k(rng, 2, cols)) for _ in range(2)]
    assert table._server_table.merge_add_requests(
        [(ids, grads[0], OPTION), (ids, grads[1], OPTION)]) is None
    waits = [table.add_async(g, ids, OPTION) for g in grads]
    for w in waits:
        table.wait(w)
    want = _replayed(ref, rows, cols, seed, [(ids, g) for g in grads])
    assert (want.steps[ids] == 2).all()
    assert ref.s_mismatch(_state(table, rows), want.s) == 0
    assert ref.w_error(table.get(), want.w, want.steps) <= 1.0


def test_a_host_request_that_repeats_an_id_is_one_step_of_the_sum(ref):
    """Within one request a worker's own duplicates are summed (a sparse
    gradient is coalesced): one step a row."""
    rows, cols, seed = 32, 16, 36
    mv.init()
    table = _table(ref, rows, cols, seed)
    rng = np.random.default_rng(seed)
    grad = ref.to_float(ref.grad_k(rng, 3, cols))
    table.add(grad, np.array([7, 2, 7], np.int32), OPTION)
    want = _replayed(ref, rows, cols, seed,
                     [(np.array([7, 2], np.int32),
                       np.stack([grad[0] + grad[2], grad[1]]))])
    assert ref.w_error(table.get(), want.w, want.steps) <= 1.0
    assert ref.s_mismatch(_state(table, rows), want.s) == 0


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_sentinel_slots_with_zero_deltas_change_nothing(ref, monkeypatch,
                                                        kernel):
    """A device Add whose caller pads with sentinel-aimed slots and zero
    deltas (the compact training space's contract): the sentinel's `s`
    stays 0 and its row finite and unchanged (-lr / (sqrt(0) + eps) is
    -1e8, times a zero delta), and the live rows take their step."""
    import jax

    rows, cols, seed = 40, 16, 37
    _open_gate(monkeypatch, kernel)
    table = _table(ref, rows, cols, seed)
    server = table._server_table
    rng = np.random.default_rng(seed)
    live = np.array([4, 9, 1], np.int32)
    grad = np.zeros((11, cols), np.float32)
    grad[:3] = ref.to_float(ref.grad_k(rng, 3, cols))
    ids = np.concatenate([live, np.full(8, table.sentinel_row, np.int32)])
    before = np.asarray(server.data)[table.sentinel_row].copy()
    table.wait(table.add_device_async(jax.device_put(grad), ids, OPTION))
    data, state = np.asarray(server.data), np.asarray(server.states["s"])
    assert np.isfinite(data).all() and np.isfinite(state).all()
    np.testing.assert_array_equal(data[table.sentinel_row], before)
    assert state[table.sentinel_row] == 0.0
    want = _replayed(ref, rows, cols, seed, [(live, grad[:3])])
    assert ref.w_error(data[:rows, :cols], want.w, want.steps) <= 1.0
    assert ref.s_mismatch(state[:rows], want.s) == 0


def test_mean_is_over_the_tables_columns_not_its_lanes(ref):
    """A 50-column table lies in 128 lanes and a device delta may be
    narrower still: the mean of the squares divides by 50."""
    import jax

    rows, cols, seed = 30, 50, 38
    mv.init()
    table = _table(ref, rows, cols, seed)
    rng = np.random.default_rng(seed)
    ids = np.array([2, 11, 17, 29], np.int32)
    grad = ref.to_float(ref.grad_k(rng, 4, cols))
    _send(table, ids, grad, "host")
    narrow = grad.copy()
    narrow[:, 40:] = 0.0
    table.wait(table.add_device_async(jax.device_put(narrow[:, :40]), ids,
                                      OPTION))
    want = _replayed(ref, rows, cols, seed, [(ids, grad), (ids, narrow)])
    # a fiftieth is not exact in float32: a unit in the last place a step
    np.testing.assert_allclose(_state(table, rows), want.s, rtol=2.0 ** -22)
    assert ref.w_error(table.get(), want.w, want.steps) <= 1.0


def test_whole_table_add_is_a_step_of_every_row(ref):
    rows, cols, seed = 24, 16, 39
    mv.init()
    table = _table(ref, rows, cols, seed)
    rng = np.random.default_rng(seed)
    grads = [ref.to_float(ref.grad_k(rng, rows, cols)) for _ in range(2)]
    for grad in grads:
        table.add(grad, option=OPTION)
    want = _replayed(ref, rows, cols, seed,
                     [(np.arange(rows), g) for g in grads])
    assert (want.steps == 2).all()
    assert ref.w_error(table.get(), want.w, want.steps) <= 1.0
    assert ref.s_mismatch(_state(table, rows), want.s) == 0


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_row_apply_traceable_gives_a_fused_transaction_the_same_rule(
        ref, monkeypatch, kernel):
    """The traceable form a caller's fused jit embeds takes the step the
    Add path takes, to the bit."""
    import jax

    rows, cols, seed = 48, 128, 40  # XLA's scatter takes the table's lanes
    _open_gate(monkeypatch, kernel)
    rng = np.random.default_rng(seed)
    ids = np.array([5, 0, 31, 17, 8, 2, 40, 9], np.int32)
    grad = ref.to_float(ref.grad_k(rng, 8, cols))
    table = _table(ref, rows, cols, seed)
    _send(table, ids, grad, "device")
    by_add = (table.get(), _state(table, rows))
    other = _table(ref, rows, cols, seed)
    server = other._server_table
    worker, scalars = server._option_consts(OPTION)
    data, states = jax.jit(server.row_apply_traceable())(
        server.data, server.states, jax.device_put(ids),
        jax.device_put(grad), worker, scalars)
    np.testing.assert_array_equal(np.asarray(data)[:rows, :cols], by_add[0])
    np.testing.assert_array_equal(np.asarray(states["s"])[:rows], by_add[1])


@pytest.mark.parametrize("through", ["store_load", "checkpoint_file"])
def test_state_round_trips_and_training_resumes_to_the_bit(ref, tmp_path,
                                                           through):
    """Train, snapshot, restore into a fresh world, continue: equal, table
    and state, to uninterrupted training; the trailer holds the state at
    its logical length, one float32 a row."""
    from multiverso_tpu.checkpoint import (load_table, read_array,
                                           read_state_dict, store_table)
    from multiverso_tpu.io import MemoryStream

    rows, cols, seed = 90, 16, 41
    ops = _ops(ref, np.random.default_rng(seed), rows, cols, 6, 12)

    def world():
        mv.init(mesh_shape="1")
        return _table(ref, rows, cols, seed)

    table = world()
    for ids, grad in ops:
        _send(table, ids, grad, "host")
    want = (table.get(), _state(table, rows))
    mv.shutdown()

    table = world()
    for ids, grad in ops[:3]:
        _send(table, ids, grad, "host")
    if through == "checkpoint_file":
        path = str(tmp_path / "rws.mvckpt")
        store_table(table, path)
    else:
        stream = MemoryStream()
        table._server_table.store(stream)
        stream.seek(0)
        assert read_array(stream).shape == (rows, cols)
        states = read_state_dict(stream)
        assert list(states) == ["s"] and states["s"].shape == (rows,)
        assert states["s"].dtype == np.float32 and states["s"].any()
        stream.seek(0)
    mv.shutdown()

    table = world()
    if through == "checkpoint_file":
        load_table(table, path)
    else:
        table._server_table.load(stream)
    for ids, grad in ops[3:]:
        _send(table, ids, grad, "host")
    np.testing.assert_array_equal(table.get(), want[0])
    np.testing.assert_array_equal(_state(table, rows), want[1])


@pytest.mark.parametrize("form", ["device", "host"])
def test_four_virtual_devices_run_it(ref, form):
    """On a mesh of four devices the updater runs through XLA's partitioned
    programs: the state is sharded like the table's rows, and the result
    is the reference's."""
    from multiverso_tpu import dashboard

    rows, cols, seed = 1003, 128, 42
    mv.init(mesh_shape="4")
    table = _table(ref, rows, cols, seed)
    server = table._server_table
    state = server.states["s"]
    assert state.shape == (4096,)  # whole lane tiles on every shard
    assert len(state.sharding.device_set) == 4
    assert {s.data.shape for s in state.addressable_shards} == {(1024,)}
    ops = _ops(ref, np.random.default_rng(seed), rows, cols, 5, 64)
    for ids, grad in ops:
        _send(table, ids, grad, form)
    want = _replayed(ref, rows, cols, seed, ops)
    assert ref.w_error(table.get(), want.w, want.steps) <= 1.0
    assert ref.s_mismatch(_state(table, rows), want.s) == 0
    assert dashboard.Dashboard.counter_value(
        "ROW_LAUNCH_XLA_STATEFUL_ADD") == 5
    assert server.states["s"].sharding == state.sharding


def _device_add_the_dispatcher_prepares(table, grad, ids, option):
    """A device Add whose request carries bare numpy ids: the dispatcher
    sends them up in its TABLE_ROW_PREP, as it did for every device Add
    before the caller did."""
    import jax

    from multiverso_tpu.runtime.message import MsgType

    table.wait(table._submit(MsgType.Request_Add,
                             (ids, jax.device_put(grad), option)))


@pytest.mark.parametrize("cols", [128, 300])
@pytest.mark.parametrize("kernel,shards", [("pallas", 1), ("xla", 1),
                                           ("xla", 4)])
def test_ids_sent_at_submit_change_no_bit(monkeypatch, kernel, shards, cols):
    """Raw gradients by `add_device_async`, their ids sent up from the
    caller's thread at submit (and overwritten as soon as the call
    returns), against the same device Adds with the ids sent up by the
    dispatcher: tables, accumulators and every Get (by `get_device_async`
    against `get_async`) equal to the bit, at one and at three lane tiles a
    row, on the interpreted row kernel and on XLA's scatter; on four
    devices (the state step takes XLA's partitioned programs, the Gets are
    routed) the dispatcher sends every op's ids up and the two tables
    still agree. Not against `add_async`: a host Add pads its rows to
    the bucket, another shape of the same program, and on the CPU the two
    differ in the last place of a few elements of a small op, before ids
    went up at submit as after."""
    from test_matrix_table import _device_ops_equal_host_ops

    from multiverso_tpu import dashboard

    _open_gate(monkeypatch, kernel, shards)
    try:
        monkeypatch.setattr(dashboard.Dashboard, "profile_annotations", True)
        records = _device_ops_equal_host_ops(
            4000, cols, NAME, OPTION,
            lambda rng, n, cols: rng.integers(-128, 128, (n, cols)).astype(
                np.float32) / 512,
            _device_add_the_dispatcher_prepares, at_submit=shards == 1)
        launched = [r for r in records if r.stage == "TABLE_ROW_LAUNCH"]
        assert [r.ids_from for r in launched] == (
            ["caller" if shards == 1 else "dispatcher"] * 3
            + ["dispatcher"] * 3) * 5
        assert [r.updater for r in launched] == [NAME, "", ""] * 10
        assert launched[0].path == kernel
    finally:
        mv.shutdown()


def test_an_array_table_refuses_it_by_name():
    from multiverso_tpu.log import FatalError
    mv.init()
    with pytest.raises(FatalError, match=NAME):
        mv.create_table("array", 16, np.float32, updater_type=NAME)


@pytest.mark.parametrize("updater,shape", [
    (NAME, lambda s: (2048,)),
    ("momentum_sgd", lambda s: (1, s.padded_rows, s.padded_cols)),
    ("adagrad", lambda s: (s.num_workers, s.padded_rows, s.padded_cols))])
def test_states_are_zeros_made_on_the_device(updater, shape):
    """Every updater's state starts as zeros on the table's devices, in
    its own shape: one value a row for the row-state updater, the table's
    shape behind a worker dimension for the others."""
    import jax

    mv.init(mesh_shape="2", local_workers=1)
    table = mv.create_table("matrix", 37, 20, np.float32,
                            updater_type=updater)
    server = table._server_table
    (state,) = server.states.values()
    assert isinstance(state, jax.Array)
    assert state.shape == shape(server) and state.dtype == np.float32
    assert len(state.sharding.device_set) == 2
    assert not np.asarray(state).any()


def test_reference_in_float32_tracks_a_float64_replay(ref):
    """The reference's float32 arithmetic against the same rule in float64,
    40 Adds of overlapping rows: inside half of its own tolerance (the
    tolerance is for another float32 implementation, not for float32)."""
    rows, cols, seed = 400, 128, 43
    ops = _ops(ref, np.random.default_rng(seed), rows, cols, 40, 96)
    single = _replayed(ref, rows, cols, seed, ops)
    double = _replayed(ref, rows, cols, seed, ops, np.float64)
    np.testing.assert_array_equal(single.steps, double.steps)
    assert single.steps.max() >= 30
    assert ref.w_error(single.w, double.w, double.steps) <= 0.5
    # the mean is exact in both; float32 rounds `s + mean` once a step
    assert (np.abs(single.s - double.s)
            <= double.steps * 2.0 ** -24 * double.s).all()


def test_reference_fails_a_bfloat16_gradient(ref):
    """The comparison is tight enough for the control: the same Adds with
    their gradients rounded to bfloat16 read far outside the tolerance, and
    a quarter of the gradient's values (9 bits, odd, a half or more) do not
    survive the rounding."""
    import jax.numpy as jnp

    rows, cols, seed = 400, 128, 44
    ops = _ops(ref, np.random.default_rng(seed), rows, cols, 6, 96)
    lowered = [(ids, np.asarray(jnp.asarray(g).astype(jnp.bfloat16)
                                .astype(jnp.float32))) for ids, g in ops]
    assert 0.2 < np.mean(lowered[0][1] != ops[0][1]) < 0.3
    sound = _replayed(ref, rows, cols, seed, ops)
    low = _replayed(ref, rows, cols, seed, lowered)
    assert ref.w_error(low.w, sound.w, sound.steps) > 4.0
    assert ref.s_mismatch(low.s, sound.s) > 0.9 * (sound.steps > 0).sum()
    # a row that took no step may not differ at all
    wrong = sound.w.copy()
    quiet = np.flatnonzero(sound.steps == 0)[0]
    wrong[quiet, 0] += np.float32(2.0 ** -20)
    assert ref.w_error(wrong, sound.w, sound.steps) == float("inf")


@pytest.mark.parametrize("fault", ["lost", "twice"])
@pytest.mark.parametrize("at_step", [2, 300, 3000])
def test_one_faulty_step_of_a_hot_row_is_seen(ref, fault, at_step):
    """A row takes 3,200 steps and one of them, early or late, is lost or
    applied twice: the state differs from then on (it is compared for
    equality, so it sees the fault at any step), and the table's values
    read outside the root-k tolerance (they see a single step until a
    row's 4,500th or so)."""
    cols, seed, steps = 128, 45, 3200
    rng = np.random.default_rng(seed)
    ids = np.array([7], np.int32)
    grads = [ref.to_float(ref.grad_k(rng, 1, cols)) for _ in range(8)]
    sound = ref.Replay(ids, cols, seed, LR, EPS)
    faulty = ref.Replay(ids, cols, seed, LR, EPS)
    plan = sound.plan(ids)
    for step in range(1, steps + 1):
        grad = grads[step % len(grads)]  # pooled: each returns in turn
        sound.add(plan, grad)
        for _ in range({"lost": 0, "twice": 2}[fault]
                       if step == at_step else 1):
            faulty.add(plan, grad)
    assert ref.s_mismatch(faulty.s, sound.s) == 1
    assert abs(float(faulty.s[0] - sound.s[0])) > 0.2  # a third, not a bit
    assert ref.w_error(faulty.w, sound.w, sound.steps) > 1.2


def test_gradient_rows_keep_their_squares_exact(ref):
    """The grid the state's equality rests on: 9 bits in [-1, 1), a row's
    squares summing to 64 at most, so float32 adds them exactly in any
    order (forwards, backwards and numpy's pairwise agree with integers);
    a row past the limit is drawn again."""
    rng = np.random.default_rng(46)
    k = ref.grad_k(rng, 4096, 128)
    assert k.min() == -512 and k.max() == 511 and k.dtype == np.int16
    g = ref.to_float(k)
    want = (k.astype(np.int64) ** 2).sum(axis=1)
    assert want.max() <= ref.GRAD_SQUARES
    squares = g * g
    for order in (squares, squares[:, ::-1]):
        run = np.zeros(len(g), np.float32)
        for j in range(order.shape[1]):
            run += order[:, j]
        np.testing.assert_array_equal(run * np.float32(512 ** 2), want)
    np.testing.assert_array_equal(
        squares.sum(axis=1, dtype=np.float32) * np.float32(512 ** 2), want)

    class Loud:  # a generator whose first draw is all at the edge
        def __init__(self):
            self.calls = 0

        def integers(self, lo, hi, size, dtype):
            self.calls += 1
            return (np.full(size, lo, dtype) if self.calls == 1
                    else rng.integers(lo, hi, size=size, dtype=dtype))

    loud = Loud()
    k = ref.grad_k(loud, 4, 128)
    assert loud.calls == 2 and (k.astype(np.int64) ** 2).sum(axis=1).max() \
        <= ref.GRAD_SQUARES


def test_an_add_without_eps_is_refused(ref):
    """The option's own defaults (lr 0.1, rho 0.1) are not the rule's: an
    Add with no option, or with rho left at its default, fails at the
    server and leaves table and state as they were; the five other
    updaters take a bare Add as before."""
    rows, cols, seed = 16, 16, 47
    mv.init()
    table = _table(ref, rows, cols, seed)
    before = table.get().copy()
    grad = ref.to_float(ref.grad_k(np.random.default_rng(seed), 2, cols))
    ids = np.array([1, 5], np.int32)
    for option in (None, AddOption(learning_rate=LR)):
        with pytest.raises(Exception, match="rho"):
            table.add(grad, ids, option)
    np.testing.assert_array_equal(table.get(), before)
    assert not _state(table, rows).any()
    table.add(grad, ids, OPTION)
    assert _state(table, rows)[ids].all()
    plain = mv.create_table("matrix", rows, cols, np.float32,
                            updater_type="adagrad")
    plain.add(grad, ids)


def test_state_step_is_read_from_a_trace_by_module(monkeypatch):
    """`benchmark/rws_trace.py` on a plain-form trace: the operations of
    the `jit__row_state_add` programs that lie wholly in the window, less
    the row kernel, overlapping events counted once; a trace without the
    program reads None."""
    from benchmark import rws_bytes, rws_trace

    def event(name, start_us, dur_us):
        return [name, start_us * 1000, dur_us * 1000]

    kernel = ("%_scatter_add_call.1 = f32[1001,128]{1,0} custom-call("
              "s32[128]{0} %ids, f32[100,128]{1,0} %mul, f32[1001,128] %d)")
    modules = [event("jit__row_state_add(123)", 100, 500),
               event("jit__row_gather(5)", 700, 100),
               event("jit__row_state_add(123)", 900, 500),
               event("jit__row_state_add(123)", 1900, 500)]  # past the end
    ops = [event("%fusion = f32[100]{0} fusion(f32[1001]{0} %s)", 110, 100),
           event("%sort = (s32[100]{0}) sort(s32[100]{0} %ids)", 150, 100),
           event(kernel, 300, 250),
           event("%fusion = f32[128,128]{1,0} fusion(f32[1001,128] %data.1, "
                 "s32[104]{0} %p)", 710, 80),
           event("%fusion.1 = f32[1001]{0} fusion(f32[1001]{0} %c)", 950, 60),
           event(kernel, 1100, 250)]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [event("bench.window", 0, 1500)]}]}]}

    class Run:
        trace = True
        cell = {"name": "x"}
        result = {"adds": 2, "add_rows": 200, "row_cols": 128}
        peaks = {"hbm_bytes_per_s": 819e9}

    monkeypatch.setattr(rws_trace, "_raw", lambda run: trace)
    run = Run()
    programs, seconds = rws_trace.state_step(run)
    assert programs == 2 and seconds == pytest.approx((140 + 60) * 1e-6)
    state_ms = common.load_module("layers", "rws_state_device_ms").read(run)
    assert state_ms == pytest.approx(0.1)
    share = common.load_module("layers", "rws_state_roofline").read(run)
    assert share == pytest.approx(
        100.0 * rws_bytes.state_step_bytes(200, 128) / 200e-6 / 819e9)
    assert rws_bytes.state_step_bytes(100_000, 128) == 100_000 * 520
    plain = Run()
    monkeypatch.setattr(rws_trace, "_raw", lambda run: {"planes": [
        trace["planes"][0] | {"lines": [trace["planes"][0]["lines"][1]]},
        trace["planes"][1]]})
    assert rws_trace.state_step(plain) is None
    assert common.load_module("layers", "rws_state_roofline").read(
        plain) is None


def test_the_cell_rehearses_and_its_control_reads_not_correct():
    """`emb128rws.bulk-updates` end to end at rehearsal sizes on the CPU:
    every comparison inside its limit; the same run with every Add's
    gradient rounded to bfloat16 where the server takes it
    (`benchmark/tests/control.py --lower delta`) reads not correct."""
    root = common.ROOT
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "emb128rws.bulk-updates", "--seed", "2147530045", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=root, capture_output=True,
        text=True, timeout=600, env=dict(os.environ, PYTHONPATH=root))
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    compared = [json.loads(x) for x in done.stdout.splitlines()
                if x.startswith('{"compared"')]
    assert len(compared) == 8 and all(c["ok"] for c in compared)
    assert last["counts"]["adds_replayed"] == last["counts"]["adds"] + 5
    sys.path.insert(0, os.path.join(root, "benchmark", "tests"))
    try:
        import control
    finally:
        sys.path.pop(0)
    report = control.run_control("emb128rws.bulk-updates", 2147530046, 1.0,
                                 lower="delta", rehearse=True)
    assert report["correct"] is False, report
    failed = {c["compared"] for c in report["compared"] if not c["ok"]}
    assert {"replay_w_error", "replay_s_mismatch"} <= failed
