"""Pallas row-kernel tests (interpret mode on the CPU mesh).

The TPU-compiled path is exercised by chip_smoke.py on hardware; these
verify kernel semantics and the caller contracts (group-multiple batches,
sentinel padding, unique live ids). The kernel inside the table path is
covered by tests/test_chip_smoke.py. The tests at the production group
share one table shape: tracing the interpreted kernel costs a quarter of a
minute per new shape there, so every other test runs at a group of 8."""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from multiverso_tpu.ops import pallas_rows
from multiverso_tpu.ops.pallas_rows import ROW_GROUP

gather_rows = functools.partial(pallas_rows.gather_rows, interpret=True)
scatter_add_rows = functools.partial(pallas_rows.scatter_add_rows,
                                     interpret=True)
ROWS = 1024


@pytest.fixture
def rng():
    return np.random.default_rng(7)


@pytest.fixture
def group_of_8(monkeypatch):
    """The scatter-add at a row group of 8, its two semaphores read as every
    grid step ends. A DMA semaphore counts bytes (the interpreter's too:
    a start adds the destination's size, a wait takes its descriptor's
    off), so a group's one wait the size of the whole block must leave
    what the group's row copies signalled at exactly zero: a wait of any
    other size would hang the chip or let the add run before the rows are
    there. Fails the test that left either semaphore off zero."""
    import jax
    from jax.experimental import pallas as pl

    kernel, seen = pallas_rows._scatter_add_kernel, []

    def watched(*refs, **static):
        kernel(*refs, **static)
        sems = refs[-1]
        jax.debug.callback(
            lambda *values: seen.append(tuple(int(v) for v in values)),
            pl.semaphore_read(sems.at[0]), pl.semaphore_read(sems.at[1]))

    monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)
    monkeypatch.setattr(pallas_rows, "_scatter_add_kernel", watched)
    # the jitted call is keyed by shapes, not by the group or the kernel
    pallas_rows._scatter_add_call.clear_cache()
    yield 8
    pallas_rows._scatter_add_call.clear_cache()
    jax.effects_barrier()
    assert seen and set(seen) == {(0, 0)}, sorted(set(seen))


def test_gather_matches_take(rng):
    table = jnp.asarray(rng.normal(size=(ROWS, 128)).astype(np.float32))
    ids = jnp.asarray(rng.choice(ROWS, ROW_GROUP, replace=False).astype(np.int32))
    out = gather_rows(table, ids)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(table)[np.asarray(ids)])


def test_gather_repeated_ids_allowed(rng):
    # reads may repeat rows freely
    table = jnp.asarray(rng.normal(size=(ROWS, 128)).astype(np.float32))
    ids = jnp.asarray(np.array([3] * ROW_GROUP, np.int32))
    out = gather_rows(table, ids)
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(np.asarray(table)[3], (ROW_GROUP, 1)))


def test_scatter_add_unique_ids(rng):
    table = jnp.asarray(rng.normal(size=(ROWS, 128)).astype(np.float32))
    ids = rng.choice(ROWS, ROW_GROUP, replace=False).astype(np.int32)
    deltas = rng.normal(size=(ROW_GROUP, 128)).astype(np.float32)
    expect = np.asarray(table).copy()
    expect[ids] += deltas
    out = scatter_add_rows(table, jnp.asarray(ids), jnp.asarray(deltas))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_scatter_add_sentinel_padding(rng, group_of_8):
    """Pad slots aim at a sentinel row with zero deltas: live rows update,
    sentinel row is untouched (zero delta), matching the matrix-table
    bucket contract. Six copies of one row each way in the group."""
    ROW_GROUP = group_of_8
    rows, sentinel = ROWS, 100
    table = jnp.zeros((rows, 128), jnp.float32)
    live = np.array([5, 17], np.int32)
    ids = np.full(ROW_GROUP, sentinel, np.int32)
    ids[:2] = live
    deltas = np.zeros((ROW_GROUP, 128), np.float32)
    deltas[:2] = 1.0
    out = np.asarray(scatter_add_rows(table, jnp.asarray(ids),
                                      jnp.asarray(deltas)))
    np.testing.assert_allclose(out[live], np.ones((2, 128)))
    np.testing.assert_allclose(out[sentinel], np.zeros(128))
    mask = np.ones(rows, bool)
    mask[live] = False
    np.testing.assert_allclose(out[mask], 0.0)


def test_multiple_groups(rng):
    batch = ROW_GROUP * 2
    table = jnp.asarray(rng.normal(size=(ROWS, 128)).astype(np.float32))
    ids = rng.choice(ROWS, batch, replace=False).astype(np.int32)
    deltas = rng.normal(size=(batch, 128)).astype(np.float32)
    expect = np.asarray(table).copy()
    expect[ids] += deltas
    out = scatter_add_rows(table, jnp.asarray(ids), jnp.asarray(deltas))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)
    got = gather_rows(jnp.asarray(expect), jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got), expect[ids], rtol=1e-6)


@pytest.mark.parametrize("sign", [1.0, -1.0])
@pytest.mark.parametrize("n", [32, 16, 21, 1])
def test_scatter_add_grid_follows_the_delta(rng, n, sign, group_of_8):
    """ids may outnumber the delta's rows (a bucket): the rows the delta
    names get ``sign * delta`` to the bit, and nothing else changes: not
    the sentinel row behind the last group's tail, and not the LIVE rows
    that later slots name, which a kernel walking the ids would touch.
    Whole groups, a delta that ends inside a group (its tail repeats the
    sentinel, seven times for one row), and one wait a group each way."""
    ROW_GROUP = group_of_8
    bucket, sentinel = 4 * ROW_GROUP, ROWS - 1
    start = rng.normal(size=(ROWS, 128)).astype(np.float32)
    named = rng.choice(sentinel, bucket, replace=False).astype(np.int32)
    ids = named.copy()
    # the last launched group's tail reads and writes back its rows (the
    # server aims it at the sentinel); the groups after it name live rows
    launched = pallas_rows.launched_slots(n)
    assert launched == -(-n // ROW_GROUP) * ROW_GROUP
    ids[n:launched] = sentinel
    deltas = rng.normal(size=(n, 128)).astype(np.float32)
    out = np.asarray(scatter_add_rows(
        jnp.asarray(start), jnp.asarray(ids), jnp.asarray(deltas), sign=sign))
    expect = start.copy()
    expect[named[:n]] += np.float32(sign) * deltas
    np.testing.assert_array_equal(out[named[:n]], expect[named[:n]])
    np.testing.assert_array_equal(out[sentinel], start[sentinel])
    np.testing.assert_array_equal(out[named[n:]], start[named[n:]])
    np.testing.assert_array_equal(out, expect)


def test_scatter_add_refuses_more_delta_rows_than_ids(rng):
    table = jnp.zeros((ROWS, 128), jnp.float32)
    ids = jnp.zeros(ROW_GROUP, jnp.int32)
    with pytest.raises(ValueError, match="delta rows"):
        scatter_add_rows(table, ids, jnp.zeros((ROW_GROUP + 1, 128)))
    # no rows, no launch
    assert scatter_add_rows(table, ids, jnp.zeros((0, 128))) is table


def test_interpret_follows_the_tables_platform():
    assert pallas_rows.interpret_for("cpu") is True
    assert pallas_rows.interpret_for("tpu") is False
    with pytest.raises(ValueError, match="gpu"):
        pallas_rows.interpret_for("gpu")


def test_pallas_scatter_gate_predicate():
    """The gate reads the platform and the width, not the number of shards:
    a table sharded over chips runs the kernel on every shard's block
    (`ops/sharded_rows`; tested directly, since on the CPU mesh the backend
    clause alone decides)."""
    from multiverso_tpu.tables.matrix_table import _use_pallas_scatter

    assert _use_pallas_scatter("tpu", 1)
    assert _use_pallas_scatter("tpu", 8)
    assert not _use_pallas_scatter("cpu", 1)
    # any number of lane tiles, float32 or narrower, up to the width whose
    # row group (delta block twice, scratch once) still fits the VMEM budget
    for lanes in (128, 256, 384, 512, 4096):
        assert _use_pallas_scatter("tpu", 1, lanes, 4)
        assert _use_pallas_scatter("tpu", 4, lanes, 4)
    widest = pallas_rows.VMEM_BUDGET_BYTES // (3 * ROW_GROUP * 4)
    assert _use_pallas_scatter("tpu", 1, widest // 128 * 128, 4)
    assert not _use_pallas_scatter("tpu", 1, widest // 128 * 128 + 128, 4)
    assert not _use_pallas_scatter("tpu", 4, widest // 128 * 128 + 128, 4)
    assert _use_pallas_scatter("tpu", 1, widest // 128 * 128 + 128, 2)


@pytest.mark.parametrize("lanes", [128, 384])
@pytest.mark.parametrize("count", [0, 1, 7, 8, 9, 13, 24])
def test_scatter_add_with_a_live_count(count, lanes, rng, group_of_8):
    """A shard's launch: 24 id slots and delta rows (three groups of 8) of
    which the first ``count`` are live. The rows they name take their
    deltas, to the bit; every slot past the count issues no descriptor, so
    the row it names (a live row of the table, with a delta that is not
    zero: a shard has no scratch row to aim a pad slot at) keeps its bytes:
    no group at all, whole groups (one wait each way), and a group cut
    anywhere (a wait a slot on the same two semaphores): one row, one short
    of a group, a group, one past it."""
    import jax

    rows, slots = 48, 24
    table = rng.integers(-99, 99, (rows, lanes)).astype(np.float32)
    ids = rng.choice(rows, slots, replace=False).astype(np.int32)
    deltas = rng.integers(1, 9, (slots, lanes)).astype(np.float32)
    expect = table.copy()
    expect[ids[:count]] -= deltas[:count]
    out = jax.jit(lambda t, i, d, n: pallas_rows._scatter_add(
        t, i, d, True, -1.0, n))(table, ids, deltas,
                                 np.array([count], np.int32))
    np.testing.assert_array_equal(np.asarray(out), expect)


def test_matrix_server_multi_shard_add_correct(mv_env):
    """A table sharded over the 8-device CPU mesh takes the XLA scatter
    branch (the kernel compiles for the TPU) and row adds land correctly."""
    import multiverso_tpu as mv
    from multiverso_tpu.runtime.zoo import Zoo

    assert Zoo.instance().num_servers > 1  # the 8-device virtual mesh
    table = mv.create_table("matrix", 64, 16, np.float32)
    assert not table._server_table.plan.kernel
    assert table._server_table.plan.interpret is None
    ids = np.array([1, 9, 42], np.int32)
    table.add(np.full((3, 16), 2.0, np.float32), row_ids=ids)
    np.testing.assert_allclose(table.get(ids), np.full((3, 16), 2.0))


@pytest.mark.parametrize("cols", [129, 256, 300, 384, 512])
def test_row_kernels_at_widths_past_one_lane_tile(cols, rng, group_of_8):
    """A table of two, three or four lane tiles, rows reached through the
    tile view: unique live ids, sentinel padding up to the id bucket, a
    delta that ends inside the last row group (the masked tail) and inside
    the last lane tile (129 and 300 columns), both signs; the rows no id
    names, and the lanes past the delta's columns, keep their bytes; the
    one wait a group spans the ``(T, 8, 128)`` block. 43 delta rows in a
    bucket of 64 appear in no other test."""
    lanes = -(-cols // 128) * 128
    rows, n, bucket = 200, 43, 64  # 200 rows: 25 whole tiles of 8
    sentinel = rows - 1
    table = rng.integers(-99, 99, (rows, lanes)).astype(np.float32)
    ids = rng.choice(sentinel, n, replace=False).astype(np.int32)
    ids_p = jnp.asarray(np.concatenate(
        [ids, np.full(bucket - n, sentinel, np.int32)]))
    deltas = rng.integers(-9, 9, (n, cols)).astype(np.float32)

    got = np.asarray(gather_rows(jnp.asarray(table), ids_p))
    np.testing.assert_array_equal(got, table[np.asarray(ids_p)])
    assert pallas_rows.launched_slots(n) == 48
    for sign in (1.0, -1.0):
        expect = table.copy()
        expect[ids, :cols] += sign * deltas
        out = scatter_add_rows(jnp.asarray(table), ids_p,
                               jnp.asarray(deltas), sign=sign)
        np.testing.assert_array_equal(np.asarray(out), expect)


def test_wide_table_needs_whole_tiles_of_rows():
    """The tile view is a reshape of whole (8, 128) tiles: a wide table
    whose rows are no multiple of 8 is the caller's error (MatrixServer pads
    its rows), and so is a delta wider than the table."""
    ids = jnp.zeros(ROW_GROUP, jnp.int32)
    with pytest.raises(ValueError, match="multiple of 8"):
        scatter_add_rows(jnp.zeros((12, 256)), ids, jnp.zeros((1, 256)))
    with pytest.raises(ValueError, match="multiple of 8"):
        gather_rows(jnp.zeros((12, 256)), ids)
    with pytest.raises(ValueError, match="columns"):
        scatter_add_rows(jnp.zeros((16, 256)), ids, jnp.zeros((1, 300)))
    with pytest.raises(ValueError, match="lane tiles"):
        gather_rows(jnp.zeros((16, 200)), ids)


# -- `add_at_lanes`: single floats into lane-dense states, by row descriptors --
_LANE_ROWS = 300    # rows of 128 a state of the lane cases has


def _lane_states(rng):
    """Two states; the second is never negative (a rule may take its
    root)."""
    return [rng.integers(-99, 99, _LANE_ROWS * 128).astype(np.float32),
            rng.integers(0, 99, _LANE_ROWS * 128).astype(np.float32)]


def _lane_shared(rng):
    """300 sorted slots over 80 rows: keys that share a row, a key three
    slots name (the first the last slot of a chunk, the second and third
    in the next chunk), pads that repeat the last key, a slot that steps
    nothing among its row's live slots, a denormal, `-0.0` and `inf` in
    lanes nobody names."""
    group = pallas_rows.LANE_GROUP
    states = _lane_states(rng)
    keys = np.sort(rng.choice(9_000, 296, replace=False)).astype(np.int32)
    keys = np.sort(np.concatenate([keys, [keys[group - 1]] * 2,
                                   [keys[-1]] * 2])).astype(np.int32)
    assert keys[group - 1] == keys[group] == keys[group + 1]
    steps = np.ones(len(keys), bool)
    steps[-2:] = False          # pads: they repeat the last key
    steps[40] = False           # and one slot among live ones
    quiet = np.setdiff1d(np.arange(80 * 128), keys)[:3]
    states[0][quiet] = 1e-42, -0.0, np.inf
    return states, keys, steps


def _lane_whole_row(rng):
    """A row named by all 128 of its keys, among 60 keys elsewhere."""
    keys = np.sort(np.concatenate([
        5 * 128 + np.arange(128),
        rng.choice(np.setdiff1d(np.arange(4_000), 5 * 128 + np.arange(128)),
                   60, replace=False)])).astype(np.int32)
    return _lane_states(rng), keys, np.ones(len(keys), bool)


def _lane_chunks(rng):
    """One step's rows (100) hold 600 slots: five chunks fold onto one
    group, and rows have slots either side of a chunk's boundary."""
    keys = np.sort(rng.choice(100 * 128, 600, replace=False)).astype(np.int32)
    rows = keys >> 7
    assert len(np.unique(rows)) <= 128
    assert sum(rows[edge - 1] == rows[edge] for edge in range(128, 600, 128)) >= 2
    return _lane_states(rng), keys, np.ones(len(keys), bool)


def _lane_distinct(rng):
    """Every slot a row of its own: 256 keys, 256 rows walked, two steps
    of one chunk each."""
    keys = (np.sort(rng.choice(_LANE_ROWS, 256, replace=False)) * 128
            + rng.integers(0, 128, 256)).astype(np.int32)
    return _lane_states(rng), keys, np.ones(len(keys), bool)


def _lane_pads(rng):
    """400 slots (four steps of the grid) in 150 rows: a whole step, a
    last live step of 22 rows whose other sublanes are copies of the last
    row, two dead steps."""
    rows = np.sort(rng.choice(_LANE_ROWS, 150, replace=False))
    keys = np.concatenate([rows * 128 + 3, rng.choice(rows, 250) * 128
                           + rng.integers(4, 128, 250)])
    keys = np.sort(keys).astype(np.int32)
    assert len(np.unique(keys >> 7)) == 150
    return _lane_states(rng), keys, np.ones(len(keys), bool)


def _lane_one_row(rng):
    """One row walked: 130 slots (two chunks) of 90 keys of row 7."""
    keys = 7 * 128 + rng.choice(128, 90, replace=False)
    keys = np.sort(np.concatenate([keys, rng.choice(keys, 40)]))
    return _lane_states(rng), keys.astype(np.int32), np.ones(130, bool)


def _lane_scratch(rng):
    """A table's pads: slots aimed at a scratch key (the largest) that
    step nothing, in a row that live keys share."""
    scratch = 50 * 128 + 77
    live = np.concatenate([rng.choice(50 * 128, 150, replace=False),
                           50 * 128 + np.array([0, 76, 78, 127])])
    keys = np.sort(np.concatenate([live, [scratch] * 46])).astype(np.int32)
    return _lane_states(rng), keys, keys != scratch


def _lane_specials(rng):
    """Deltas that are NaN, +-inf, -0.0 and denormal, in one row beside an
    unnamed lane that holds a denormal; each reaches its own key's lane
    and no other."""
    states = _lane_states(rng)
    keys = np.sort(np.concatenate([
        9 * 128 + np.array([1, 2, 3, 4, 5, 6]),
        rng.choice(8 * 128, 40, replace=False)])).astype(np.int32)
    states[0][9 * 128 + 7] = 1e-42
    states[1][9 * 128 + 7] = 1e-42
    return states, keys, np.ones(len(keys), bool)


_SPECIALS = np.array([np.nan, np.inf, -np.inf, -0.0, 1e-42, -3e-40],
                     np.float32)
_LANE_CASES = {
    "shared_rows": _lane_shared, "whole_row": _lane_whole_row,
    "chunks": _lane_chunks, "distinct_rows": _lane_distinct,
    "pads": _lane_pads, "one_row": _lane_one_row,
    "scratch_row": _lane_scratch, "specials": _lane_specials}


def _callers_rule(olds, brought):
    """ONE delta for two states: `a += d`, `b = max(b, a_old * d)`."""
    (a, b), (d,) = olds, brought
    return a + d, jnp.maximum(b, a * d)


def _ftrl_rule(olds, brought):
    from multiverso_tpu.tables.ftrl_table import ftrl_step

    return ftrl_step(*olds, *brought, 0.1, 1.0, 1.0, 1.0)


@pytest.mark.parametrize("rule", [None, _callers_rule, _ftrl_rule],
                         ids=["no_rule", "callers_rule", "ftrl_rule"])
@pytest.mark.parametrize("case", _LANE_CASES)
def test_add_at_lanes_writes_what_xlas_path_writes(case, rule, rng):
    """`add_at_lanes`, interpreted, against XLA's path (its gathers, the
    rule a slot, its scatters at the first stepping slot of every key) bit
    for bit in every entry of both states, with no rule (every state takes
    its own delta, one float32 addition), under a caller's rule traced on
    the blocks the kernel read (one delta for two states) and under the
    FTRL table's; the count of rows walked is the keys' distinct rows.
    The cases are `_LANE_CASES`' docstrings."""
    import jax

    states, keys, steps = _LANE_CASES[case](rng)
    deltas = [rng.integers(1, 9, len(keys)).astype(np.float32)
              for _ in range(1 if rule else 2)]
    if case == "specials":
        for delta in deltas:
            delta[keys >> 7 == 9] = _SPECIALS

    @jax.jit
    def xla(states, keys, deltas, steps):
        first = steps & jnp.concatenate(
            [jnp.ones(1, bool), keys[1:] != keys[:-1]])
        olds = [s[keys] for s in states]
        news = (rule or pallas_rows._add_brought)(olds, deltas)
        at = jnp.where(first, keys, states[0].shape[0])
        return [s.at[at].set(new, mode="drop")
                for s, new in zip(states, news)]

    want = xla(tuple(states), keys, tuple(deltas), steps)
    got, walked = jax.jit(lambda s, k, d, m: pallas_rows.add_at_lanes(
        s, k, d, m, step=rule, interpret=True))(
            tuple(states), keys, tuple(deltas), steps)
    assert int(walked) == len(np.unique(keys >> 7))
    for before, expect, out in zip(states, want, got):
        np.testing.assert_array_equal(np.asarray(out).view(np.int32),
                                      np.asarray(expect).view(np.int32))
        assert not np.array_equal(np.asarray(out).view(np.int32),
                                  before.view(np.int32))


def test_add_at_lanes_refuses_what_it_cannot_serve():
    """No keys, more than the scalar prefetch holds, and without a rule a
    count of deltas that is not the states'."""
    state = jnp.zeros(1024, jnp.float32)
    some = jnp.zeros(8, jnp.int32)
    for keys in (jnp.zeros(0, jnp.int32),
                 jnp.zeros(pallas_rows.PREFETCH_SLOTS + 1, jnp.int32)):
        with pytest.raises(ValueError, match="add_at_lanes"):
            pallas_rows.add_at_lanes((state,), keys, (keys.astype(
                jnp.float32),), keys >= 0, interpret=True)
    with pytest.raises(ValueError, match="no rule"):
        pallas_rows.add_at_lanes((state, state), some,
                                 (some.astype(jnp.float32),), some >= 0,
                                 interpret=True)
