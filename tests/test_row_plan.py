"""The row plan's chooser alone (`tables/row_plan.py`): which device program
serves a table's row Add and row Get, decided from the platform, the mesh,
the lane width and the table's rule. No table is built: a case a program."""

import threading
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.tables import matrix_table
from multiverso_tpu.tables.device_ids import LaunchIds
from multiverso_tpu.tables.row_plan import row_plan
from multiverso_tpu.updaters import get_updater


def _mesh(devices):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:devices]), ("server",))


# name: (updater, devices, the mesh spans processes, gate (None: the real
# one), platform, lanes) -> (path, unique_ids, routed, longer_delta, merge,
# interpret, a phrase of the creation log line)
_MATRIX = {
    "linear, one chip, kernel compiled": (
        ("default", 1, False, None, "tpu", 128),
        ("pallas", True, (), True, True, False, "kernel, compiled")),
    "sgd, one device, kernel interpreted": (
        ("sgd", 1, False, True, "cpu", 384),
        ("pallas", True, (), True, True, True, "kernel, interpreted")),
    "linear, no kernel on this platform": (
        ("default", 1, False, None, "cpu", 128),
        ("xla", False, (), True, True, None, "compiles for tpu only")),
    "linear, a row group past VMEM": (
        ("default", 1, False, None, "tpu", 8192),
        ("xla", False, (), True, True, None, "8192 lanes is past")),
    "linear, four chips, routed kernels": (
        ("sgd", 4, False, True, "tpu", 128),
        ("pallas", True, ("get", "add"), False, True, False,
         "block of 250 rows, ids routed")),
    "linear, a mesh over processes": (
        ("default", 4, True, True, "tpu", 128),
        ("xla", False, (), True, True, None, "the mesh spans processes")),
    "one chip over processes keeps the kernel": (
        ("default", 1, True, True, "tpu", 128),
        ("pallas", True, (), True, True, False, "kernel, compiled")),
    "table-shaped state, one chip": (
        ("momentum_sgd", 1, False, True, "tpu", 128),
        ("xla", True, (), False, False, False, "XLA's row update")),
    "table-shaped state, gate shut, eight devices": (
        ("adagrad", 8, False, None, "cpu", 128),
        ("xla", True, (), False, False, None, "compiles for tpu only")),
    "row state, one chip, step in front of the kernel": (
        ("rowwise_adagrad", 1, False, True, "cpu", 128),
        ("pallas", True, (), False, False, True,
         "state step, then that scatter-add")),
    "row state, gate shut": (
        ("rowwise_adagrad", 1, False, None, "cpu", 128),
        ("xla", True, (), False, False, None, "state step")),
    "row state, four chips: the Get routed, the Add XLA's": (
        ("rowwise_adagrad", 4, False, True, "tpu", 128),
        ("xla", True, ("get",), False, False, False,
         "takes XLA's partitioned row update")),
}


@pytest.mark.parametrize("case", list(_MATRIX))
def test_the_matrix_tables_plan_by_program(case, monkeypatch):
    (name, devices, spans, gate, platform, lanes), want = _MATRIX[case]
    if gate is not None:
        monkeypatch.setattr(matrix_table, "_use_pallas_scatter",
                            lambda platform, num_shards, *width: gate)
    updater = get_updater(np.float32, name)
    plan = row_plan(_mesh(devices), spans, dtype=np.float32, lanes=lanes,
                    updater=updater, cols=lanes - 28, padded_rows=1000,
                    sentinel=999, platform=platform)
    assert (plan.path, plan.unique_ids, plan.routed, plan.longer_delta,
            plan.merge, plan.interpret) == want[:6]
    assert plan.kernel == (plan.interpret is not None)
    assert want[6] in plan.why, plan.why
    # the programs are there, whichever they are; the records' arithmetic
    assert all(callable(f) for f in (
        plan.add, plan.get, plan.whole_update, plan.row_apply,
        plan.scatter_add, plan.device_delta))
    assert (plan.slot_bytes, plan.arrays, plan.launched(257)) == (
        4 * lanes, 1, 512)
    stateful = name not in ("default", "sgd")
    assert plan.state_ops == (("add",) if stateful else ())
    assert plan.updater == (name if stateful else "")
    assert plan.state_slot_bytes == {
        "rowwise_adagrad": 4, "momentum_sgd": 4 * lanes,
        "adagrad": 4 * lanes}.get(name, 0)
    assert (plan.stateful_adds is not None) == stateful


@pytest.mark.parametrize("case,devices,platform,want", [
    ("one device, the lane kernel", 1, "cpu", ("pallas", True)),
    ("one chip, compiled", 1, "tpu", ("pallas", False)),
    ("a mesh keeps XLA's scatters", 4, "cpu", ("xla", None)),
    ("a platform the kernels do not serve", 1, "gpu", ("xla", None)),
])
def test_the_ftrl_tables_plan(case, devices, platform, want, monkeypatch):
    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables import row_plan as module

    loading, filled, seen = threading.Event(), threading.Event(), []

    def load():
        loading.set()
        assert filled.wait(10)
        return pallas_rows

    def fill():
        # the kernel's module loads UNDER the fill, on a thread of its own
        if want[1] is not None:
            assert loading.wait(10)
        seen.append(sorted(t.name for t in threading.enumerate()
                           if "kernel" in t.name))
        filled.set()

    monkeypatch.setattr(module, "_row_kernel", load)
    plan = row_plan(_mesh(devices), False, keyed=(len, len), fill=fill,
                    platform=platform)
    # that thread is gone when the plan answers; a table the kernel does not
    # serve starts none and loads nothing
    assert [[name.rstrip("_0") for name in names] for names in seen] == [
        ["ftrl-row-kernel-import"] if want[1] is not None else []]
    assert loading.is_set() == (want[1] is not None)
    assert not [t for t in threading.enumerate() if "kernel" in t.name]
    assert (plan.path, plan.interpret) == want
    assert (plan.unique_ids, plan.routed, plan.longer_delta, plan.merge) == (
        False, (), True, False)
    assert (plan.updater, plan.state_ops, plan.stateful_adds) == (
        "ftrl", ("add", "get"), None)
    assert (plan.slot_bytes, plan.state_slot_bytes, plan.arrays) == (8, 8, 2)
    if want[0] == "pallas":
        assert plan.group == pallas_rows.LANE_GROUP
        # the creation log line says where the step runs (PR 49)
        assert "stepped in VMEM" in plan.why
        assert "Pallas row kernel" in plan.why
    else:
        assert "step on XLA's gathers" in plan.why
        assert "XLA scatter" in plan.why
    # a launch by bucket: past the kernel's scalar prefetch XLA's scatters
    # serve the Add, and the counters and the program's `rows` say which
    served = []
    plan = row_plan(_mesh(devices), False, fill=fill, platform=platform,
                    keyed=(len, lambda z, n, ids, grad, live, rows:
                           served.append(rows) or (z, n, rows)))
    counts = {}
    for bucket in (pallas_rows.PREFETCH_SLOTS, 2 * pallas_rows.PREFETCH_SLOTS):
        before = {path: Dashboard.counter_value("ROW_LAUNCH_%s_ADD" % path)
                  for path in ("PALLAS", "XLA")}
        took = LaunchIds(None, bucket, None, 0, 0, np.zeros(5, np.int32))
        # the program's third result, the rows its kernel walked, is not
        # the table's state
        assert plan.launch_add((1, 2), took, np.zeros(5, np.float32), 128,
                               "dispatcher") == (1, 2)
        counts[bucket] = [
            Dashboard.counter_value("ROW_LAUNCH_%s_ADD" % path) - was
            for path, was in before.items()]
    assert served == [want[1], None]
    assert list(counts.values()) == [
        [1, 0] if want[0] == "pallas" else [0, 1], [0, 1]]


class _RoutedTable(matrix_table.DeviceIdsServer):
    """What a routed plan asks of its table: the form of an op's id array
    and the rows and sentinel its padding aims at."""

    padded_rows, sentinel_row = 1000, 999

    def __init__(self):
        self._init_device_ids(self.sentinel_row, one_device=False)

    def _get_bucket(self, n, ensure_pad):
        return max(matrix_table._next_pow2(n + 1 if ensure_pad else n), 8)


@pytest.mark.parametrize("n", [100, 64])
@pytest.mark.parametrize("order", [("add", "get"), ("get", "add")])
def test_the_routed_ops_ids_go_up_in_one_form(order, n, monkeypatch):
    """The operands a routed plan hands its programs: an Add's ids and a
    Get's in ONE form, the slots the Get gathers (the ids named, ids past
    the table, the sentinel last) on the mesh's first device; the counts by
    shard and a segment's capacity are the op's own (a Get's with the
    sentinel at its owner, over its slots; an Add's of the ids named), and
    the second op of a pair launches on the first's array with the counts
    a plan that kept nothing works out. 64 ids: the device Get's bucket
    keeps a sentinel slot, 128 against the Add's 64, and both go up."""
    from multiverso_tpu.ops import sharded_rows
    from multiverso_tpu.tables.device_ids import live_slots

    monkeypatch.setattr(matrix_table, "_use_pallas_scatter",
                        lambda platform, num_shards, *width: True)
    plans = [row_plan(_mesh(4), False, dtype=np.float32, lanes=128,
                      updater=get_updater(np.float32, "default"), cols=100,
                      padded_rows=1000, sentinel=999, platform="tpu")
             for _ in range(2)]
    assert plans[0]._shards is plans[1]._shards and plans[0]._kept is None
    table = _RoutedTable()
    ids = np.random.default_rng(n).choice(990, n, replace=False).astype(
        np.int32)
    form = {"add": {"rows": n}, "get": {"ensure_pad": True}}
    kept = Dashboard.counter_value("ROW_IDS_KEPT")
    # each op's TABLE_ROW_PREP section, which is told the bytes that went up
    preps = [SimpleNamespace(bytes=0) for _ in range(3)]
    first, second = (
        plans[0].launch_ids(table, ids, op, prep, **form[op])
        for op, prep in zip(order, preps))
    alone = plans[1].launch_ids(table, ids, order[1], preps[2],
                                **form[order[1]])
    hit = n == 100
    assert Dashboard.counter_value("ROW_IDS_KEPT") == kept + hit
    assert (second.ids is first.ids) == hit
    assert [prep.bytes for prep in preps] == [
        first.nbytes, 0 if hit else second.nbytes, alone.nbytes]
    named = np.bincount(ids // 250, minlength=4)
    for took, op in ((first, order[0]), (second, order[1]),
                     (alone, order[1])):
        bucket = (128 if op == "get" else 64) if n == 64 else 128
        slots = live_slots(n, bucket)
        assert took.bucket == bucket and not took.counted
        assert took.ids.shape == (4 * slots,)
        up = np.asarray(took.ids.addressable_shards[0].data)
        np.testing.assert_array_equal(up[:n], ids)
        # (64 ids fill an Add's bucket of 64: no pad, and no sentinel)
        assert slots == n or ((up[n:-1] == 1000).all() and up[-1] == 999)
        np.testing.assert_array_equal(took.host, ids)
        assert not np.shares_memory(took.host, ids)
        counts = named + (np.arange(4) == 3) * (op == "get" and slots > n)
        np.testing.assert_array_equal(took.counts, counts)
        assert took.capacity == sharded_rows.shard_capacity(
            counts.max(), slots if op == "get" else n, 4)
    assert second.capacity == alone.capacity
    # what is kept holds an Add's counts, whichever op sent it up
    np.testing.assert_array_equal(plans[0]._kept.took.counts, named)
