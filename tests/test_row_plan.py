"""The row plan's chooser alone (`tables/row_plan.py`): which device program
serves a table's row Add and row Get, decided from the platform, the mesh,
the lane width and the table's rule. No table is built: a case a program."""

import threading

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
