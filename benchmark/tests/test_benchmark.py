"""The benchmark's own checks, run by hand in the sandbox (not part of
tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import gzip
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, kernel_bytes, trace_reduce  # noqa: E402

BENCH = common.load_json("BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


# -- trace_reduce on the small recorded trace ---------------------------------

@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(ROOT, "benchmark", "fixtures",
                        "trace_bulk_rows_60ms.json.gz")
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           "trace_bulk_rows_60ms.expected.json")) as f:
        return trace, json.load(f)


def test_trace_reduce_is_exact_on_the_recorded_trace(recorded):
    trace, want = recorded
    got = trace_reduce.reduce(trace)
    assert got.window_s == want["window_s"]
    assert got.busy_s == pytest.approx(want["busy_s"], abs=1e-12)
    assert got.idle_share == pytest.approx(want["idle_share"], abs=1e-12)
    for name, (events, seconds) in want["ops"].items():
        assert got.op_seconds[name][0] == events
        assert got.op_seconds[name][1] == pytest.approx(seconds, abs=1e-12)
    assert {k: pytest.approx(v, abs=1e-12) for k, v in want["gaps"].items()} \
        == got.gaps
    # busy plus the gaps is the window, to the nanosecond
    assert got.busy_s + sum(got.gaps.values()) == pytest.approx(
        got.window_s, abs=1e-9)


def test_trace_reduce_by_hand():
    """Three operations, one overlap, one gap under a span, one under none."""
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 100, 300], ["scatter_add", 300, 300],
                ["fusion.1", 800, 100]]},
            {"name": "XLA Modules", "events": [["jit_f", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ["bench.window", 0, 1000], ["bench.op.add", 550, 300],
                ["SERVER_PROCESS_ADD_MSG", 610, 100], ["other", 0, 1000]]}]}]}
    got = trace_reduce.reduce(trace)
    assert got.window_s == pytest.approx(1000e-9)
    assert got.busy_s == pytest.approx(600e-9)       # 100-600 and 800-900
    assert got.idle_share == pytest.approx(0.4)
    assert got.op_seconds == {"fusion.1": [2, pytest.approx(400e-9)],
                              "scatter_add": [1, pytest.approx(300e-9)]}
    # 0-100 and 900-1000 lie under no span but the window's own; 600-800
    # lies in bench.op.add, and its middle, 700, in the shorter monitor span
    assert got.gaps == {"(no span)": pytest.approx(200e-9),
                        "SERVER_PROCESS_ADD_MSG": pytest.approx(200e-9)}
    assert got.ops_matching("scatter") == [
        ("scatter_add", 1, pytest.approx(300e-9))]
    assert got.breakdown()["device_ops"][0][0] == "fusion.1"


def test_roofline_counts_the_rows_the_adds_name(recorded):
    trace, _ = recorded
    roofline = common.load_module("layers", "row_scatter_roofline")

    class FakeRun:
        peaks = {"hbm_bytes_per_s": 819e9}
        # seven Adds of 100,000 rows of 128 columns in the recorded 60 ms
        result = {"add_rows": 700_000, "row_cols": 128}
    FakeRun.trace = trace_reduce.reduce(trace)
    (slots, lanes, events, seconds), = roofline.launches(FakeRun)
    assert (slots, lanes, events) == (131072, 128, 7)
    # the 31,072 sentinel slots of each launch are moved and not counted
    assert roofline.read(FakeRun) == pytest.approx(
        100 * 7 * 153_600_000 / seconds / 819e9)
    FakeRun.peaks = {"hbm_bytes_per_s": 8e9}      # a chip 100 times slower
    with pytest.raises(ValueError, match="more than the chip can move"):
        roofline.read(FakeRun)
    FakeRun.peaks = {"hbm_bytes_per_s": 819e9}
    FakeRun.result = {"add_rows": 8 * 131072, "row_cols": 128}
    with pytest.raises(ValueError, match="part of the work is not in the"):
        roofline.read(FakeRun)       # more rows named than slots launched


def test_roofline_refuses_a_scatter_event_it_cannot_read():
    roofline = common.load_module("layers", "row_scatter_roofline")
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%_scatter_add_call.1 = f32[8,128] custom-call(renamed)",
             0, 100]]}]}]}

    class FakeRun:
        peaks = {"hbm_bytes_per_s": 819e9}
        result = {"add_rows": 8, "row_cols": 128}
    FakeRun.trace = trace_reduce.reduce(trace)
    with pytest.raises(ValueError, match="shapes cannot be read"):
        roofline.read(FakeRun)


def test_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


# -- bytes, peaks ----------------------------------------------------------------

def test_row_scatter_bytes_against_hand_worked_shapes():
    # 1,024 rows of 128 float32 columns: 512 B a row, read + written + delta
    assert kernel_bytes.row_scatter_bytes(1024, 128) == 3 * 1024 * 512
    assert kernel_bytes.row_scatter_bytes(1024, 128) == 1_572_864
    # the bulk cell's Add: 100,000 rows -> 153,600,000 bytes, whatever the
    # launch's bucket; a 50-column table moves 50 useful columns a row
    assert kernel_bytes.row_scatter_bytes(100_000, 128) == 153_600_000
    assert kernel_bytes.row_scatter_bytes(100_000, 50) == 60_000_000
    # 153.6 MB in 3.4 ms is 45.2 GB/s, 5.52% of 819 GB/s
    assert kernel_bytes.share_of_peak(153_600_000, 3.4e-3, 819e9) == \
        pytest.approx(5.5161, abs=1e-3)


def test_share_over_the_peak_raises():
    with pytest.raises(ValueError, match="more than the chip can move"):
        kernel_bytes.share_of_peak(201_326_592, 0.2e-3, 819e9)


def test_unknown_device_kind_raises():
    assert common.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        common.peaks_for("TPU v9 imaginary")


# -- the harness on the CPU --------------------------------------------------------

def _run(*args, prelude=""):
    """benchmark/run.py in a process of its own; the last line as JSON."""
    code = (prelude + "\nimport sys; from benchmark import run; "
            f"sys.exit(run.main({list(args)!r}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          stdout=subprocess.PIPE, text=True, timeout=600)
    lines = done.stdout.strip().splitlines()
    return done.returncode, lines


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_a_well_formed_line(cell):
    code, lines = _run("--workload", cell, "--seed", str(2**31 + 7),
                       "--seconds", "1", "--rehearse")
    assert code == 0, lines[-5:]
    last = json.loads(lines[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    # a rehearsal prints counts only: no name of a benchmark metric
    assert last["metrics"] == {}
    names = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    assert not names & set(json.dumps(last).replace('"', " ").split())
    compared = [json.loads(x) for x in lines if x.startswith('{"compared"')]
    assert len(compared) >= 4 and all("limit" in c for c in compared)


@pytest.mark.parametrize("cell", CELLS)
def test_without_a_tpu_the_run_exits_non_zero(cell):
    code, lines = _run("--workload", cell, "--seed", "1", "--seconds", "1",
                       "--trace", "0")
    assert code != 0
    assert not any(x.startswith('{"correct"') for x in lines)


# the timed path broken underneath: `correct` must come out false
BREAKS = {
    # a delta altered where the in-process worker hands it to the table
    "emb128.bulk-rows": """
from multiverso_tpu.tables import matrix_table as mt
_orig = mt.MatrixWorker.add_device_async
def _altered(self, values, row_ids, option=None):
    _altered.calls += 1
    if _altered.calls == 9:
        values = values.at[0, 0].add(1.0 / 64)
    return _orig(self, values, row_ids, option)
_altered.calls = 0
mt.MatrixWorker.add_device_async = _altered
""",
    # an Add that the server acknowledges and does not apply
    "emb128.remote-workers": """
from multiverso_tpu.tables import matrix_table as mt
_orig = mt.MatrixServer.process_add
def _dropping(self, request):
    _dropping.calls += 1
    if _dropping.calls == 40:
        return None
    return _orig(self, request)
_dropping.calls = 0
mt.MatrixServer.process_add = _dropping
""",
}


@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell):
    code, lines = _run("--workload", cell, "--seed", "3", "--seconds", "1",
                       "--rehearse", prelude=BREAKS[cell])
    assert code == 0, lines[-5:]
    last = json.loads(lines[-1])
    assert last["correct"] is False
    compared = [json.loads(x) for x in lines if x.startswith('{"compared"')]
    assert any(not c["ok"] for c in compared)


# -- the control: the cell's timed path in the next lower precision -------------

@pytest.mark.parametrize("cell,seed,lower", [
    (CELLS[0], 1, "table"), (CELLS[0], 2**31 + 3, "delta"),
    (CELLS[-1], 2, "delta"), (CELLS[-1], 3, "table")])
def test_bfloat16_control_is_not_correct(cell, seed, lower):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import control
    sound = control.run_control(cell, seed, seconds=1, dtype="float32",
                                lower=lower, rehearse=True)
    assert sound["correct"] is True, sound
    report = control.run_control(cell, seed, seconds=1, lower=lower,
                                 rehearse=True)
    # a run that gave no result (the wire refuses a bfloat16 table) failed
    assert report["correct"] is not True, report
    assert report["correct"] is None or \
        any(not c["ok"] for c in report["compared"])
