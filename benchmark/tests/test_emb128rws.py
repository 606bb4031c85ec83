"""The harness's tests for the cell `emb128rws.bulk-updates`, run by hand
like test_benchmark.py, test_w2v300.py and test_emb128x4.py (whose tables of
breaks and controls name other cells and may not be edited by the PR that
adds a cell):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_emb128rws.py -q -p no:cacheprovider

Rehearsals on the CPU (a 20,000-row table): the cell as it is (on the CPU
the table's rows take XLA's scatter) and with the gate open, so that the
stateful Add runs the row kernel, interpreted, behind its state step:
`correct` has to come out true on both, false where the timed path is broken
underneath (an Add's gradient altered by one unit, an Add applied twice),
and false under the delta control. And the reference itself: its float32
replay against the same rule in float64."""

import json
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CELL = "emb128rws.bulk-updates"
COMPARED = ("replay_quiet_mismatch", "replay_w_error", "replay_s_mismatch",
            "window_get_error", "final_sample_w_error",
            "final_sample_s_mismatch", "unnamed_checksum_mismatch_columns",
            "unnamed_state_nonzero")

# the row kernel behind the state step, as on the chip
KERNEL = """
from multiverso_tpu.tables import matrix_table as mt
mt._use_pallas_scatter = lambda platform, num_shards, *width: num_shards == 1
"""

# one column of one gradient altered by 16 units where the in-process
# worker hands it to the table (the 7th device Add: in the window). In the
# window the comparison sees the rows of its seeded sample, hot and cold,
# not every row of every Add (a replay of everything would take minutes):
# a fault in one row outside the sample is not seen, one in a column is
ALTERED = """
from multiverso_tpu.tables import matrix_table as mt
_orig = mt.MatrixWorker.add_device_async
def _altered(self, values, row_ids, option=None):
    _altered.calls += 1
    if _altered.calls == 7:
        values = values.at[:, 127].add(16.0 / 1024)
    return _orig(self, values, row_ids, option)
_altered.calls = 0
mt.MatrixWorker.add_device_async = _altered
"""

# one Add of the window applied twice: the order the driver recorded is no
# longer the order the table took
TWICE = """
from multiverso_tpu.tables import matrix_table as mt
_orig = mt.MatrixWorker.add_device_async
def _twice(self, values, row_ids, option=None):
    _twice.calls += 1
    if _twice.calls == 9:
        self.wait(_orig(self, values, row_ids, option))
    return _orig(self, values, row_ids, option)
_twice.calls = 0
mt.MatrixWorker.add_device_async = _twice
"""


def _run(*args, prelude=""):
    code = (prelude + "\nimport sys; from benchmark import run; "
            f"sys.exit(run.main({list(args)!r}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          stdout=subprocess.PIPE, text=True, timeout=900)
    return done.returncode, done.stdout.strip().splitlines()


def _rehearse(prelude, seed):
    code, lines = _run("--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--rehearse", prelude=prelude)
    assert code == 0, lines[-5:]
    compared = [json.loads(x) for x in lines if x.startswith('{"compared"')]
    return compared, json.loads(lines[-1]), lines


def test_rehearsal_ends_correct_on_both_programs():
    for prelude, program in (("", "XLA scatter"), (KERNEL, "pallas")):
        compared, last, lines = _rehearse(prelude, 2**31 + 33)
        assert [c["compared"] for c in compared] == list(COMPARED)
        assert all(c["ok"] for c in compared), compared
        assert last["correct"] is True and last["failed"] == 0
        created = [x for x in lines if "MatrixTable 20000x128 on 1" in x]
        assert created and program in created[0], created
        assert "rowwise_adagrad updater: state step" in created[0]
        assert last["counts"]["rows_stepped"] > 100
        assert last["counts"]["rows_unnamed"] > 10_000


def test_a_broken_timed_path_is_not_correct():
    for prelude in (ALTERED, TWICE):
        compared, last, _ = _rehearse(prelude, 2**31 + 34)
        assert last["correct"] is False, prelude
        failed = {c["compared"] for c in compared if not c["ok"]}
        assert failed & {"window_get_error", "final_sample_w_error",
                         "final_sample_s_mismatch"}, compared


def test_bfloat16_delta_control_is_not_correct():
    import control
    sound = control.run_control(CELL, 2**31 + 35, seconds=1, dtype="float32",
                                lower="delta", rehearse=True)
    assert sound["correct"] is True, sound
    report = control.run_control(CELL, 2**31 + 35, seconds=1, lower="delta",
                                 rehearse=True)
    assert report["correct"] is False, report
    assert any(not c["ok"] for c in report["compared"])


def test_reference_in_float32_tracks_a_float64_replay():
    """2,000 Adds of 64 rows over a hot head of 256: rows that took over a
    thousand steps, float32 against float64: the table's values inside
    half of the tolerance the reference allows another float32
    implementation, the state inside float32's own rounding of `s + mean`
    (half a unit a step; the mean itself is exact in both)."""
    from benchmark import common
    ref = common.load_module("reference", "dlrm-rwsadagrad-emb128")
    rows, cols, seed = 4096, 128, 2**31 + 36
    rng = np.random.default_rng(seed)
    replays = [ref.Replay(np.arange(rows), cols, seed, 0.01, 1e-10, dtype)
               for dtype in (np.float32, np.float64)]
    for _ in range(2000):
        ids = np.concatenate([rng.choice(256, 48, replace=False),
                              256 + rng.choice(rows - 256, 16,
                                               replace=False)])
        grad = ref.to_float(ref.grad_k(rng, 64, cols))
        for replay in replays:
            replay.add(replay.plan(ids), grad)
    single, double = replays
    assert single.steps.max() > 300
    assert ref.w_error(single.w, double.w, double.steps) <= 0.5
    assert (np.abs(single.s - double.s)
            <= double.steps * 2.0 ** -24 * double.s).all()
