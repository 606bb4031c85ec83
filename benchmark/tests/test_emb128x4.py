"""The harness's tests for the cell `emb128x4.bulk-rows`, run by hand like
test_benchmark.py and test_w2v300.py (whose tables of breaks and controls
name other cells and may not be edited by the PR that adds a cell):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_emb128x4.py -q -p no:cacheprovider

Rehearsals on the CPU (four virtual devices, a 20,000-row table): the cell
as it is (on the CPU the table takes XLA's partitioned programs), and with
the gate open, so that the routed programs of a table sharded over chips
run, their kernel interpreted: `correct` has to come out true on both, false
where a timed path is broken underneath, and false under the delta
control."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CELL = "emb128x4.bulk-rows"

# the row kernel on every shard, as on the chip
ROUTED = """
from multiverso_tpu.tables import matrix_table as mt
mt._use_pallas_scatter = lambda platform, num_shards, *width: True
"""

# one element of one delta altered where the in-process worker hands it to
# the table (the 7th device Add: after the warm-up pairs)
BREAK = ROUTED + """
_orig = mt.MatrixWorker.add_device_async
def _altered(self, values, row_ids, option=None):
    _altered.calls += 1
    if _altered.calls == 7:
        values = values.at[0, 127].add(1.0 / 64)
    return _orig(self, values, row_ids, option)
_altered.calls = 0
mt.MatrixWorker.add_device_async = _altered
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


def test_rehearsal_ends_correct_on_four_devices():
    for prelude, program in (("", "XLA scatter"), (ROUTED, "ids routed")):
        compared, last, lines = _rehearse(prelude, 2**31 + 30)
        assert sorted(c["compared"] for c in compared) == sorted(
            ("replay_mismatch", "window_get_mismatch",
             "final_sample_mismatch", "checksum_mismatch_columns"))
        assert all(c["ok"] and c["value"] == 0 and c["limit"] == 0
                   for c in compared)
        assert last["correct"] is True and last["failed"] == 0
        assert last["device"]["count"] == 4
        created = [x for x in lines if "MatrixTable 20000x128 on 4" in x]
        assert created and program in created[0], created


def test_a_broken_timed_path_is_not_correct():
    compared, last, _ = _rehearse(BREAK, 3)
    assert last["correct"] is False
    assert any(not c["ok"] for c in compared)


def test_bfloat16_delta_control_is_not_correct():
    import control
    sound = control.run_control(CELL, 2**31 + 5, seconds=1, dtype="float32",
                                lower="delta", rehearse=True)
    assert sound["correct"] is True, sound
    report = control.run_control(CELL, 2**31 + 5, seconds=1, lower="delta",
                                 rehearse=True)
    assert report["correct"] is False, report
    assert any(not c["ok"] for c in report["compared"])
