"""The harness's tests for the cell `w2v300.block-rows`, run by hand like
test_benchmark.py (whose per-cell tables of breaks and controls name the
`emb128` cells only and may not be edited by the PR that adds a cell):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_w2v300.py -q -p no:cacheprovider

A timed path broken underneath and the delta control, both as rehearsals on
the CPU: `correct` has to come out false."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CELL = "w2v300.block-rows"

# one element of one delta altered where the in-process worker hands it to
# the second table (the 13th device Add: after the warm-up blocks)
BREAK = """
from multiverso_tpu.tables import matrix_table as mt
_orig = mt.MatrixWorker.add_device_async
def _altered(self, values, row_ids, option=None):
    _altered.calls += 1
    if _altered.calls == 13:
        values = values.at[0, 299].add(1.0 / 64)
    return _orig(self, values, row_ids, option)
_altered.calls = 0
mt.MatrixWorker.add_device_async = _altered
"""


def _run(*args, prelude=""):
    code = (prelude + "\nimport sys; from benchmark import run; "
            f"sys.exit(run.main({list(args)!r}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          stdout=subprocess.PIPE, text=True, timeout=600)
    return done.returncode, done.stdout.strip().splitlines()


def test_rehearsal_compares_both_tables():
    code, lines = _run("--workload", CELL, "--seed", str(2**31 + 9),
                       "--seconds", "1", "--rehearse")
    assert code == 0, lines[-5:]
    compared = [json.loads(x) for x in lines if x.startswith('{"compared"')]
    assert sorted(c["compared"] for c in compared) == sorted(
        f"{name}.{table}" for table in ("input", "output")
        for name in ("replay_mismatch", "window_get_mismatch",
                     "final_sample_mismatch", "checksum_mismatch_columns"))
    assert all(c["ok"] and c["limit"] == 0 for c in compared)
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    counts = last["counts"]
    assert counts["ops"] == 4 * counts["blocks"] == last["attempted"]
    assert counts["rows"] == 2 * counts["add_rows"] == 2 * counts["get_rows"]


def test_a_broken_timed_path_is_not_correct():
    code, lines = _run("--workload", CELL, "--seed", "3", "--seconds", "1",
                       "--rehearse", prelude=BREAK)
    assert code == 0, lines[-5:]
    assert json.loads(lines[-1])["correct"] is False
    compared = [json.loads(x) for x in lines if x.startswith('{"compared"')]
    wrong = {c["compared"] for c in compared if not c["ok"]}
    # the altered Add went to one table: the other's numbers stay 0
    assert wrong and all(name.endswith(".input") for name in wrong) or \
        all(name.endswith(".output") for name in wrong), wrong


def test_bfloat16_delta_control_is_not_correct():
    import control
    sound = control.run_control(CELL, 2**31 + 5, seconds=1, dtype="float32",
                                lower="delta", rehearse=True)
    assert sound["correct"] is True, sound
    report = control.run_control(CELL, 2**31 + 5, seconds=1, lower="delta",
                                 rehearse=True)
    assert report["correct"] is False, report
    assert any(not c["ok"] for c in report["compared"])
