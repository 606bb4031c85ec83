"""The harness's tests for the cell `dlrm26.step-rows`, run by hand like
test_benchmark.py (whose per-cell tables of breaks and controls name the
`emb128` cells only and may not be edited by the PR that adds a cell):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_dlrm26.py -q -p no:cacheprovider

The rehearsal, a timed path broken underneath, a base off by one row and the
delta control, all as rehearsals on the CPU: `correct` has to come out true
for the first and false for the rest. (`tests/test_group_table.py`, tier-1,
keeps the rehearsal and the wrong base.)"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CELL = "dlrm26.step-rows"
COMPARISONS = ["replay_mismatch", "window_get_mismatch",
               "final_sample_mismatch", "member_edge_mismatch",
               "small_member_mismatch", "checksum_mismatch_columns"]

# one element of one delta altered where the in-process worker hands it to
# the group (the 12th group Add: after the three warm-up pairs and the two
# replay steps, the seventh of the window)
BREAK = """
from multiverso_tpu.tables import group_table as gt
_orig = gt.MatrixGroupWorker.add_device_async
def _altered(self, values, ids, lengths=None, option=None):
    _altered.calls += 1
    if _altered.calls == 12:
        values = values.at[0, 127].add(1.0 / 64)
    return _orig(self, values, ids, lengths, option)
_altered.calls = 0
gt.MatrixGroupWorker.add_device_async = _altered
"""

# member 7's base one row too far in the group's own table of bases
WRONG_BASE = """
from multiverso_tpu.tables import group_table as gt
_init = gt.MatrixGroupWorker.__init__
def _shifted(self, *args, **kwargs):
    _init(self, *args, **kwargs)
    self._bases[7] += 1
gt.MatrixGroupWorker.__init__ = _shifted
"""


def _run(*args, prelude=""):
    code = (prelude + "\nimport sys; from benchmark import run; "
            f"sys.exit(run.main({list(args)!r}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          stdout=subprocess.PIPE, text=True, timeout=600)
    lines = done.stdout.strip().splitlines()
    compared = {c["compared"]: c for c in (
        json.loads(x) for x in lines if x.startswith('{"compared"'))}
    return done.returncode, lines, compared


def test_rehearsal_compares_the_slab_and_the_members():
    code, lines, compared = _run("--workload", CELL, "--seed",
                                 str(2**31 + 9), "--seconds", "1",
                                 "--rehearse")
    assert code == 0, lines[-5:]
    assert sorted(compared) == sorted(COMPARISONS)
    assert all(c["ok"] and c["limit"] == 0 for c in compared.values())
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    counts = last["counts"]
    assert counts["tables"] == 26
    assert counts["ops"] == 2 * counts["adds"] == last["attempted"]
    assert counts["rows"] == 2 * counts["add_rows"] == 2 * counts["get_rows"]


def test_a_broken_timed_path_is_not_correct():
    code, lines, compared = _run("--workload", CELL, "--seed", "3",
                                 "--seconds", "1", "--rehearse",
                                 prelude=BREAK)
    assert code == 0, lines[-5:]
    assert json.loads(lines[-1])["correct"] is False
    # one element of the first table's first row of the step: its column
    assert not compared["checksum_mismatch_columns"]["ok"]
    assert compared["checksum_mismatch_columns"]["value"] == 1


def test_a_wrong_base_is_not_correct():
    code, lines, compared = _run("--workload", CELL, "--seed", "4",
                                 "--seconds", "1", "--rehearse",
                                 prelude=WRONG_BASE)
    assert code == 0, lines[-5:]
    assert json.loads(lines[-1])["correct"] is False
    assert not compared["replay_mismatch"]["ok"]
    assert compared["checksum_mismatch_columns"]["ok"]
    named = [json.loads(x) for x in lines
             if x.startswith('{"members_wrong"')]
    # member 7, and member 8 where a step named member 7's last row
    assert named and {7} <= set().union(
        *named[0]["members_wrong"].values()) <= {7, 8}


def test_bfloat16_delta_control_is_not_correct():
    import control
    sound = control.run_control(CELL, 2**31 + 5, seconds=1, dtype="float32",
                                lower="delta", rehearse=True)
    assert sound["correct"] is True, sound
    report = control.run_control(CELL, 2**31 + 5, seconds=1, lower="delta",
                                 rehearse=True)
    assert report["correct"] is False, report
    assert any(not c["ok"] for c in report["compared"])
