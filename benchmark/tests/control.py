#!/usr/bin/env python
"""The control of `correct`: the cell itself, run.py and its timed path, in
the next lower precision (bfloat16 for a float32 table). Two places to lower
it: `--lower table` keeps the configuration's table in bfloat16; `--lower
delta` rounds every Add's delta to bfloat16 where the server's table takes
it (the step that would halve a wire's or a kernel's bytes). Every run has to
come out as not correct. On the chip, at the cell's own sizes, each seed a
process of its own:

    python benchmark/tests/control.py --workload emb128.bulk-rows \
        --lower table --seconds 3 --seeds 1 2 3

Prints, for each seed, every number `correct` compared beside its limit and
the run's `correct`; exits 0 only if every run read false (a run that crashed
gave no number and has failed too). test_benchmark.py keeps it as a
rehearsal on the CPU."""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

LOWER = {
    # the configuration file as the run loads it, with one key changed
    "table": """
from benchmark import common
_load = common.load_json
def _lower(*parts):
    loaded = _load(*parts)
    if isinstance(loaded, dict) and "table" in loaded:
        loaded["table"]["dtype"] = {dtype!r}
    return loaded
common.load_json = _lower
""",
    # the delta of every row Add, rounded as it reaches the server's table
    "delta": """
import jax.numpy as jnp
import numpy as np
from multiverso_tpu.tables import matrix_table as mt
_process_add = mt.MatrixServer.process_add
def _lower(self, request):
    if not isinstance(request[0], str):
        ids, values, option = request
        if not hasattr(values, "astype"):
            values = np.asarray(values)
        request = (ids, values.astype(jnp.dtype({dtype!r})).astype(
            values.dtype), option)
    return _process_add(self, request)
mt.MatrixServer.process_add = _lower
""",
}


def run_control(workload, seed, seconds=3.0, dtype="bfloat16",
                lower="table", rehearse=False, timeout=1200):
    """One run of the cell with its table, or its deltas, in ``dtype``; the
    compared numbers and `correct` (None where the run printed no
    result)."""
    args = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"] + (["--rehearse"] * rehearse)
    code = (LOWER[lower].format(dtype=dtype) + "import sys\n"
            "from benchmark import run\n"
            f"sys.exit(run.main({args!r}))")
    env = dict(os.environ, PYTHONPATH=ROOT)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=timeout)
    lines = done.stdout.strip().splitlines()
    compared = [json.loads(x) for x in lines if x.startswith('{"compared"')]
    last = json.loads(lines[-1]) if lines and lines[-1].startswith(
        '{"correct"') else None
    report = {"control": workload, "seed": seed, "lower": lower,
              "dtype": dtype,
              "exit": done.returncode, "compared": compared,
              "correct": None if last is None else last["correct"]}
    if last is None:
        errors = [x for x in (done.stdout + done.stderr).splitlines()
                  if "Error" in x]
        report["errors"] = errors[-3:]
    return report


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--lower", choices=sorted(LOWER), default="table")
    args = parser.parse_args()
    passed = 0
    for seed in args.seeds:
        report = run_control(args.workload, seed, args.seconds, args.dtype,
                             args.lower)
        print(json.dumps(report), flush=True)
        passed += report["correct"] is True
    sys.exit(1 if passed else 0)
