#!/usr/bin/env python
"""The control of `correct` for a cell whose table is the keyed FTRL table:
the cell itself, run.py and its timed path, with every Add's gradient rounded
to bfloat16 where the server's table takes it (`control.py`'s `--lower
delta` patches `MatrixServer.process_add` alone and would lower nothing
here). Every run has to come out as not correct. On the chip, at the cell's
own sizes, each seed a process of its own:

    python benchmark/tests/control_keys.py --workload ftrlctr.step-keys \
        --seconds 3 --seeds 1 2 3

Prints, for each seed, every number `correct` compared beside its limit and
the run's `correct`; exits 0 only if every run read false (a run that crashed
gave no number and has failed too). The run itself is `control.py`'s."""

import argparse
import importlib.util
import json
import os
import sys

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_control",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "control.py"))
control = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(control)

# the gradient of every keyed Add, rounded as it reaches the server's table
control.LOWER["keys"] = """
import jax.numpy as jnp
import numpy as np
from multiverso_tpu.tables import ftrl_table as ft
_process_add = ft.FTRLServer.process_add
def _lower(self, request):
    keys, grad = request
    if not hasattr(grad, "astype"):
        grad = np.asarray(grad, np.float32)
    return _process_add(self, (keys, grad.astype(
        jnp.dtype({dtype!r})).astype(jnp.float32)))
ft.FTRLServer.process_add = _lower
"""


def run_control(workload, seed, seconds=3.0, dtype="bfloat16",
                rehearse=False, timeout=1200):
    return control.run_control(workload, seed, seconds, dtype, "keys",
                               rehearse, timeout)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--dtype", default="bfloat16")
    args = parser.parse_args()
    passed = 0
    for seed in args.seeds:
        report = run_control(args.workload, seed, args.seconds, args.dtype)
        print(json.dumps(report), flush=True)
        passed += report["correct"] is True
    sys.exit(1 if passed else 0)
