#!/usr/bin/env python
"""The control of `correct` for a cell whose configuration states the BSP
guarantee: the cell itself, run.py and its timed path, with the server's
`sync` turned off in the configuration as the run loads it, so that the plain
async server answers every Get at once. Hot rows are shared between the
workers' pools, so an ungated Get sees another worker's Add early or late,
and every run has to come out as not correct, by `round_get_mismatch` or
`window_get_mismatch`: a control that passes means the comparison does not
see the guarantee. On the chip, at the cell's own sizes, each seed a process
of its own:

    python benchmark/tests/control_bsp.py --workload emb128bsp.round-workers \
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

# the configuration file as the run loads it, with the guarantee taken out
# (`sync` stands where the other controls' dtype does: "False" ungates,
# "True" leaves the cell as it is)
control.LOWER["ungated"] = """
from benchmark import common
_load = common.load_json
def _ungated(*parts):
    loaded = _load(*parts)
    if isinstance(loaded, dict) and "server" in loaded:
        loaded["server"]["sync"] = {dtype}
    return loaded
common.load_json = _ungated
"""


def run_control(workload, seed, seconds=3.0, sync=False, rehearse=False,
                timeout=1200):
    return control.run_control(workload, seed, seconds, str(bool(sync)),
                               "ungated", rehearse, timeout)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    parser.add_argument("--seconds", type=float, default=3.0)
    args = parser.parse_args()
    passed = 0
    for seed in args.seeds:
        report = run_control(args.workload, seed, args.seconds)
        print(json.dumps(report), flush=True)
        passed += report["correct"] is True
    sys.exit(1 if passed else 0)
