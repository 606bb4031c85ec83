#!/usr/bin/env python
"""The controls of `correct` for a cell whose keyed FTRL table is served to
worker processes (`ftrlctr8.remote-steps`): the cell itself, run.py and its
timed path, with one thing broken. Every run has to come out as not correct;
a control that passes means the comparison does not see what it is there to
see. Three faults, `--fault`:

`gradient`  every worker rounds the gradient of every Add to bfloat16 before
            it sends it (the step that would halve an Add's bytes on the
            wire): the next precision below the configuration's float32.
`order`     the replay swaps two Adds that are next to each other in the
            server's order and name keys in common (any two minibatches do:
            the 14 keys every sample names, the small features' values). The
            swapped order is still a legal serial order, so the rules say
            nothing and only the replayed state can: this is the control of
            "the Adds take effect in the order the ordinals give".
`retry`     every worker's connection sends some of its Add frames twice
            (the fault injection of `tests/test_fault.py`) to a server whose
            dedup window is off, so a retried Add is applied twice: the
            control of "a retried Add is applied once".

On the chip, at the cell's own sizes, each seed a process of its own:

    python benchmark/tests/control_keys_remote.py \
        --workload ftrlctr8.remote-steps --fault order --seconds 3 --seeds 1 2

Prints, for each seed, every number `correct` compared beside its limit and
the run's `correct`; exits 0 only if every run read false (a run that crashed
gave no number and has failed too). The run itself is `control.py`'s. With
`--sound` the same patch is applied with the fault left out, and every run
has to read correct: the patch itself breaks nothing."""

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

# a driver's or a reference's module as the run loads it, handed to `_fix`
_ON_LOAD = """
from benchmark import common
_load_module = common.load_module
def _loaded(kind, name):
    module = _load_module(kind, name)
    _fix(kind, name, module)
    return module
common.load_module = _loaded
"""

# what runs in every worker process before it connects: the proxy's Add
# rounds its gradient ({dtype!r}: float32 leaves it as it is)
control.LOWER["gradient"] = """
_PRELUDE = '''
import ml_dtypes
import numpy as np
from multiverso_tpu.runtime import remote
_add = remote._RemoteFTRLWorker.add
def _lower(self, keys, grads=None):
    return _add(self, keys, np.asarray(grads, np.float32).astype(
        np.dtype({dtype!r})).astype(np.float32))
remote._RemoteFTRLWorker.add = _lower
'''
def _fix(kind, name, module):
    if hasattr(module, "WORKER_PRELUDE"):
        module.WORKER_PRELUDE = _PRELUDE
""" + _ON_LOAD

# the reference's `serial_order` with two neighbouring Adds swapped, in the
# middle of the order ({dtype}: False leaves the order as it is)
control.LOWER["order"] = """
def _fix(kind, name, module):
    if kind != "reference" or not hasattr(module, "serial_order"):
        return
    order_of = module.serial_order
    def swapped(records):
        order = order_of(records).copy()
        at = len(order) // 2
        while at + 1 < len(order) and order[at] == order[at + 1]:
            at += 1
        if {dtype} and at + 1 < len(order):
            order[at], order[at + 1] = order[at + 1], order[at]
            print('{{"control_swapped": [%d, %d]}}' % (at + 1, at + 2),
                  flush=True)
        return order
    module.serial_order = swapped
""" + _ON_LOAD

# every worker's connection sends every 7th Add frame twice, and the
# server's dedup window answers nothing ({dtype}: False leaves it on)
control.LOWER["retry"] = """
_PRELUDE = '''
import multiverso_tpu as mv
mv.set_flag("fault_spec", "dup:type=Request_Add,every=7")
mv.set_flag("fault_seed", 1 + spec["worker"])
'''
def _fix(kind, name, module):
    if hasattr(module, "WORKER_PRELUDE"):
        module.WORKER_PRELUDE = _PRELUDE
if {dtype}:
    from multiverso_tpu.runtime import remote
    remote.RemoteServer._replayed = lambda self, msg: False
""" + _ON_LOAD

BROKEN = {"gradient": "bfloat16", "order": "True", "retry": "True"}
SOUND = {"gradient": "float32", "order": "False", "retry": "False"}


def run_control(workload, seed, seconds=3.0, fault="gradient", sound=False,
                rehearse=False, timeout=1200):
    return control.run_control(workload, seed, seconds,
                               (SOUND if sound else BROKEN)[fault], fault,
                               rehearse, timeout)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--fault", choices=sorted(BROKEN), required=True)
    parser.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--sound", action="store_true")
    args = parser.parse_args()
    wrong = 0
    for seed in args.seeds:
        report = run_control(args.workload, seed, args.seconds, args.fault,
                             args.sound)
        print(json.dumps(report), flush=True)
        wrong += report["correct"] is not args.sound
    sys.exit(1 if wrong else 0)
