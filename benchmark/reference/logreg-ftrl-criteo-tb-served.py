"""The plain reference of the keyed FTRL table SERVED to several trainer
processes (upstream Multiverso `Applications/LogisticRegression`,
`objective_type=ftrl sparse=true`, run distributed: `ps_role` server and N
workers, `-sync=false`): the table, step, closed form, `Replay`, error limits
and `n` equality of `logreg-ftrl-criteo-tb.py`, to the letter (that module is
loaded from beside this file and its names are this module's: its docstring
has the limits `z_error`, `w_error` and `n_mismatch` hold the system to, and
the reason for each), plus what is new when the workers are concurrent: **the
order rule**.

An FTRL step reads the key's old `(z, n)`, so two Adds that name one key give
different states in different orders, and with 8 workers no worker knows the
order from its own calls. The server says it: every reply to an op on the
table carries the table's **Add ordinal**. An Add's is its place, 1, 2, ...,
in the one order in which the server applied every worker's Adds; a Get's is
the number of Adds applied when it was launched. A worker keeps a record of
its acknowledged ops in program order (`Ops`), and from every worker's record
alone, with no reference to the server, `order_faults` decides whether those
ordinals are a legal serial order:

(a) **exactly once**: the Adds' ordinals are exactly 1..N, each once. A lost
    Add leaves a gap below the largest, one applied twice takes two places
    and its worker learns one: both leave an ordinal in 1..N that nobody
    holds; a reply without an ordinal and an ordinal two Adds hold count too.
(b) **program order** (a worker's ops are sequential): its Adds' ordinals
    rise; a Get sent after the worker's own Add was acknowledged reports at
    least that Add's ordinal (read your writes); an Add after a Get that
    reported `k` lies beyond `k`; Gets never go back.
(c) **real time**, between workers: an op whose reply had arrived before
    another op was sent is not ordered after it. Add before Add: the smaller
    ordinal. Add before Get: the Get's count is at least the Add's ordinal.
    Get before Add: the Get's count is below the Add's ordinal. Get before
    Get: the count does not fall. Times are `time.perf_counter`, which on
    Linux is `CLOCK_MONOTONIC`, one clock for every process of a machine
    (`one_clock` asserts it); a worker stamps `sent` before its call and
    `replied` after it returns, so both intervals only widen and a sound
    server can never be accused.

Rule (b)'s faults are not counted again by (c), which pairs ops of different
workers only: each fault reads under its own letter.

Then the order is replayed (`OrderedReplay`): the Adds in ordinal order, Add
by Add, for the keys a comparison asks for, so that a Get that reported `k`
is compared, EVERY element, with the weights after exactly the first `k`
Adds, and the final `(z, n)` of the checked keys with the state after all N.
A wrong ordinal that is still a legal order (two concurrent Adds swapped) is
not caught by the rules and is caught here: the two orders give different
`z` on every key both Adds name whose weight is not 0.

Imports nothing of the program."""

import importlib.util
import os
import time

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_logreg_ftrl_criteo_tb",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "logreg-ftrl-criteo-tb.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

init_zn, grad_k, to_float = _base.init_zn, _base.grad_k, _base.to_float
weights, Replay = _base.weights, _base.Replay
z_error, w_error, n_mismatch = _base.z_error, _base.w_error, _base.n_mismatch
GRAD_UNIT = _base.GRAD_UNIT

GET, ADD = 0, 1
NO_ORDINAL = -1   # a reply that carried none


def one_clock():
    """The clock the records are on; raises where it is not one clock for
    every process of the machine."""
    info = time.get_clock_info("perf_counter")
    if not info.monotonic or "CLOCK_MONOTONIC" not in info.implementation:
        raise RuntimeError(
            f"time.perf_counter is {info.implementation!r}: the records of "
            f"different processes cannot be laid on one line")
    return info.implementation


class Ops:
    """One worker's record of its acknowledged ops, in program order:
    ``kind`` (GET or ADD), ``entry`` (which pooled minibatch; the driver's
    number, unique over all workers), ``ordinal`` (NO_ORDINAL where the
    reply carried none), ``sent`` and ``replied`` (``time.perf_counter``
    seconds)."""

    __slots__ = ("kind", "entry", "ordinal", "sent", "replied")

    def __init__(self, kind, entry, ordinal, sent, replied):
        self.kind = np.asarray(kind, np.int8)
        self.entry = np.asarray(entry, np.int64)
        self.ordinal = np.asarray(
            [NO_ORDINAL if k is None else k for k in ordinal], np.int64)
        self.sent = np.asarray(sent, np.float64)
        self.replied = np.asarray(replied, np.float64)
        if not (len(self.kind) == len(self.entry) == len(self.ordinal)
                == len(self.sent) == len(self.replied)):
            raise ValueError("a record's columns differ in length")

    def __len__(self):
        return len(self.kind)

    def place(self):
        """Each op's place on the line the ordinals describe, doubled so
        that it is whole: Add ``k`` at ``2k``, a Get that reported ``k`` at
        ``2k + 1``, between Add ``k`` and Add ``k + 1``."""
        return 2 * self.ordinal + (self.kind == GET)


def exactly_once(records):
    """Rule (a). ``(accepted, faults)``: for every worker a mask over its
    ops, True at an Add whose ordinal lies in 1..N and is no other Add's (N:
    the Adds of all records); and the count of faults: Adds not accepted,
    and ordinals of 1..N that no Add holds."""
    ordinals = np.concatenate([r.ordinal[r.kind == ADD] for r in records])
    n = len(ordinals)
    inside = (ordinals >= 1) & (ordinals <= n)
    held = np.bincount(ordinals[inside], minlength=n + 1)
    accepted = [(r.kind == ADD) & (r.ordinal >= 1) & (r.ordinal <= n)
                & (held[np.clip(r.ordinal, 0, n)] == 1) for r in records]
    unheld = int((held[1:] == 0).sum())
    return accepted, n - int(sum(a.sum() for a in accepted)) + unheld


def program_order(record):
    """Rule (b): the ops of one worker that lie before an earlier op of
    the same worker, counted."""
    place = record.place()
    if len(place) < 2:
        return 0
    before = np.maximum.accumulate(place)[:-1]
    # an Add lies strictly beyond everything before it; a Get may repeat
    # the place of the Get before it
    return int(((place[1:] < before)
                | ((record.kind[1:] == ADD) & (place[1:] == before))).sum())


def real_time(records):
    """Rule (c): the ops that lie before an op of ANOTHER worker whose
    reply had arrived before they were sent, counted."""
    faults = 0
    for w, mine in enumerate(records):
        others = [r for v, r in enumerate(records) if v != w and len(r)]
        if not others or not len(mine):
            continue
        replied = np.concatenate([r.replied for r in others])
        place = np.concatenate([r.place() for r in others])
        by_reply = np.argsort(replied, kind="stable")
        latest = np.maximum.accumulate(place[by_reply])
        # the ops of others acknowledged before each of mine was sent
        n_before = np.searchsorted(replied[by_reply], mine.sent, side="left")
        some = n_before > 0
        before = latest[n_before[some] - 1]
        at = mine.place()[some]
        faults += int(((at < before)
                       | ((mine.kind[some] == ADD) & (at == before))).sum())
    return faults


def order_faults(records):
    """The three rules over every worker's record: ``{"a": faults, "b":
    faults, "c": faults}``; a legal serial order reads 0, 0, 0."""
    return {"a": exactly_once(records)[1],
            "b": sum(program_order(r) for r in records),
            "c": real_time(records)}


def serial_order(records):
    """The pooled entry of every Add, in ordinal order: what the server
    says it applied, first to last. Asks rule (a) first: an order with a
    gap or a doubled place is not an order."""
    if exactly_once(records)[1]:
        raise ValueError("the Adds' ordinals are not 1..N, each once")
    ordinal = np.concatenate([r.ordinal[r.kind == ADD] for r in records])
    entry = np.concatenate([r.entry[r.kind == ADD] for r in records])
    return entry[np.argsort(ordinal)]


class OrderedReplay:
    """A `Replay` of ``keys`` fed the Adds of a serial order. ``pool_keys``
    and ``pool_gk``: the keys (distinct) and the gradient in units of every
    pooled entry an order may name, by entry number. Moves forward only:
    ask for the states at rising counts."""

    def __init__(self, keys, pool_keys, pool_gk, seed, opt):
        self.replay = Replay(keys, seed, opt)
        self._plans, self._grads = {}, {}
        for entry, entry_keys in pool_keys.items():
            plan = self.replay.plan(entry_keys)
            if len(plan[1]):
                self._plans[entry] = plan
                self._grads[entry] = to_float(pool_gk[entry][plan[1]])
        self.applied = 0

    def after(self, order, count):
        """The replay after exactly the first ``count`` Adds of ``order``."""
        if count < self.applied:
            raise ValueError("an ordered replay does not go back")
        for entry in order[self.applied:count]:
            plan = self._plans.get(int(entry))
            if plan is not None:
                self.replay.add(plan, self._grads[int(entry)])
        self.applied = count
        return self.replay

    def get_error(self, order, count, keys, got, opt):
        """The error of a Get of ``keys`` (all held) that reported ``count``
        and returned ``got``, every element, in units of what is allowed:
        at most 1 passes."""
        z, _, want, steps = self.after(order, count).state(keys)
        return w_error(got, want, z, steps, opt)
