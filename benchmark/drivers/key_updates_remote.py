"""Traffic `key_updates_remote`: the chip's process holds a keyed FTRL table
(`mv.create_table("ftrl", key_space, ...)`) with no worker of its own
(`ps_role="server"`, the async server) and serves it (`mv.serve`); worker
processes pinned to the CPU, which never start a JAX backend, connect over
loopback TCP (`mv.remote_connect(endpoint).table(id)`) and each run a closed
loop, one op in flight, no think time, in upstream's order (`ps_model.cpp`:
pull, compute, push): ONE keyed Get of the distinct keys its minibatch's
samples name, then ONE keyed Add of a raw gradient for the same keys, both as
numpy arrays. Upstream's `Applications/LogisticRegression` in its distributed
mode: `ps_role` server and worker ranks, `-sync=false`.

The keys are `key_updates_local`'s (its `ZipfValues` and its `Driver`'s
key layout, sample and device passes are this driver's): a sample names one
value of each categorical feature (Zipf over a seeded bijection of the
feature's ids, the same bijection for every worker), every integer feature
and the bias; a worker draws its pool of minibatches from the seed and its
own number, so the workers' Adds overlap on the hot keys.

Every Add is an FTRL step, so Adds do not commute, the dispatcher never
merges them, and the checks cannot count them. Every reply carries the
table's Add ordinal (`table.last_ordinal`); every worker keeps a record of
its acknowledged ops (kind, pooled entry, ordinal, send and reply time on
`time.perf_counter`), and the configuration's reference first decides from
the records alone whether the ordinals are a legal serial order (its rules
a, b, c), then replays the Adds in that order: every element of every kept
Get against the weights after exactly the Adds its reply counted (each
worker checks its own, all at once), and the final `(z, n)` of the checked
keys against the state after all of them.

End to end: keys per second of acknowledged ops, all workers together, and
the median and 95th percentile of the time from each call to its reply on
the worker's own clock.

This file is also the worker: `python key_updates_remote.py --worker <json>`.
Parent and workers talk in lines: the parent writes a command to a worker's
stdin, the worker answers with one JSON line that starts {"bench_worker".
A worker that ends (its `client.table` raised, on a program that does not
serve the kind) ends the read of its answer and the run; one that is silent
past a phase's time is killed with its fellows.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark import common, rows_table  # noqa: E402

local = common.load_module("drivers", "key_updates_local")

POOL_SALT = 1000  # worker w draws its pool from mix_seed(seed, POOL_SALT + w)
ANSWER_S = 240    # a phase's time limit beyond its own seconds
# what a control puts here runs in every worker process before it connects
# (benchmark/tests/control_keys_remote.py); the cell leaves it empty
WORKER_PRELUDE = ""


class Keys:
    """The key layout and every worker's pool, the same in the parent and
    in every worker: made from the configuration's counts and the seed."""

    def __init__(self, counts, always, params, seed):
        self.bases = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        self.always = np.asarray(always, np.int64)
        self.params = params
        rng = np.random.default_rng(common.mix_seed(seed, 1))
        self.zipf = [local.ZipfValues(c, rng) for c in counts]
        self.seed, self.entries = seed, params["pool"]

    # the distinct keys one minibatch names: the local driver's own draw,
    # which reads `params`, `bases`, `zipf` and `always`
    step_keys = local.Driver._step_keys

    def pool(self, ref, worker):
        """Worker ``worker``'s pooled minibatches: ``[(keys, gradient in
        units)]``; entry ``i`` of it is entry ``worker * entries + i`` of
        the run."""
        rng = np.random.default_rng(
            common.mix_seed(self.seed, POOL_SALT + worker))
        out = []
        for _ in range(self.entries):
            keys = self.step_keys(rng)
            out.append((keys, ref.grad_k(rng, len(keys))))
        return out

    def pools(self, ref, workers):
        """``({entry: keys}, {entry: gradient in units})`` of every
        worker."""
        keys, gk = {}, {}
        for w in range(workers):
            for i, (k, g) in enumerate(self.pool(ref, w)):
                keys[w * self.entries + i] = k
                gk[w * self.entries + i] = g
        return keys, gk


def records_of(ref, reports):
    return [ref.Ops(*(r["ops"][c] for c in
                      ("kind", "entry", "ordinal", "sent", "replied")))
            for r in reports]


class Driver(local.Driver):
    """`key_updates_local.Driver` for its key layout (`__init__`), the
    sample's state read on the device (`_state`) and the passes over the
    whole state (`_differing`, `_unnamed_changed`)."""

    def __init__(self, run):
        super().__init__(run)
        self.procs = []
        self.server = dict(run.config["server"])
        self.workers = self.params.get("workers", self.server["workers"])

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp

        import multiverso_tpu as mv

        run, p = self.run, self.params
        self.ref = ref = common.load_module("reference", run.cell["config"])
        self.clock = ref.one_clock()
        mv.init(mesh_shape=str(run.chips), remote_workers=self.workers,
                **dict(rows_table.INIT_FLAGS, sync=self.server["sync"],
                       ps_role=self.server["ps_role"]))
        run.phase("program start")
        seed = run.seed
        self.piece = piece = min(local._PIECE, self.key_space)

        @jax.jit
        def init_piece(lo):
            return ref.init_zn(lo + jnp.arange(piece, dtype=jnp.int32),
                               seed, jnp)

        self._init_piece = init_piece
        self.table = mv.create_table(
            "ftrl", self.key_space,
            init=lambda lo, count: tuple(
                s[:count] for s in init_piece(jnp.int32(lo))), **self.opt)
        self.table.get_state_device("n").block_until_ready()
        run.phase("create_table")
        endpoint = mv.serve("127.0.0.1:0")
        spec = {"endpoint": endpoint, "table_id": self.table.table_id,
                "seed": seed, "config": run.cell["config"],
                "counts": self.counts, "always": self.always.tolist(),
                "opt": self.opt, "workers": self.workers, "params": p,
                "prelude": WORKER_PRELUDE}
        # a chip belongs to one process: each worker's platform is written,
        # not inherited, and it must finish without starting a backend
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for w in range(self.workers):
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker",
                 json.dumps(dict(spec, worker=w))],
                env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True, bufsize=1))
        # meanwhile, under the workers' own start: what the block source
        # gave, over every key and before any op (one pass on the device,
        # the first use of the program that counts the changed keys after
        # the window), and the same pools for the checks
        run.compare.add("created_state_mismatch", self._differing(), 0)
        run.phase("created state check")
        self.keys = Keys(self.counts, self.always, p, seed)
        self.pool_keys, self.pool_gk = self.keys.pools(ref, self.workers)
        rng = np.random.default_rng(common.mix_seed(seed, 2))
        self.sample, self.quiet = self._checked_keys(rng)
        self._take = jax.jit(lambda state, keys: state[keys])
        self.followed = ref.OrderedReplay(self.sample, self.pool_keys,
                                          self.pool_gk, seed, self.opt)
        run.phase("traffic pools and reference plans")
        hello = self._ask("hello")
        if any(h["clock"] != self.clock for h in hello):
            raise RuntimeError("a worker's perf_counter is another clock")
        run.phase("workers ready")
        # warm-up, all workers at once: each walks its entries so that
        # every count of slots its pool launches has compiled
        warmed = self._ask_each(["warm " + json.dumps(self._warm_order(w))
                                 for w in range(self.workers)])
        self.reports = warmed
        run.phase("warm-up")
        # what the checks after the window will read, read once here at the
        # Adds acknowledged so far, in the order the server gave them: the
        # sample's weights through the table's host Get, the quiet keys to
        # the bit, and on the device every key no pooled set names
        records = records_of(ref, warmed)
        faults = ref.order_faults(records)
        for rule in "abc":
            run.compare.add(f"warm_order_{rule}_faults", faults[rule], 0)
        order = ref.serial_order(records) if not faults["a"] else []
        replay = self.followed.after(order, len(order))
        want_z, _, want_w, steps = replay.state(self.sample)
        run.compare.add("start_sample_w_error", ref.w_error(
            self.table.get(self.sample), want_w, want_z, steps, self.opt),
            1.0)
        run.compare.add("start_quiet_mismatch", self._quiet_changed(), 0)
        run.compare.add("start_unnamed_mismatch", self._unnamed_changed(), 0)
        run.spans.samples.clear()
        run.phase("start state check")

    def _checked_keys(self, rng):
        """``(sample, quiet)``. The sample: the keys compared before and
        after the window, at least half of them named by more than one
        worker's pool (where an order shows), the rest keys one worker
        names, cold keys, the keys every sample names, the first and the
        last key of every feature and the features of at most 64 values
        whole. Quiet: keys no pooled minibatch names."""
        p, entries = self.params, self.params["pool"]
        by_worker = [np.unique(np.concatenate(
            [self.pool_keys[w * entries + i] for i in range(entries)]))
            for w in range(self.workers)]
        named, naming = np.unique(np.concatenate(by_worker),
                                  return_counts=True)
        self.named = named
        shared, single = named[naming > 1], named[naming == 1]
        want = p["check_keys"]
        hot = rng.choice(shared, min(want // 2 + want // 8, len(shared)),
                         replace=False)
        lone = rng.choice(single, min(want // 8, len(single)), replace=False)
        cold = rng.choice(self.key_space,
                          max(want - len(hot) - len(lone), 0), replace=False)
        edges = np.concatenate([self.bases[:-1], self.bases[1:] - 1])
        small = [base + np.arange(c) for base, c in
                 zip(self.bases, self.counts) if c <= 64]
        sample = np.unique(np.concatenate(
            [hot, lone, cold, edges, self.always, *small])).astype(np.int32)
        self.sample_shared = int(np.isin(sample, shared).sum())
        quiet = np.setdiff1d(
            rng.choice(self.key_space,
                       min(p["quiet_keys"], self.key_space // 2),
                       replace=False), named).astype(np.int32)
        return sample, quiet

    def _warm_order(self, w):
        """Worker ``w``'s entries for its warm-up pairs: one of every
        program its pool launches first (the table's own rule: a bucket,
        and the slots of it a count of keys works on), then the others."""
        from multiverso_tpu.tables.device_ids import live_slots

        server, entries = self.table._server_table, self.params["pool"]

        def program(i):
            n = len(self.pool_keys[w * entries + i])
            bucket = server.launch_form(n, "get")[0]
            return bucket, live_slots(n, bucket)

        first = {}
        for i in range(entries):
            first.setdefault(program(i), i)
        if len(first) > self.params["warmup_pairs"]:
            raise ValueError("the warm-up pairs do not reach every count "
                             "of slots a worker's pool launches")
        rest = [i for i in range(entries) if i not in first.values()]
        return (list(first.values()) + rest)[:self.params["warmup_pairs"]]

    def _quiet_changed(self):
        z, n = self._state(self.quiet)
        want_z, want_n = self.ref.init_zn(self.quiet, self.run.seed)
        return (self.ref.n_mismatch(z, want_z)
                + self.ref.n_mismatch(n, want_n))

    # -- parent and workers ----------------------------------------------
    def _ask(self, command, seconds=0.0):
        return self._ask_each([command] * len(self.procs), seconds)

    def _ask_each(self, commands, seconds=0.0):
        """A command to every worker, one answer from each. A worker that
        has ended ends the read; one that is silent past the phase's time
        is killed with the others, which ends the read too."""
        guard = threading.Timer(seconds + ANSWER_S, self._kill)
        guard.daemon = True
        guard.start()
        try:
            for proc, command in zip(self.procs, commands):
                proc.stdin.write(command + "\n")
                proc.stdin.flush()
            return [self._answer(w) for w in range(len(self.procs))]
        finally:
            guard.cancel()

    def _kill(self):
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()

    def _answer(self, w):
        while True:
            line = self.procs[w].stdout.readline()
            if not line:
                raise RuntimeError(
                    f"worker {w} ended (exit {self.procs[w].wait()})")
            if line.startswith('{"bench_worker"'):
                return json.loads(line)

    # -- the window ------------------------------------------------------
    def window(self, seconds):
        from multiverso_tpu.dashboard import Dashboard

        run = self.run
        counters = ("FTRL_SERVED_GET", "FTRL_SERVED_ADD", "ADDS_ORDERED",
                    "WIRE_FLOAT_DENSE", "WIRE_FLOAT_SPARSE")
        before = [Dashboard.counter_value(c) for c in counters]
        t0 = time.perf_counter()
        reports = self._ask(f"go {seconds}", seconds)
        t1 = time.perf_counter()
        self.reports = reports
        run.attempted = sum(r["attempted"] for r in reports)
        run.failed = sum(r["failed"] for r in reports)
        adds = sum(r["adds"] for r in reports)
        gets = sum(r["gets"] for r in reports)
        by_second = np.bincount(
            np.concatenate([np.asarray(r["done_s"], int) for r in reports]),
            minlength=int(seconds))
        print(json.dumps({
            "pairs_by_second": by_second.tolist(),
            "served_counters": {
                c: Dashboard.counter_value(c) - was
                for c, was in zip(counters, before)}}), flush=True)
        ms = {"add": [x for r in reports for x in r["add_ms"]],
              "get": [x for r in reports for x in r["get_ms"]]}
        keys_named = sum(r["keys_named"] for r in reports)
        run.result.update(
            ops=adds + gets, adds=adds, gets=gets, rows=2 * keys_named,
            add_rows=keys_named, get_rows=keys_named, row_cols=1,
            elapsed_s=max(r["elapsed_s"] for r in reports), op_ms=ms)
        return t1

    # -- after the window -------------------------------------------------
    def finish(self):
        run, ref, opt = self.run, self.ref, self.opt
        t = time.perf_counter()
        assert not any(r["backends_initialized"] for r in self.reports), \
            "a worker process started a JAX backend"
        # the order, from the workers' records alone
        records = records_of(ref, self.reports)
        accepted, _ = ref.exactly_once(records)
        faults = ref.order_faults(records)
        for rule in "abc":
            run.compare.add(f"order_{rule}_faults", faults[rule], 0)
        # of the window's acknowledged Adds, as the workers counted them
        in_window = [np.arange(len(r)) >= len(r) - rep["window_ops"]
                     for r, rep in zip(records, self.reports)]
        run.result["adds_acked"] = int(sum(
            (w & (r.kind == ref.ADD)).sum()
            for r, w in zip(records, in_window)))
        run.result["adds_ordered"] = int(sum(
            (w & a).sum() for a, w in zip(accepted, in_window)))
        if faults["a"]:
            # no order to replay: what was compared has said not correct
            print(json.dumps({"reference_replay_s": None}), flush=True)
            return
        order = ref.serial_order(records)
        # the kept Gets, every element, at the Adds each reply counted:
        # each worker replays the order for its own, all at once
        verified = self._ask("verify " + json.dumps(order.tolist()))
        run.compare.add("window_get_error",
                        max(v["error"] for v in verified), 1.0)
        run.result["gets_checked"] = sum(v["gets"] for v in verified)
        run.result["get_elements_checked"] = sum(v["elements"]
                                                 for v in verified)
        # the sample after every Add: its state on the device, its weights
        # through the table's host Get; the quiet keys and every key no
        # pooled minibatch names, to the bit
        replay = self.followed.after(order, len(order))
        want_z, want_n, want_w, steps = replay.state(self.sample)
        z, n = self._state(self.sample)
        run.compare.add("final_sample_w_error", ref.w_error(
            self.table.get(self.sample), want_w, want_z, steps, opt), 1.0)
        run.compare.add("final_sample_z_error",
                        ref.z_error(z, want_z, steps), 1.0)
        run.compare.add("final_sample_n_mismatch",
                        ref.n_mismatch(n, want_n), 0)
        run.compare.add("final_quiet_mismatch", self._quiet_changed(), 0)
        run.compare.add("unnamed_state_mismatch", self._unnamed_changed(), 0)
        run.result.update(
            keys_checked=int(len(self.sample)),
            keys_checked_shared=self.sample_shared,
            keys_stepped=int((steps > 0).sum()),
            most_steps=int(steps.max()), adds_replayed=int(len(order)),
            keys_unnamed=self.key_space - int(len(self.named)))
        print(json.dumps({
            "reference_replay_s": time.perf_counter() - t,
            "keys_an_op": self._keys_an_op(),
            "keys_also_in_the_add_before": self._overlap(order)}),
            flush=True)

    def _keys_an_op(self):
        counts = [len(k) for k in self.pool_keys.values()]
        return {"min": min(counts), "max": max(counts),
                "mean": float(np.mean(counts))}

    def _overlap(self, order, pairs=256):
        """Of the keys of a typical Add, how many the Add applied just
        before it named too: the median and the mean over the last
        ``pairs`` Adds of the order."""
        tail = [int(e) for e in order[-(pairs + 1):]]
        both = [len(np.intersect1d(self.pool_keys[a], self.pool_keys[b],
                                   assume_unique=True))
                for a, b in zip(tail, tail[1:])]
        if not both:
            return None
        return {"median": float(np.median(both)),
                "mean": float(np.mean(both)), "pairs": len(both)}

    def end_to_end(self):
        """The four numbers of every row cell (`rows_per_s` counts keys: a
        key is this table's row), all printed; BENCHMARK.json says which
        of them this cell is judged on."""
        values = rows_table.end_to_end(self.run.result)
        print(json.dumps({"end_to_end_all": values}), flush=True)
        return values

    def close(self):
        import multiverso_tpu as mv
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    proc.stdin.write("quit\n")
                    proc.stdin.flush()
                except OSError:
                    pass
        for proc in self.procs:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        mv.shutdown()


# -- the worker process --------------------------------------------------------

def _say(**fields):
    print(json.dumps(dict({"bench_worker": fields.pop("worker")}, **fields)),
          flush=True)


def worker_main(spec):
    import jax._src.xla_bridge as xla_bridge

    import multiverso_tpu as mv

    if spec["prelude"]:
        exec(spec["prelude"], {"spec": spec})
    w, p, seed, opt = spec["worker"], spec["params"], spec["seed"], \
        spec["opt"]
    ref = common.load_module("reference", spec["config"])
    keys = Keys(spec["counts"], spec["always"], p, seed)
    entries = p["pool"]
    pool = [(k, ref.to_float(gk)) for k, gk in keys.pool(ref, w)]
    client = mv.remote_connect(spec["endpoint"])
    table = client.table(spec["table_id"])
    # every acknowledged op since the connection, in program order
    ops = {c: [] for c in ("kind", "entry", "ordinal", "sent", "replied")}
    state = {"pairs": 0}
    kept = []   # (index into ops, pooled entry, the weights) of the window

    def note(kind, i, sent, replied):
        ops["kind"].append(kind)
        ops["entry"].append(w * entries + i)
        ops["ordinal"].append(table.last_ordinal)
        ops["sent"].append(sent)
        ops["replied"].append(replied)

    def pair(i=None):
        if i is None:
            i = state["pairs"] % entries
        k, grad = pool[i]
        t0 = time.perf_counter()
        got = table.get(k)
        t1 = time.perf_counter()
        note(ref.GET, i, t0, t1)
        t2 = time.perf_counter()
        table.add(k, grad)
        t3 = time.perf_counter()
        note(ref.ADD, i, t2, t3)
        state["pairs"] += 1
        return i, got, (t1 - t0) * 1e3, (t3 - t2) * 1e3

    for line in sys.stdin:
        command = line.split(None, 1)
        if not command or command[0] == "quit":
            break
        if command[0] == "hello":
            _say(worker=w, ready=True, clock=ref.one_clock())
        elif command[0] == "warm":
            # the entries the parent chose: one of every program first
            for i in json.loads(command[1]):
                pair(i)
            state["pairs"] = 0
            _say(worker=w, ops=ops, window_ops=0)
        elif command[0] == "go":
            seconds = float(command[1])
            rng = np.random.default_rng(common.mix_seed(seed, 77, w))
            sample_at = list(np.sort(rng.random(p["sampled_gets"])) * seconds)
            add_ms, get_ms, done_s = [], [], []
            attempted = failed = keys_named = 0
            first = len(ops["kind"])
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                attempted += 2
                try:
                    i, got, g, a = pair()
                except Exception as e:  # an op that raised has failed
                    failed += 1
                    print(f"benchmark worker {w}: op failed: {e!r}",
                          flush=True)
                    if failed > 100:
                        break
                    continue
                get_ms.append(g)
                add_ms.append(a)
                keys_named += len(pool[i][0])
                done_s.append(time.perf_counter() - t0)
                if sample_at and time.perf_counter() - t0 >= sample_at[0]:
                    sample_at.pop(0)
                    kept.append((len(ops["kind"]) - 2, i, got))
            elapsed = time.perf_counter() - t0
            _say(worker=w, adds=len(add_ms), gets=len(get_ms),
                 attempted=attempted, failed=failed, elapsed_s=elapsed,
                 keys_named=keys_named, add_ms=add_ms, get_ms=get_ms,
                 done_s=done_s, ops=ops,
                 window_ops=len(ops["kind"]) - first,
                 backends_initialized=xla_bridge.backends_are_initialized())
        elif command[0] == "verify":
            # every element of every kept Get against the weights after
            # exactly the Adds its reply counted, in the server's order
            order = np.asarray(json.loads(command[1]), np.int64)
            error, elements = 0.0, 0
            if kept:
                pool_keys, pool_gk = keys.pools(ref, spec["workers"])
                held = np.unique(np.concatenate(
                    [pool[i][0] for _, i, _ in kept]))
                followed = ref.OrderedReplay(held, pool_keys, pool_gk, seed,
                                             opt)
                # a Get whose reply counted nothing cannot be placed
                counted = [k for k in kept if ops["ordinal"][k[0]] is not None]
                if len(counted) < len(kept):
                    error = float("inf")
                for at, i, got in sorted(
                        counted, key=lambda k: ops["ordinal"][k[0]]):
                    error = max(error, followed.get_error(
                        order, ops["ordinal"][at], pool[i][0], got, opt))
                    elements += len(got)
            _say(worker=w, error=error, gets=len(kept), elements=elements)
    client.close()


if __name__ == "__main__":
    worker_main(json.loads(sys.argv[sys.argv.index("--worker") + 1]))
