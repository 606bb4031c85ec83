"""Traffic `row_ops_bsp`: the chip's process holds the table under BSP
(`sync=True`) with no worker of its own (`ps_role="server"`) and serves it
(`mv.serve`); worker processes pinned to the CPU, which never start a JAX
backend, connect over loopback TCP and move in rounds. Each is a closed
loop, no think time, one op in flight: Add of a pooled set of distinct rows
as numpy arrays, then Get of the same rows. The server's round gates hold a
round's Gets until its last Add is applied and the next round's Adds until
its last Get is served: upstream's `-sync=true`, its worker-rank /
server-rank split.

Under BSP a Get is determined in every element (the reference's round
rule), so every element of every compared Get is compared, not only the rows
private to a worker. When its time is up a worker ends its pair and sends
`finish_train`, so that the others' last rounds are served.

End to end: rows per second of acknowledged ops, all workers together, and
the median and 95th percentile of the time from each call to its reply on
the worker's own clock.

This file is also the worker: `python row_ops_bsp.py --worker <json>`.
Parent and workers talk in lines: the parent writes a command to a worker's
stdin, the worker answers with one JSON line that starts {"bench_worker".
A worker that does not answer in time is killed with its fellows, and the
run fails: a broken gate hangs and must not hang the run.
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

worker_pools = common.load_module("drivers", "row_ops_remote").worker_pools

# the program's always-on counters of the gate, where it has them
SYNC_COUNTERS = ("SYNC_ROUNDS", "SYNC_SERVED_ADD", "SYNC_SERVED_GET",
                 "SYNC_DEFERRED_ADD", "SYNC_DEFERRED_GET")
ANSWER_S = 600  # a phase's time limit beyond its own seconds


class Driver:
    def __init__(self, run):
        self.run = run
        self.shape, self.params = rows_table.sizes(run)
        self.procs = []

    def setup(self):
        import multiverso_tpu as mv

        run, p, shape = self.run, self.params, self.shape
        rows, cols = shape["num_row"], shape["num_col"]
        server = run.config["server"]
        self.ref = common.load_module("reference", run.cell["config"])
        mv.init(mesh_shape=str(run.chips), remote_workers=p["workers"],
                **dict(rows_table.INIT_FLAGS, sync=server["sync"],
                       ps_role=server["ps_role"]))
        run.phase("program start")
        init, self.init_sums = self.ref.init_table(rows, cols, run.seed)
        run.phase("initial values")
        self.table = mv.create_table(
            shape["kind"], rows, cols, np.dtype(shape["dtype"]),
            updater_type=shape["updater_type"], init_value=init)
        del init
        run.phase("create_table")
        endpoint = mv.serve("127.0.0.1:0")
        spec = {"endpoint": endpoint, "table_id": self.table.table_id,
                "seed": run.seed, "config": run.cell["config"],
                "rows": rows, "cols": cols,
                "exponent": run.config["row_popularity"]["exponent"],
                "params": p}
        # a chip belongs to one process: each worker's platform is written,
        # not inherited, and it must finish without starting a backend
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for w in range(p["workers"]):
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker",
                 json.dumps(dict(spec, worker=w))],
                env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True, bufsize=1))
        # meanwhile the parent builds the same pools for the checks
        self.zipf = common.ZipfRows(rows, spec["exponent"], run.seed)
        self.mirror = self.ref.Mirror(cols, run.seed)
        worker_pools(self.ref, self.mirror, self.zipf, run.seed,
                     p["workers"], p, cols)
        run.phase("traffic pools")
        self._ask("hello")
        run.phase("workers ready")
        # every worker, in lockstep: every element of every Get against the
        # reference's round rule
        checked = self._ask("check")
        run.compare.add("round_get_mismatch",
                        sum(r["mismatch"] for r in checked), 0)
        run.result["round_elements_checked"] = sum(r["elements"]
                                                   for r in checked)
        self._ask("warm")
        run.phase("round check and warm-up")

    def _ask(self, command, seconds=0.0):
        """One command to every worker, one answer from each. A worker that
        is silent past the phase's time is killed with the others, which
        ends the read and fails the run."""
        guard = threading.Timer(seconds + ANSWER_S, self._kill)
        guard.daemon = True
        guard.start()
        try:
            for proc in self.procs:
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
                    f"worker {w} ended (exit {self.procs[w].poll()})")
            if line.startswith('{"bench_worker"'):
                return json.loads(line)

    def window(self, seconds):
        from multiverso_tpu.dashboard import Dashboard

        run = self.run
        before = [Dashboard.counter_value(c) for c in SYNC_COUNTERS]
        t0 = time.perf_counter()
        reports = self._ask(f"go {seconds}", seconds)
        t1 = time.perf_counter()
        run.result["sync_counters"] = {
            c: Dashboard.counter_value(c) - was
            for c, was in zip(SYNC_COUNTERS, before)}
        n = self.params["rows_per_op"]
        self.reports = reports
        # the Adds each worker had made when it finished: the round rule's
        # n_v, and with the pools' order the count of every pooled Add
        self.finals = [r["made"] for r in reports]
        run.attempted = sum(r["attempted"] for r in reports)
        run.failed = sum(r["failed"] for r in reports)
        ops = sum(r["adds"] + r["gets"] for r in reports)
        # how the rate held over the window: pairs completed in each second
        by_second = np.bincount(
            np.concatenate([np.asarray(r["done_s"], int) for r in reports]),
            minlength=int(seconds))
        print(json.dumps({"pairs_by_second": by_second.tolist(),
                          "rounds_by_worker": self.finals,
                          "sync_counters": run.result["sync_counters"]}),
              flush=True)
        ms = {"add": [x for r in reports for x in r["add_ms"]],
              "get": [x for r in reports for x in r["get_ms"]]}
        run.result.update(
            ops=ops, adds=sum(r["adds"] for r in reports),
            gets=sum(r["gets"] for r in reports), rows=ops * n,
            add_rows=sum(r["adds"] for r in reports) * n,
            row_cols=self.shape["num_col"],
            elapsed_s=max(r["elapsed_s"] for r in reports), op_ms=ms)
        return t1

    def finish(self):
        run = self.run
        assert not any(r["backends_initialized"] for r in self.reports), \
            "a worker process started a JAX backend"
        # the kept Gets, every element, at their round and every worker's
        # final count: each worker compares its own
        verified = self._ask("verify " + json.dumps(self.finals))
        run.compare.add("window_get_mismatch",
                        sum(r["mismatch"] for r in verified), 0)
        run.result["gets_checked"] = sum(r["gets"] for r in verified)
        run.result["window_elements_checked"] = sum(r["elements"]
                                                    for r in verified)
        # the round rule at a round no worker reached: every Add made
        counts = self.mirror.round_counts(max(self.finals), self.finals)
        rows_table.final_checks(run, self.table, self.ref, self.mirror,
                                counts, self.init_sums, self.zipf,
                                self.shape, self.params["check_rows"])

    def end_to_end(self):
        """The four numbers of every row cell, all printed; BENCHMARK.json
        says which of them this cell is judged on."""
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

    w, p, cols, seed = spec["worker"], spec["params"], spec["cols"], \
        spec["seed"]
    ref = common.load_module("reference", spec["config"])
    zipf = common.ZipfRows(spec["rows"], spec["exponent"], seed)
    mirror = ref.Mirror(cols, seed)
    pools = worker_pools(ref, mirror, zipf, seed, p["workers"], p, cols)
    entries = p["pool"]
    pool = [(ids, ref.to_float(dk)) for ids, dk in pools[w]]
    unfinished = [None] * p["workers"]
    client = mv.remote_connect(spec["endpoint"])
    table = client.table(spec["table_id"])
    state = {"adds": 0}    # acknowledged since it connected: its round
    kept = []              # (round, pooled set, the Get) of the window

    def pair():
        i = state["adds"] % entries
        ids, delta = pool[i]
        t0 = time.perf_counter()
        table.add(delta, row_ids=ids)
        t1 = time.perf_counter()
        state["adds"] += 1
        got = table.get(ids)
        t2 = time.perf_counter()
        return i, got, (t1 - t0) * 1e3, (t2 - t1) * 1e3

    def wrong(i, got, round_, finals):
        want = mirror.rows_at_round(pool[i][0], round_, finals)
        return ref.mismatches(got, want), int(want.size)

    for line in sys.stdin:
        command = line.split(None, 1)
        if not command or command[0] == "quit":
            break
        if command[0] == "hello":
            _say(worker=w, ready=True)
        elif command[0] == "check":
            bad = elements = 0
            for _ in range(p["check_rounds"]):
                i, got, _, _ = pair()
                b, n = wrong(i, got, state["adds"], unfinished)
                bad, elements = bad + b, elements + n
            _say(worker=w, mismatch=bad, elements=elements)
        elif command[0] == "warm":
            for _ in range(p["warmup_rounds"]):
                pair()
            _say(worker=w, ready=True)
        elif command[0] == "go":
            seconds = float(command[1])
            rng = np.random.default_rng(common.mix_seed(seed, 77, w))
            sample_at = list(np.sort(rng.random(p["sampled_gets"])) * seconds)
            add_ms, get_ms, done_s = [], [], []
            attempted = failed = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                attempted += 2
                try:
                    i, got, a, g = pair()
                except Exception as e:  # an op that raised has failed, and
                    # a worker out of step with its clocks holds every round
                    failed += 1
                    print(f"benchmark worker {w}: op failed: {e!r}",
                          flush=True)
                    break
                add_ms.append(a)
                get_ms.append(g)
                done_s.append(time.perf_counter() - t0)
                if sample_at and time.perf_counter() - t0 >= sample_at[0]:
                    sample_at.pop(0)
                    kept.append((state["adds"], i, got))
            elapsed = time.perf_counter() - t0
            # this worker holds no round from here on: the others' last
            # rounds are served without it
            table.finish_train()
            _say(worker=w, adds=len(add_ms), gets=len(get_ms),
                 attempted=attempted, failed=failed, elapsed_s=elapsed,
                 made=state["adds"], add_ms=add_ms, get_ms=get_ms,
                 done_s=done_s,
                 backends_initialized=xla_bridge.backends_are_initialized())
        elif command[0] == "verify":
            finals = json.loads(command[1])
            bad = elements = 0
            for round_, i, got in kept:
                b, n = wrong(i, got, round_, finals)
                bad, elements = bad + b, elements + n
            _say(worker=w, mismatch=bad, gets=len(kept), elements=elements)
    client.close()


if __name__ == "__main__":
    worker_main(json.loads(sys.argv[sys.argv.index("--worker") + 1]))
