"""Traffic `row_ops_remote`: the chip's process serves the table
(`mv.serve`); worker processes pinned to the CPU, which never start a JAX
backend, connect over loopback TCP and each run a closed loop, no think
time: Add of a pooled set of distinct rows as numpy arrays, then Get of the
same rows. Upstream's worker-rank / server-rank split.

End to end: rows per second of acknowledged ops, all workers together, and
the median and 95th percentile of the time from each call to its reply on
the worker's own clock.

This file is also the worker: `python row_ops_remote.py --worker <json>`.
Parent and workers talk in lines: the parent writes a command to a worker's
stdin, the worker answers with one JSON line that starts {"bench_worker".
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark import common, rows_table  # noqa: E402

POOL_SALT = 1000  # worker w draws its pool from mix_seed(seed, POOL_SALT + w)


def worker_pools(ref, mirror, zipf, seed, workers, params, cols):
    """Every worker's pool, in worker order, registered with ``mirror``
    (pool index = worker * entries + entry). Parent and workers build the
    same pools from the seed."""
    pools = []
    for w in range(workers):
        rng = np.random.default_rng(common.mix_seed(seed, POOL_SALT + w))
        pools.append(rows_table.make_pool(
            ref, mirror, zipf, rng, params["pool"], params["rows_per_op"],
            cols))
    return pools


class Driver:
    def __init__(self, run):
        self.run = run
        self.shape, self.params = rows_table.sizes(run)
        self.procs = []

    def setup(self):
        import multiverso_tpu as mv

        run, p = self.run, self.params
        rows, cols = self.shape["num_row"], self.shape["num_col"]
        self.table, self.ref, self.init_sums = rows_table.start_table(
            run, self.shape, remote_workers=p["workers"])
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
        self._ask(range(p["workers"]), "hello")
        run.phase("workers ready")
        # replay: worker 0 alone, every Get against the reference
        replay = self._ask([0], "replay")[0]
        run.compare.add("replay_mismatch", replay["mismatch"], 0)
        self._ask(range(p["workers"]), "warm")
        run.phase("replay check and warm-up")

    def _ask(self, workers, command):
        workers = list(workers)
        for w in workers:
            self.procs[w].stdin.write(command + "\n")
            self.procs[w].stdin.flush()
        return [self._answer(w) for w in workers]

    def _answer(self, w):
        while True:
            line = self.procs[w].stdout.readline()
            if not line:
                raise RuntimeError(
                    f"worker {w} ended (exit {self.procs[w].poll()})")
            if line.startswith('{"bench_worker"'):
                return json.loads(line)

    def window(self, seconds):
        run = self.run
        t0 = time.perf_counter()
        reports = self._ask(range(len(self.procs)), f"go {seconds}")
        t1 = time.perf_counter()
        n = self.params["rows_per_op"]
        self.counts = [c for r in reports for c in r["counts"]]
        self.reports = reports
        run.attempted = sum(r["attempted"] for r in reports)
        run.failed = sum(r["failed"] for r in reports)
        ops = sum(r["adds"] + r["gets"] for r in reports)
        # how the rate held over the window: pairs completed in each second
        by_second = np.bincount(
            np.concatenate([np.asarray(r["done_s"], int) for r in reports]),
            minlength=int(seconds))
        print(json.dumps({"pairs_by_second": by_second.tolist()}), flush=True)
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
        run.compare.add("window_get_mismatch",
                        sum(r["get_mismatch"] for r in self.reports), 0)
        run.result["gets_checked"] = sum(r["gets_checked"]
                                         for r in self.reports)
        run.result["private_elements_checked"] = sum(
            r["elements_checked"] for r in self.reports)
        rows_table.final_checks(run, self.table, self.ref, self.mirror,
                                self.counts, self.init_sums, self.zipf,
                                self.shape, self.params["check_rows"])

    def end_to_end(self):
        return rows_table.end_to_end(self.run.result)

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
    mine = set(range(w * entries, (w + 1) * entries))
    pool = [(ids, ref.to_float(dk)) for ids, dk in pools[w]]
    counts = [0] * (entries * p["workers"])   # this worker's own Adds only
    client = mv.remote_connect(spec["endpoint"])
    table = client.table(spec["table_id"])
    state = {"pairs": 0}

    def pair():
        i = state["pairs"] % entries
        ids, delta = pool[i]
        t0 = time.perf_counter()
        table.add(delta, row_ids=ids)
        t1 = time.perf_counter()
        counts[w * entries + i] += 1
        got = table.get(ids)
        t2 = time.perf_counter()
        state["pairs"] += 1
        return i, got, (t1 - t0) * 1e3, (t2 - t1) * 1e3

    def wrong(i, got, then, private_only):
        ids = pool[i][0]
        want = mirror.rows_k(ids, then)
        if private_only:
            # rows that other workers' pools name move under this worker's
            # feet; the rows only its own pools name must hold its own
            # acknowledged Adds, the one just before this Get among them
            keep = mirror.owners(ids, mine)
            got, want = got[keep], want[keep]
        return ref.mismatches(got, want), int(want.size)

    for line in sys.stdin:
        command = line.split()
        if not command or command[0] == "quit":
            break
        if command[0] == "hello":
            _say(worker=w, ready=True)
        elif command[0] == "replay":
            bad = 0
            for _ in range(p["replay_ops"]):
                i, got, _, _ = pair()
                bad += wrong(i, got, counts, private_only=False)[0]
            _say(worker=w, mismatch=bad)
        elif command[0] == "warm":
            for _ in range(p["warmup_pairs"]):
                pair()
            _say(worker=w, ready=True)
        elif command[0] == "go":
            seconds = float(command[1])
            rng = np.random.default_rng(common.mix_seed(seed, 77, w))
            sample_at = list(np.sort(rng.random(p["sampled_gets"])) * seconds)
            add_ms, get_ms, done_s, kept = [], [], [], []
            attempted = failed = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                attempted += 2
                try:
                    i, got, a, g = pair()
                except Exception as e:  # an op that raised has failed
                    failed += 1
                    print(f"benchmark worker {w}: op failed: {e!r}",
                          flush=True)
                    if failed > 100:
                        break
                    continue
                add_ms.append(a)
                get_ms.append(g)
                done_s.append(time.perf_counter() - t0)
                if sample_at and time.perf_counter() - t0 >= sample_at[0]:
                    sample_at.pop(0)
                    kept.append((i, got, list(counts)))
            elapsed = time.perf_counter() - t0
            bad = checked = 0
            for i, got, then in kept:
                b, c = wrong(i, got, then, private_only=True)
                bad, checked = bad + b, checked + c
            _say(worker=w, adds=len(add_ms), gets=len(get_ms),
                 attempted=attempted, failed=failed, elapsed_s=elapsed,
                 counts=counts[w * entries:(w + 1) * entries],
                 add_ms=add_ms, get_ms=get_ms, done_s=done_s,
                 get_mismatch=bad,
                 gets_checked=len(kept), elements_checked=checked,
                 backends_initialized=xla_bridge.backends_are_initialized())
    client.close()


if __name__ == "__main__":
    worker_main(json.loads(sys.argv[sys.argv.index("--worker") + 1]))
