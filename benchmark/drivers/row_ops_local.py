"""Traffic `row_ops_local`: one in-process worker, closed loop, one op in
flight. Alternately Add and Get of the same pooled set of distinct rows
through the device path an in-process JAX worker uses (`add_device_async` +
`wait`, `get_device_async` + `wait_device`), deltas already on the device.

End to end: rows per second of acknowledged ops over the window, and the
median and 95th percentile of the time from each call to its completed
result on the device."""

import time

import numpy as np

from benchmark import common, rows_table


class Driver:
    def __init__(self, run):
        self.run = run
        self.shape, self.params = rows_table.sizes(run)
        self.kept = []

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import jax

        run, p = self.run, self.params
        rows, cols = self.shape["num_row"], self.shape["num_col"]
        self.table, self.ref, self.init_sums = rows_table.start_table(
            run, self.shape, remote_workers=1)
        self.zipf = common.ZipfRows(
            rows, run.config["row_popularity"]["exponent"], run.seed)
        rng = np.random.default_rng(common.mix_seed(run.seed, 1))
        self.mirror = self.ref.Mirror(cols, run.seed)
        pool = rows_table.make_pool(self.ref, self.mirror, self.zipf, rng,
                                    p["pool"], p["rows_per_op"], cols)
        self.pool = [(ids, jax.device_put(self.ref.to_float(dk)))
                     for ids, dk in pool]
        self.counts = [0] * len(self.pool)
        self.sample_at = np.sort(rng.random(p["sampled_gets"]))
        run.phase("traffic pools")

        for j in range(p["warmup_pairs"]):
            self._add(j % len(self.pool))
            self._get(j % len(self.pool))
        run.phase("warm-up")
        # replay: a fixed number of ops against the reference, every row
        # they touch and a seeded sample of rows none of them names
        wrong = 0
        for j in range(p["replay_ops"]):
            i = (j + 1) % len(self.pool)
            self._add(i)
            wrong += self._wrong(self._get(i), i, self.counts)
        named = np.concatenate([ids for ids, _ in self.pool])
        quiet = np.setdiff1d(rng.choice(rows, min(4096, rows // 2),
                                        replace=False), named)
        wrong += self.ref.mismatches(
            self.table.get(quiet.astype(np.int32)),
            self.ref.init_k(quiet, cols, run.seed))
        run.compare.add("replay_mismatch", wrong, 0)
        run.spans.samples.clear()
        run.phase("replay check")

    def _wrong(self, out, i, counts):
        ids = self.pool[i][0]
        got = np.asarray(out)[:len(ids), :self.shape["num_col"]]
        return self.ref.mismatches(got, self.mirror.rows_k(ids, counts))

    # -- the two ops, each timed to its completed result --------------------
    def _add(self, i):
        ids, delta = self.pool[i]
        with self.run.spans.span("bench.op.add"):
            self.table.wait(self.table.add_device_async(delta, ids))
            # wait() returns when the dispatcher has submitted the scatter;
            # the op is done when the table's new state is
            self.table.get_device().block_until_ready()
        self.counts[i] += 1

    def _get(self, i):
        ids = self.pool[i][0]
        with self.run.spans.span("bench.op.get"):
            out = self.table.wait_device(
                self.table.get_device_async(ids), ids)
            out.block_until_ready()
        return out

    # -- the window ------------------------------------------------------
    def window(self, seconds):
        run = self.run
        t0 = time.perf_counter()
        deadline = t0 + seconds
        sample_at = list(t0 + self.sample_at * seconds)
        pairs = 0
        while time.perf_counter() < deadline:
            i = pairs % len(self.pool)
            run.attempted += 2
            try:
                self._add(i)
                out = self._get(i)
            except Exception as e:  # an op that raised has failed
                run.failed += 1
                print(f"benchmark: op failed: {e!r}", flush=True)
                if run.failed > 100:
                    break
                continue
            pairs += 1
            if sample_at and time.perf_counter() >= sample_at[0]:
                sample_at.pop(0)
                self.kept.append((i, list(self.counts), out))
        t1 = time.perf_counter()
        n = self.params["rows_per_op"]
        ms = {name.rsplit(".", 1)[1]:
              [(b - a) * 1e3 for a, b in run.spans.samples.get(name, [])]
              for name in ("bench.op.add", "bench.op.get")}
        run.result.update(
            ops=2 * pairs, adds=pairs, gets=pairs, rows=2 * pairs * n,
            add_rows=pairs * n, row_cols=self.shape["num_col"],
            elapsed_s=t1 - t0, op_ms=ms)
        return t1

    # -- after the window -------------------------------------------------
    def finish(self):
        run = self.run
        wrong = sum(self._wrong(out, i, counts)
                    for i, counts, out in self.kept)
        run.compare.add("window_get_mismatch", wrong, 0)
        run.result["gets_checked"] = len(self.kept)
        self.kept.clear()
        rows_table.final_checks(run, self.table, self.ref, self.mirror,
                                self.counts, self.init_sums, self.zipf,
                                self.shape, self.params["check_rows"])

    def end_to_end(self):
        return rows_table.end_to_end(self.run.result)

    def close(self):
        import multiverso_tpu as mv
        mv.shutdown()
