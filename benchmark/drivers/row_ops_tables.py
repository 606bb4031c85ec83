"""Traffic `row_ops_tables`: `row_ops_local` over every table the
configuration lists, the program started once. One in-process worker, closed
loop, one op in flight. A block is a trainer's data block in upstream
WordEmbedding's order: Get of the block's distinct rows from each table, then
Add to the same rows of each, through the device path an in-process JAX worker
uses (`get_device_async` + `wait_device`, `add_device_async` + `wait`), deltas
already on the device.

End to end: rows per second of acknowledged ops over the window (the rows of
every op of a block), and the median and 95th percentile of the time from each
call to its completed result on the device; the tables have one shape, so
their Gets pool into one median and their Adds into another."""

import time

import numpy as np

from benchmark import common, rows_table


class _Table:
    """One of the configuration's tables: its worker proxy, its mirror in
    the reference, its pooled (ids, device delta) sets and how often each
    was acknowledged."""

    def __init__(self, name, proxy, mirror, init_sums):
        self.name, self.proxy, self.mirror = name, proxy, mirror
        self.init_sums = init_sums
        self.pool, self.counts = [], []


class _Scoped:
    """`run` as `rows_table.final_checks` takes it, with every comparison
    named after the table it is of."""

    def __init__(self, run, suffix):
        self.seed, self.result, self.compare = run.seed, run.result, self
        self._compare, self._suffix = run.compare, suffix

    def add(self, name, value, limit):
        return self._compare.add(f"{name}.{self._suffix}", value, limit)


class Driver:
    def __init__(self, run):
        self.run = run
        self.shape, self.params = rows_table.sizes(run)
        self.kept = []

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import jax

        import multiverso_tpu as mv

        run, p, shape = self.run, self.params, self.shape
        rows, cols = shape["num_row"], shape["num_col"]
        self.ref = ref = common.load_module("reference", run.cell["config"])
        mv.init(mesh_shape=str(run.chips), remote_workers=1,
                **rows_table.INIT_FLAGS)
        run.phase("program start")
        self.tables = []
        for index, name in enumerate(run.config["tables"]):
            init, sums = ref.init_table(rows, cols, run.seed, index)
            run.phase(f"initial values, {name}")
            proxy = mv.create_table(
                shape["kind"], rows, cols, np.dtype(shape["dtype"]),
                updater_type=shape["updater_type"], init_value=init)
            del init
            run.phase(f"create_table, {name}")
            self.tables.append(_Table(name, proxy,
                                      ref.Mirror(cols, run.seed, index), sums))
        self.zipf = common.ZipfRows(
            rows, run.config["row_popularity"]["exponent"], run.seed)
        rng = np.random.default_rng(common.mix_seed(run.seed, 1))
        for t in self.tables:
            pool = rows_table.make_pool(ref, t.mirror, self.zipf, rng,
                                        p["pool"], p["rows_per_op"], cols)
            t.pool = [(ids, jax.device_put(ref.to_float(dk)))
                      for ids, dk in pool]
            t.counts = [0] * len(t.pool)
        self.sample_at = np.sort(rng.random(p["sampled_gets"]))
        run.phase("traffic pools")

        for j in range(p["warmup_blocks"]):
            self._block(j % p["pool"])
        run.phase("warm-up")
        # replay: a fixed number of blocks against the reference, every row
        # their Gets return, then every row their Adds touched and a seeded
        # sample of rows that no op names, table by table
        wrong = {t.name: 0 for t in self.tables}
        for j in range(p["replay_blocks"]):
            i = (j + 1) % p["pool"]
            counts = [list(t.counts) for t in self.tables]
            for t, out, was in zip(self.tables, self._block(i), counts):
                wrong[t.name] += self._wrong(t, out, i, was)
                wrong[t.name] += self._wrong(t, self._get(t, i), i, t.counts)
        for index, t in enumerate(self.tables):
            named = np.concatenate([ids for ids, _ in t.pool])
            quiet = np.setdiff1d(rng.choice(rows, min(4096, rows // 2),
                                            replace=False), named)
            wrong[t.name] += ref.mismatches(
                t.proxy.get(quiet.astype(np.int32)),
                ref.init_k(quiet, cols, run.seed, index))
            run.compare.add(f"replay_mismatch.{t.name}", wrong[t.name], 0)
        run.spans.samples.clear()
        run.phase("replay check")

    def _wrong(self, t, out, i, counts):
        ids = t.pool[i][0]
        got = np.asarray(out)[:len(ids), :self.shape["num_col"]]
        return self.ref.mismatches(got, t.mirror.rows_k(ids, counts))

    # -- the two ops, each timed to its completed result --------------------
    def _add(self, t, i):
        ids, delta = t.pool[i]
        with self.run.spans.span("bench.op.add"):
            t.proxy.wait(t.proxy.add_device_async(delta, ids))
            # wait() returns when the dispatcher has submitted the scatter;
            # the op is done when the table's new state is
            t.proxy.get_device().block_until_ready()
        t.counts[i] += 1

    def _get(self, t, i):
        ids = t.pool[i][0]
        with self.run.spans.span("bench.op.get"):
            out = t.proxy.wait_device(t.proxy.get_device_async(ids), ids)
            out.block_until_ready()
        return out

    def _block(self, i):
        """One data block: the Gets of every table, then the Adds; the
        Gets' results, in the tables' order."""
        outs = [self._get(t, i) for t in self.tables]
        for t in self.tables:
            self._add(t, i)
        return outs

    # -- the window ------------------------------------------------------
    def window(self, seconds):
        run = self.run
        ops_a_block = 2 * len(self.tables)
        t0 = time.perf_counter()
        deadline = t0 + seconds
        sample_at = list(t0 + self.sample_at * seconds)
        blocks = 0
        while time.perf_counter() < deadline:
            i = blocks % self.params["pool"]
            run.attempted += ops_a_block
            counts = [list(t.counts) for t in self.tables]
            try:
                outs = self._block(i)
            except Exception as e:  # an op that raised has failed
                run.failed += 1
                print(f"benchmark: op failed: {e!r}", flush=True)
                if run.failed > 100:
                    break
                continue
            blocks += 1
            if sample_at and time.perf_counter() >= sample_at[0]:
                sample_at.pop(0)
                self.kept.append((i, counts, outs))
        t1 = time.perf_counter()
        n, tables = self.params["rows_per_op"], len(self.tables)
        ms = {name.rsplit(".", 1)[1]:
              [(b - a) * 1e3 for a, b in run.spans.samples.get(name, [])]
              for name in ("bench.op.add", "bench.op.get")}
        run.result.update(
            blocks=blocks, ops=ops_a_block * blocks, adds=tables * blocks,
            gets=tables * blocks, rows=ops_a_block * blocks * n,
            add_rows=tables * blocks * n, get_rows=tables * blocks * n,
            row_cols=self.shape["num_col"], elapsed_s=t1 - t0, op_ms=ms)
        return t1

    # -- after the window -------------------------------------------------
    def finish(self):
        run = self.run
        wrong = {t.name: 0 for t in self.tables}
        for i, counts, outs in self.kept:
            for t, out, was in zip(self.tables, outs, counts):
                wrong[t.name] += self._wrong(t, out, i, was)
        for t in self.tables:
            run.compare.add(f"window_get_mismatch.{t.name}", wrong[t.name], 0)
        run.result["gets_checked"] = len(self.kept) * len(self.tables)
        self.kept.clear()
        for t in self.tables:
            # the pooled deltas have done their work; the whole-table
            # checksum gets their room on the device
            t.pool = [(ids, None) for ids, _ in t.pool]
        for t in self.tables:
            rows_table.final_checks(_Scoped(run, t.name), t.proxy, self.ref,
                                    t.mirror, t.counts, t.init_sums,
                                    self.zipf, self.shape,
                                    self.params["check_rows"])

    def end_to_end(self):
        return rows_table.end_to_end(self.run.result)

    def close(self):
        import multiverso_tpu as mv
        mv.shutdown()
