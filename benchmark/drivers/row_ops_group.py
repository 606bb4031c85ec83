"""Traffic `row_ops_group`: `row_ops_local` over a table group. One in-process
worker, closed loop, one op in flight. A step is a batch of samples, each
naming one row in every table of the configuration; the step is ONE group
Add of the distinct rows it names in all tables (`add_device_async` + `wait`
+ the slab's new state ready, the delta already on the device), then ONE
group Get of the same rows (`get_device_async` + `wait_device` + ready):
every op of the cell names rows of every table.

Every pooled step has its own count of rows (the distinct rows among its
draws), as every step of a trainer has. The deltas are held at ONE shape, as a
trainer's are: the `(bucket, columns)` of the device Get's result whose
gradient a delta is, the power of two above the pool's largest count. Their
rows past the step's ids hold values like the rest (a gradient of the Get's
sentinel tail is not zero) and must not land anywhere. `warmup_pairs` is 3
for a pool of 8, the steps of the fewest and of the most rows first: five of
the pool's counts are first seen inside the window, so a program keyed by
the count of rows would compile there and `window_compiles.rows` would say
so. `replay_ops` and `check_rows` are `bulk-rows`'s, under its names. The row
popularity is `common.ZipfRows`' law (Zipf over a seeded permutation, the
same seeds), drawn here from the exponent and the seed alone, so that an
edit to that class cannot move this cell's traffic.

End to end: rows per second of acknowledged ops over the window, and the
median and 95th percentile of the time from each call to its completed result
on the device (`rows_table.end_to_end`).

`correct`, every comparison exact (limit 0): the reference keeps a mirror a
table (the hash takes the table's index, so a row read from another table is
a wrong value), and every element of a Get is compared with the table its
segment belongs to. `replay_mismatch` (before the window: every element of
whole Gets, and rows no op names), `window_get_mismatch` (Gets kept at seeded
times, at the counts acknowledged when each was issued),
`final_sample_mismatch` (after the window, through the group's host Get: a
seeded sample of every table, hot and cold, with the first and the last row
of every table in it and the small tables whole), `checksum_mismatch_columns`
(int32 column sums of the whole slab on the device), and through the members'
own proxies `member_edge_mismatch` (the first and the last row of every
table, by the table's own ids) and `small_member_mismatch` (every table of at
most `WHOLE` rows read whole). Where one fails, a `{"members_wrong": ...}`
line names the tables."""

import json
import time

import numpy as np

from benchmark import common, rows_table

WHOLE = 64      # tables of at most this many rows are compared whole
# rows a host Get of the final sample names: under the group op's limit
SAMPLE_OP_ROWS = 100_000


class TableRows:
    """One table's row popularity: rank r has weight r**-exponent and lands
    on row perm[r] of a seeded permutation of the table's own ids."""

    def __init__(self, rows, exponent, seed):
        pmf = np.arange(1, rows + 1, dtype=np.float64) ** -float(exponent)
        self.cdf = np.cumsum(pmf / pmf.sum())
        self.perm = np.random.default_rng(common.mix_seed(
            seed, 0x7065726D)).permutation(rows).astype(np.int32)
        self.rows = rows

    def drawn(self, rng, draws):
        """The distinct rows among ``draws`` draws, in draw order (a
        trainer sums its duplicates before it sends)."""
        ranks = np.minimum(np.searchsorted(self.cdf, rng.random(draws)),
                           self.rows - 1)
        _, first = np.unique(ranks, return_index=True)
        return self.perm[ranks[np.sort(first)]]

    def hottest(self, n):
        return self.perm[:n]


class Driver:
    def __init__(self, run):
        self.run = run
        self.params = {k: v for k, v in run.traffic.items()
                       if k != "rehearse"}
        cap = run.config["max_ind_range"]
        if run.rehearse:
            small = dict(run.traffic.get("rehearse", {}))
            cap = small.pop("max_ind_range", cap)
            self.params.update(small)
        self.num_rows = [min(n, cap)
                         for n in run.config["num_rows_published"]]
        if not run.rehearse and self.num_rows != run.config["num_rows"]:
            raise ValueError("the configuration's num_rows are not its "
                             "published counts under its max_ind_range")
        self.cols = run.config["table"]["num_col"]
        self.offsets = np.concatenate([[0], np.cumsum(self.num_rows)])
        self.kept = []
        self.wrong_in = {}    # comparison -> tables it found wrong

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import jax

        import multiverso_tpu as mv

        run, p, cols = self.run, self.params, self.cols
        shape = run.config["table"]
        self.ref = ref = common.load_module("reference", run.cell["config"])
        mv.init(mesh_shape=str(run.chips), remote_workers=1,
                **rows_table.INIT_FLAGS)
        run.phase("program start")
        tables = range(len(self.num_rows))
        self.init_sums = np.zeros(cols, np.int64)
        made = [0]

        def source(table):
            def rows(lo, n):
                values, sums = ref.init_rows(lo, n, cols, run.seed, table)
                self.init_sums += sums
                made[0] += n
                return values
            return rows

        self.group = mv.create_table(
            shape["kind"], self.num_rows, cols, np.dtype(shape["dtype"]),
            updater_type=shape["updater_type"],
            init_values=[source(t) for t in tables])
        if made[0] != self.offsets[-1]:
            raise RuntimeError(f"{made[0]} initial rows were asked for, the "
                               f"tables hold {self.offsets[-1]}")
        run.phase("create_table")
        exponent = run.config["row_popularity"]["exponent"]
        self.zipf = [TableRows(n, exponent, (int(run.seed) << 8) | t)
                     for t, n in enumerate(self.num_rows)]
        rng = np.random.default_rng(common.mix_seed(run.seed, 1))
        self.mirrors = [ref.Mirror(cols, run.seed, t) for t in tables]
        steps = [[z.drawn(rng, p["samples_per_step"]) for z in self.zipf]
                 for _ in range(p["pool"])]
        # every delta at one shape: the bucket of the largest step's Get
        held = 1 << max(sum(map(len, parts)) for parts in steps).bit_length()
        self.pool = []
        for parts in steps:
            lengths = np.array([len(part) for part in parts])
            at = np.concatenate([[0], np.cumsum(lengths)])
            dk = ref.delta_k(rng, held, cols)
            for t in tables:
                self.mirrors[t].add_pool(parts[t], dk[at[t]:at[t + 1]])
            self.pool.append((np.concatenate(parts).astype(np.int32),
                              lengths, at, jax.device_put(ref.to_float(dk))))
        self.counts = [0] * len(self.pool)
        self.sample_at = np.sort(rng.random(p["sampled_gets"]))
        pool_rows = [int(at[-1]) for *_, at, _ in self.pool]
        print(json.dumps({"pool_rows": pool_rows, "delta_rows": held,
                          "segment_rows": self.pool[0][1].tolist()}),
              flush=True)
        run.phase("traffic pools")

        # the steps of the fewest and of the most rows first: between them
        # lies every shape a step of the pool can ask of a Get
        order = np.argsort(pool_rows)
        order = [int(order[0]), int(order[-1]), *map(int, order[1:-1])]
        for j in range(p["warmup_pairs"]):
            self._add(order[j % len(order)])
            self._get(order[j % len(order)])
        run.phase("warm-up")
        # replay: a fixed number of steps against the reference, every row
        # their Gets return, and a seeded sample of rows none of them names
        wrong = 0
        for j in range(p["replay_ops"]):
            i = (j + 1) % len(self.pool)
            self._add(i)
            wrong += self._wrong("replay_mismatch", self._get(i), i,
                                 self.counts)
        quiet = []
        for t, rows in enumerate(self.num_rows):
            named = np.concatenate([ids[at[t]:at[t + 1]]
                                    for ids, _, at, _ in self.pool])
            quiet.append(np.setdiff1d(
                rng.choice(rows, min(256, rows), replace=False), named))
        wrong += self._read_and_compare("replay_mismatch", quiet,
                                        [0] * len(self.pool))
        run.compare.add("replay_mismatch", wrong, 0)
        run.spans.samples.clear()
        run.phase("replay check")

    # -- comparisons -------------------------------------------------------
    def _note(self, name, table, wrong):
        if wrong:
            self.wrong_in.setdefault(name, set()).add(table)
        return wrong

    def _wrong(self, name, out, i, counts):
        """Elements of a kept device Get of pooled set ``i`` that differ
        from the reference, every segment against its own table."""
        ids, _, at, _ = self.pool[i]
        got = np.asarray(out)[:at[-1], :self.cols]
        return sum(self._note(name, t, self.ref.mismatches(
            got[at[t]:at[t + 1]],
            self.mirrors[t].rows_k(ids[at[t]:at[t + 1]], counts)))
            for t in range(len(self.num_rows)))

    def _read_and_compare(self, name, parts, counts):
        """``parts`` (one array of its own row ids a table) read through the
        group's host Get, in ops of at most SAMPLE_OP_ROWS rows, every
        segment against its own table."""
        pieces = -(-sum(len(part) for part in parts) // SAMPLE_OP_ROWS)
        wrong = 0
        for k in range(max(pieces, 1)):
            some = [part[k::pieces].astype(np.int32) for part in parts]
            got, at = self.group.get(some)
            wrong += sum(self._note(name, t, self.ref.mismatches(
                got[at[t]:at[t + 1]], self.mirrors[t].rows_k(some[t],
                                                             counts)))
                for t in range(len(parts)))
        return wrong

    # -- the two ops, each timed to its completed result --------------------
    def _add(self, i):
        ids, lengths, _, delta = self.pool[i]
        with self.run.spans.span("bench.op.add"):
            self.group.wait(self.group.add_device_async(delta, ids, lengths))
            # wait() returns when the dispatcher has submitted the scatter;
            # the op is done when the slab's new state is
            self.group.get_device().block_until_ready()
        self.counts[i] += 1

    def _get(self, i):
        ids, lengths, _, _ = self.pool[i]
        with self.run.spans.span("bench.op.get"):
            out, _ = self.group.wait_device(
                self.group.get_device_async(ids, lengths))
            out.block_until_ready()
        return out

    # -- the window ------------------------------------------------------
    def window(self, seconds):
        run = self.run
        t0 = time.perf_counter()
        deadline = t0 + seconds
        sample_at = list(t0 + self.sample_at * seconds)
        pairs = rows = 0
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
            rows += int(self.pool[i][2][-1])
            if sample_at and time.perf_counter() >= sample_at[0]:
                sample_at.pop(0)
                self.kept.append((i, list(self.counts), out))
        t1 = time.perf_counter()
        ms = {name.rsplit(".", 1)[1]:
              [(b - a) * 1e3 for a, b in run.spans.samples.get(name, [])]
              for name in ("bench.op.add", "bench.op.get")}
        run.result.update(
            ops=2 * pairs, adds=pairs, gets=pairs, rows=2 * rows,
            add_rows=rows, get_rows=rows, row_cols=self.cols,
            tables=len(self.num_rows), elapsed_s=t1 - t0, op_ms=ms)
        return t1

    # -- after the window -------------------------------------------------
    def finish(self):
        import jax
        import jax.numpy as jnp

        run, ref, counts = self.run, self.ref, self.counts
        run.compare.add("window_get_mismatch", sum(
            self._wrong("window_get_mismatch", out, i, was)
            for i, was, out in self.kept), 0)
        run.result["gets_checked"] = len(self.kept)
        self.kept.clear()
        # the pooled deltas have done their work; the checksum gets their
        # room on the device
        self.pool = [(ids, lengths, at, None)
                     for ids, lengths, at, _ in self.pool]

        # a seeded sample of every table in proportion to its rows, hot and
        # cold; the edges of every table; the small tables whole
        rng = np.random.default_rng(common.mix_seed(run.seed, 0x636865636B))
        total, sample = self.offsets[-1], []
        for t, rows in enumerate(self.num_rows):
            if rows <= WHOLE:
                sample.append(np.arange(rows))
                continue
            share = min(rows, max(4, self.params["check_rows"] * rows
                                  // total))
            hot = self.zipf[t].hottest(min(share // 2, rows // 4))
            cold = rng.choice(rows, share - len(hot), replace=False)
            sample.append(np.unique(np.concatenate(
                [[0, rows - 1], hot, cold])))
        run.compare.add("final_sample_mismatch", self._read_and_compare(
            "final_sample_mismatch", sample, counts), 0)
        run.result["rows_checked"] = int(sum(len(s) for s in sample))

        # through the members' own proxies, by their own ids
        edges = small = 0
        for t, rows in enumerate(self.num_rows):
            member, ends = self.group.tables[t], np.array([0, rows - 1])
            edges += self._note("member_edge_mismatch", t, ref.mismatches(
                member.get(ends), self.mirrors[t].rows_k(ends, counts)))
            if rows <= WHOLE:
                small += self._note(
                    "small_member_mismatch", t, ref.mismatches(
                        member.get(), self.mirrors[t].rows_k(
                            np.arange(rows), counts)))
        run.compare.add("member_edge_mismatch", edges, 0)
        run.compare.add("small_member_mismatch", small, 0)

        unit, rows, cols = ref.UNIT, int(total), self.cols

        @jax.jit
        def column_sums(data):
            return jnp.sum(jnp.round(data[:rows, :cols] * unit).astype(
                jnp.int32), axis=0)

        got = np.asarray(column_sums(self.group.get_device())).astype(
            np.int64)
        want = sum((m.column_sums(np.zeros(cols, np.int64), counts)
                    for m in self.mirrors), self.init_sums)
        run.compare.add("checksum_mismatch_columns",
                        int(((got - want) % (1 << 32) != 0).sum()), 0)
        if self.wrong_in:
            print(json.dumps({"members_wrong": {
                name: sorted(tables)
                for name, tables in self.wrong_in.items()}}), flush=True)

    def end_to_end(self):
        return rows_table.end_to_end(self.run.result)

    def close(self):
        import multiverso_tpu as mv
        mv.shutdown()
