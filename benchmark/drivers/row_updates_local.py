"""Traffic `row_updates_local`: one in-process worker, closed loop, one op in
flight, against a table whose optimizer the server runs. Alternately an Add
of a RAW gradient for a pooled set of distinct rows (`add_device_async` +
`wait` + the table's new state ready; the gradient already on the device)
and a Get of the same rows (`get_device_async` + `wait_device` + ready).

Every Add is an optimizer step, so Adds do not commute and the checks cannot
count them (`row_ops_local`'s do): the driver keeps the order in which its
Adds were acknowledged and the configuration's reference replays it, Add by
Add, for the rows a comparison asks for. Each comparison's number is the
largest error of the table's values in units of what the reference allows a
row that took so many steps (limit 1), or a count that must be 0 (the state
is compared for equality: the reference says why that holds).

The window, the Get, the end-to-end arithmetic and the close are
`row_ops_local`'s own (its Driver is this one's base): the two cells time
the same loop."""

import time

import numpy as np

from benchmark import common, rows_table

_plain = common.load_module("drivers", "row_ops_local")


class _Follower:
    """The reference's Replay of some rows with every pooled Add planned
    and its gradient rows cut out, fed the acknowledged Adds in order."""

    def __init__(self, ref, row_ids, pool_ids, pool_gk, cols, seed, opt):
        self.replay = ref.Replay(row_ids, cols, seed, opt["lr"], opt["eps"])
        self.plans = [self.replay.plan(ids) for ids in pool_ids]
        self._ref, self._gk, self._grads = ref, pool_gk, {}
        self.applied = 0

    def follow(self, history, upto):
        for i in history[self.applied:upto]:
            if i not in self._grads:  # cut out when the entry first comes
                self._grads[i] = self._ref.to_float(
                    self._gk[i][self.plans[i][1]])
            self.replay.add(self.plans[i], self._grads[i])
        self.applied = upto
        return self.replay


class Driver(_plain.Driver):
    def __init__(self, run):
        super().__init__(run)
        self.history = []   # the pool entry of every acknowledged Add

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import jax
        from multiverso_tpu.updaters import AddOption

        run, p = self.run, self.params
        rows, cols = self.shape["num_row"], self.shape["num_col"]
        self.opt = run.config["optimizer"]
        self.option = AddOption(learning_rate=self.opt["lr"],
                                rho=self.opt["eps"])
        self.table, self.ref, self.init_sums = rows_table.start_table(
            run, self.shape, remote_workers=1)
        self.state_name = run.config["state"]["name"]
        self.zipf = common.ZipfRows(
            rows, run.config["row_popularity"]["exponent"], run.seed)
        rng = np.random.default_rng(common.mix_seed(run.seed, 1))
        n = p["rows_per_op"]
        self.pool_ids = [self.zipf.distinct(rng, n) for _ in range(p["pool"])]
        self.pool_gk = [self.ref.grad_k(rng, n, cols) for _ in self.pool_ids]
        self.pool = [(ids, jax.device_put(self.ref.to_float(gk)))
                     for ids, gk in zip(self.pool_ids, self.pool_gk)]
        self.counts = [0] * len(self.pool)
        self.sample_at = np.sort(rng.random(p["sampled_gets"]))
        # the rows compared in and after the window, hot and cold
        hot = self.zipf.distinct(rng, min(p["check_rows"] // 2, rows // 4))
        cold = rng.choice(rows, p["check_rows"] - len(hot), replace=False)
        self.sample = np.unique(np.concatenate([hot, cold])).astype(np.int32)
        self._take = jax.jit(lambda state, ids: state[ids])
        run.phase("traffic pools")

        for j in range(p["warmup_pairs"]):
            self._add(j % len(self.pool))
            self._get(j % len(self.pool))
        run.phase("warm-up")
        # replay: a fixed number of pairs kept whole for the reference (every
        # element of each Get, the state of the rows it names, and how many
        # Adds had been acknowledged); the reference's own work on them is
        # done after the window (`finish`), so set-up pays for the program's
        # Adds and Gets alone. Then rows no pooled Add names, to the bit
        self.replayed = []
        for j in range(p["replay_ops"]):
            i = (j + 1) % len(self.pool)
            self._add(i)
            self.replayed.append((i, len(self.history),
                                  self._rows_of(self._get(i), i),
                                  self._state(self.pool_ids[i])))
        self.named = np.unique(np.concatenate(self.pool_ids))
        quiet = np.setdiff1d(rng.choice(rows, min(p["quiet_rows"], rows // 2),
                                        replace=False),
                             self.named).astype(np.int32)
        wrong = int((self.table.get(quiet)
                     != self.ref.init_rows(quiet, cols, run.seed)).sum())
        wrong += int(np.count_nonzero(self._state(quiet)))
        run.compare.add("replay_quiet_mismatch", wrong, 0)
        run.spans.samples.clear()
        run.phase("replay check")

    def _follower(self, row_ids):
        return _Follower(self.ref, row_ids, self.pool_ids, self.pool_gk,
                         self.shape["num_col"], self.run.seed, self.opt)

    def _rows_of(self, out, i):
        return np.asarray(out)[:len(self.pool_ids[i]),
                               :self.shape["num_col"]]

    def _state(self, row_ids):
        """The optimizer state of rows ``row_ids``, from the device array
        the table holds."""
        return np.asarray(self._take(
            self.table.get_state_device(self.state_name),
            np.asarray(row_ids, np.int32)))

    # -- the Add, timed to its completed result ----------------------------
    def _add(self, i):
        ids, grad = self.pool[i]
        with self.run.spans.span("bench.op.add"):
            self.table.wait(self.table.add_device_async(grad, ids,
                                                        self.option))
            # wait() returns when the dispatcher has submitted the update;
            # the op is done when the table's new rows and state are
            self.table.get_device().block_until_ready()
            self.table.get_state_device(self.state_name).block_until_ready()
        self.counts[i] += 1
        self.history.append(i)

    # -- after the window -------------------------------------------------
    def finish(self):
        import jax
        import jax.numpy as jnp

        run, ref = self.run, self.ref
        rows, cols = self.shape["num_row"], self.shape["num_col"]
        t = time.perf_counter()
        # the pairs kept before the window: every element of each Get and
        # the state of its rows, over every Add acknowledged by then
        every = self._follower(np.concatenate(
            [self.pool_ids[i] for i, *_ in self.replayed]))
        w_err, s_wrong = 0.0, 0
        for i, upto, got, state in self.replayed:
            replay = every.follow(self.history, upto)
            at, _ = every.plans[i]  # it holds every row of the entry
            w_err = max(w_err, ref.w_error(got, replay.w[at],
                                           replay.steps[at]))
            s_wrong += ref.s_mismatch(state, replay.s[at])
        run.compare.add("replay_w_error", w_err, 1.0)
        run.compare.add("replay_s_mismatch", s_wrong, 0)
        del every, self.replayed
        sampled = self._follower(self.sample)
        # kept Gets: their rows that the sample holds, against the
        # reference at the Adds acknowledged when each was issued
        err = 0.0
        for i, counts, out in self.kept:
            replay = sampled.follow(self.history, sum(counts))
            at, hit = sampled.plans[i]
            err = max(err, ref.w_error(self._rows_of(out, i)[hit],
                                       replay.w[at], replay.steps[at]))
        run.compare.add("window_get_error", err, 1.0)
        run.result["gets_checked"] = len(self.kept)
        self.kept.clear()
        # after the window: the sample, table and state
        replay = sampled.follow(self.history, len(self.history))
        want_w, want_s, steps = replay.rows(self.sample)
        run.compare.add("final_sample_w_error",
                        ref.w_error(self.table.get(self.sample), want_w,
                                    steps), 1.0)
        run.compare.add("final_sample_s_mismatch",
                        ref.s_mismatch(self._state(self.sample), want_s), 0)
        run.result["rows_checked"] = int(len(self.sample))
        run.result["rows_stepped"] = int((steps > 0).sum())
        run.result["adds_replayed"] = len(self.history)
        # exact: the rows no pooled Add names hold their initial values (the
        # int32 column sums on the device) and a state of zero
        unnamed = np.ones(rows, bool)
        unnamed[self.named] = False
        unit = ref.UNIT

        @jax.jit
        def untouched(data, state, unnamed):
            k = jnp.round(data[:rows, :cols] * unit).astype(jnp.int32)
            return (jnp.sum(jnp.where(unnamed[:, None], k, 0), axis=0),
                    jnp.count_nonzero(jnp.where(unnamed, state[:rows], 0)))

        sums, nonzero = untouched(
            self.table.get_device(),
            self.table.get_state_device(self.state_name), unnamed)
        want = np.array(self.init_sums, np.int64) - ref.init_k(
            self.named, cols, run.seed).sum(axis=0, dtype=np.int64)
        got = np.asarray(sums).astype(np.int64)
        run.compare.add("unnamed_checksum_mismatch_columns",
                        int(((got - want) % (1 << 32) != 0).sum()), 0)
        run.compare.add("unnamed_state_nonzero", int(nonzero), 0)
        run.result["rows_unnamed"] = int(unnamed.sum())
        print(f'{{"reference_replay_s": {time.perf_counter() - t:.3f}}}',
              flush=True)
