"""Traffic `key_updates_local`: one in-process worker, closed loop, one op in
flight, against a keyed FTRL table (`mv.create_table("ftrl", key_space,
...)`: `(z, n)` a key on the device, no weight array). A step is upstream's
order (`ps_model.cpp`: pull, compute, push): ONE keyed Get of the distinct
keys the step's samples name (`get_device_async` + `wait_device` + the
weights ready), then ONE keyed Add of a raw gradient for the same keys
(`add_device_async` + `wait` + the table's new `z` and `n` ready; the
gradient already on the device, held at the Get's bucket, its values past
the keys not zero and stepping nothing).

The keys: a sample names one value of each categorical feature (Zipf over a
seeded bijection of the feature's ids), every integer feature and the bias.
Nothing of a feature's size is built on the host: a rank comes from the
inverse of the cumulative Zipf weights (exact up to `_TABLE_RANKS`, the
logarithm the harmonic sum tends to beyond) and goes to an id through an
affine map modulo the feature's count.

Every Add is an FTRL step, so Adds do not commute and the checks cannot
count them: the driver keeps the order in which its Adds were acknowledged
and the configuration's reference replays it, Add by Add, for the keys a
comparison asks for. Each comparison's number is the largest error in units
of what the reference allows a key that took so many steps (limit 1), or a
count that must be 0 (`n` is compared for equality: the reference says why
that holds)."""

import json
import math
import time

import numpy as np

from benchmark import common, rows_table

# ranks whose cumulative Zipf weight is tabulated; later ranks come from
# the logarithm
_TABLE_RANKS = 1 << 16
# keys a piece of the device's own pass over the state covers, at most
_PIECE = 1 << 25


class ZipfValues:
    """Value popularity Zipf(exponent 1.0 only) over a seeded bijection of
    ``count`` ids, with no array of ``count``: rank ``r`` (from 1) has
    weight ``1 / r`` and lands on id ``(a * (r - 1) + b) mod count``, ``a``
    coprime to ``count``."""

    def __init__(self, count, rng):
        self.count = count
        head = min(count, _TABLE_RANKS)
        self._head = np.cumsum(1.0 / np.arange(1, head + 1))
        # H(r) past the table: H(head) + ln((r + 1/2) / (head + 1/2)), the
        # midpoint rule, off by under 1e-11 of a weight there
        self._total = self._head[-1] + math.log((count + 0.5) / (head + 0.5))
        self._a = 1
        while count > 2 and (self._a == 1
                             or math.gcd(self._a, count) != 1):
            self._a = int(rng.integers(1, count))
        self._b = int(rng.integers(0, count))

    def draw(self, rng, n):
        """ids of ``n`` draws, with replacement."""
        t = rng.random(n) * self._total
        rank = np.searchsorted(self._head, t).astype(np.int64)  # from 0
        late = t > self._head[-1]
        if late.any():
            head = len(self._head)
            rank[late] = np.ceil(
                (head + 0.5) * np.exp(t[late] - self._head[-1]) - 1.5)
        np.minimum(rank, self.count - 1, out=rank)
        return (self._a * rank + self._b) % self.count


class _Follower:
    """The reference's Replay of some keys with every pooled Add planned
    and its gradient cut out, fed the acknowledged Adds in order."""

    def __init__(self, ref, keys, pool_keys, pool_gk, seed, opt):
        self.replay = ref.Replay(keys, seed, opt)
        self.plans = [self.replay.plan(k) for k in pool_keys]
        self._grads = [ref.to_float(gk[plan[1]])
                       for gk, plan in zip(pool_gk, self.plans)]
        self.applied = 0

    def follow(self, history, upto):
        for i in history[self.applied:upto]:
            self.replay.add(self.plans[i], self._grads[i])
        self.applied = upto
        return self.replay


class Driver:
    def __init__(self, run):
        self.run = run
        self.params = {k: v for k, v in run.traffic.items()
                       if k != "rehearse"}
        counts = list(run.config["categorical_values"])
        if run.rehearse:
            small = dict(run.traffic.get("rehearse", {}))
            cap = small.pop("max_ind_range")
            counts = [min(c, cap) for c in counts]
            self.params.update(small)
        self.counts = counts
        self.bases = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        # the keys every step names: the integer features and the bias
        self.always = (self.bases[-1] + np.arange(
            run.config["integer_features"] + run.config["bias_keys"]))
        self.key_space = int(self.bases[-1] + len(self.always))
        if not run.rehearse and \
                self.key_space != run.config["table"]["key_space"]:
            raise ValueError("the configuration's counts and key_space differ")
        self.opt = dict(run.config["optimizer"])
        self.history = []   # the pool entry of every acknowledged Add
        self.kept = []
        self._changed = None

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp

        import multiverso_tpu as mv

        run, p = self.run, self.params
        self.ref = ref = common.load_module("reference", run.cell["config"])
        mv.init(mesh_shape=str(run.chips), remote_workers=1,
                **rows_table.INIT_FLAGS)
        run.phase("program start")
        seed = run.seed
        self.piece = piece = min(_PIECE, self.key_space)

        @jax.jit
        def init_piece(lo):
            # the reference's hash, in the same uint32 arithmetic, on the
            # device: the host never holds a piece of the key space
            return ref.init_zn(lo + jnp.arange(piece, dtype=jnp.int32),
                               seed, jnp)

        self._init_piece = init_piece
        self.table = mv.create_table(
            "ftrl", self.key_space,
            init=lambda lo, count: tuple(
                s[:count] for s in init_piece(jnp.int32(lo))), **self.opt)
        self.table.get_state_device("n").block_until_ready()
        run.phase("create_table")
        # what the block source gave, over every key and before any op: one
        # pass on the device, which is also the first use of the program
        # that counts the changed keys after the window
        run.compare.add("created_state_mismatch", self._differing(), 0)
        run.phase("created state check")

        rng = np.random.default_rng(common.mix_seed(seed, 1))
        self.zipf = [ZipfValues(c, rng) for c in self.counts]
        self.pool_keys = [self._step_keys(rng) for _ in range(p["pool"])]
        self.pool_gk = [ref.grad_k(rng, len(k)) for k in self.pool_keys]
        self.bucket = int(self.table.wait_device(
            self.table.get_device_async(self.pool_keys[0])).shape[0])
        if any(len(k) >= self.bucket for k in self.pool_keys):
            raise ValueError("a pooled step's keys do not share one bucket")
        self.pool = []
        for keys, gk in zip(self.pool_keys, self.pool_gk):
            # a trainer's buffer at the Get's bucket: past the keys it holds
            # whatever the last step left, here sevens
            grad = np.full(self.bucket, 7.0, np.float32)
            grad[:len(keys)] = ref.to_float(gk)
            self.pool.append((keys, jax.device_put(grad)))
        self.sample_at = np.sort(rng.random(p["sampled_gets"]))
        self.sample = self._sample(rng)
        self._take = jax.jit(lambda state, keys: state[keys])
        self.named = np.unique(np.concatenate(self.pool_keys))
        run.phase("traffic pools")
        # the reference's side of every comparison: its replays at their
        # initial state, each pooled Add planned and its gradient cut out
        every = self._follower(self.named)
        self.sampled = self._follower(self.sample)
        run.phase("reference plans")

        # warm-up: the fewest and the most keys first, so that every count
        # of slots the pool launches has compiled
        by_count = sorted(range(len(self.pool)),
                          key=lambda i: len(self.pool_keys[i]))
        order = [by_count[0], by_count[-1]] + by_count[1:-1]
        for j in range(p["warmup_pairs"]):
            self._get(order[j % len(order)])
            self._add(order[j % len(order)])
        run.phase("warm-up")
        # replay: pairs kept whole and compared with the reference here,
        # before the window: every weight of each Get at the Adds
        # acknowledged when it was issued, and the state of its keys after
        # its Add. Then keys no pooled Add names, to the bit
        w_err = z_err = 0.0
        n_wrong = 0
        for j in range(p["replay_ops"]):
            i = (j + 1) % len(self.pool)
            at, _ = every.plans[i]  # it holds every key of the entry
            got = np.asarray(self._get(i))[:len(self.pool_keys[i])]
            replay = every.follow(self.history, len(self.history))
            w_err = max(w_err, ref.w_error(
                got, ref.weights(replay.z[at], replay.n[at], **replay.opt),
                replay.z[at], replay.steps[at], self.opt))
            self._add(i)
            z, n = self._state(self.pool_keys[i])
            replay = every.follow(self.history, len(self.history))
            z_err = max(z_err, ref.z_error(z, replay.z[at],
                                           replay.steps[at]))
            n_wrong += ref.n_mismatch(n, replay.n[at])
        run.compare.add("replay_w_error", w_err, 1.0)
        run.compare.add("replay_z_error", z_err, 1.0)
        run.compare.add("replay_n_mismatch", n_wrong, 0)
        del every
        quiet = np.setdiff1d(
            rng.choice(self.key_space,
                       min(p["quiet_keys"], self.key_space // 2),
                       replace=False), self.named).astype(np.int32)
        z, n = self._state(quiet)
        want_z, want_n = ref.init_zn(quiet, seed)
        run.compare.add("replay_quiet_mismatch",
                        ref.n_mismatch(z, want_z) + ref.n_mismatch(n, want_n),
                        0)
        run.phase("replay check")
        # what the checks after the window will read, read once here, at
        # the Adds acknowledged so far: the sample's weights through the
        # table's host Get, and on the device every key no pooled set
        # names, which the padded slots and the gradients' tails must have
        # left alone
        replay = self.sampled.follow(self.history, len(self.history))
        want_z, _, want_w, steps = replay.state(self.sample)
        run.compare.add("start_sample_w_error", ref.w_error(
            self.table.get(self.sample), want_w, want_z, steps, self.opt),
            1.0)
        run.compare.add("start_unnamed_mismatch", self._unnamed_changed(), 0)
        run.spans.samples.clear()
        run.phase("start state check")

    def _step_keys(self, rng):
        """The distinct keys one step of samples names, int32, feature by
        feature and sorted within each (what `np.unique` of a batch's
        lookups gives a trainer), then the keys every step names."""
        n = self.params["samples_per_step"]
        parts = [base + np.unique(zipf.draw(rng, n))
                 for base, zipf in zip(self.bases, self.zipf)]
        return np.concatenate(parts + [self.always]).astype(np.int32)

    def _sample(self, rng):
        """The keys compared in and after the window: hot (one more step's
        keys, thinned) and cold, the keys every step names, the first and
        the last key of every feature, the features of at most 64 values
        whole."""
        want = self.params["check_keys"]
        hot = self._step_keys(rng)
        hot = rng.choice(hot, min(want // 2, len(hot)), replace=False)
        cold = rng.choice(self.key_space, want - len(hot), replace=False)
        edges = np.concatenate([self.bases[:-1], self.bases[1:] - 1])
        small = [base + np.arange(c) for base, c in
                 zip(self.bases, self.counts) if c <= 64]
        return np.unique(np.concatenate(
            [hot, cold, edges, self.always, *small])).astype(np.int32)

    def _follower(self, keys):
        return _Follower(self.ref, keys, self.pool_keys, self.pool_gk,
                         self.run.seed, self.opt)

    def _state(self, keys):
        """``(z, n)`` of ``keys``, from the device arrays the table
        holds."""
        keys = np.asarray(keys, np.int32)
        # at the next power of two (the rest key 0): one program a
        # comparison, whatever count of keys the seed drew
        padded = np.zeros(1 << max(len(keys) - 1, 0).bit_length(), np.int32)
        padded[:len(keys)] = keys
        return tuple(np.asarray(self._take(
            self.table.get_state_device(name), padded))[:len(keys)]
            for name in "zn")

    # -- the two ops, each timed to its completed result --------------------
    def _get(self, i):
        keys = self.pool[i][0]
        with self.run.spans.span("bench.op.get"):
            out = self.table.wait_device(self.table.get_device_async(keys))
            out.block_until_ready()
        return out

    def _add(self, i):
        keys, grad = self.pool[i]
        with self.run.spans.span("bench.op.add"):
            self.table.wait(self.table.add_device_async(grad, keys))
            # wait() returns when the dispatcher has submitted the step; the
            # op is done when the table's new z and n are
            self.table.get_state_device("z").block_until_ready()
            self.table.get_state_device("n").block_until_ready()
        self.history.append(i)

    # -- the window ------------------------------------------------------
    def window(self, seconds):
        run = self.run
        t0 = time.perf_counter()
        deadline = t0 + seconds
        sample_at = list(t0 + self.sample_at * seconds)
        pairs = keys_named = 0
        while time.perf_counter() < deadline:
            i = pairs % len(self.pool)
            run.attempted += 2
            try:
                issued = len(self.history)
                out = self._get(i)
                self._add(i)
            except Exception as e:  # an op that raised has failed
                run.failed += 1
                print(f"benchmark: op failed: {e!r}", flush=True)
                if run.failed > 100:
                    break
                continue
            pairs += 1
            keys_named += len(self.pool_keys[i])
            if sample_at and time.perf_counter() >= sample_at[0]:
                sample_at.pop(0)
                self.kept.append((i, issued, out))
        t1 = time.perf_counter()
        ms = {name.rsplit(".", 1)[1]:
              [(b - a) * 1e3 for a, b in run.spans.samples.get(name, [])]
              for name in ("bench.op.add", "bench.op.get")}
        run.result.update(
            ops=2 * pairs, adds=pairs, gets=pairs, rows=2 * keys_named,
            add_rows=keys_named, get_rows=keys_named, row_cols=1,
            elapsed_s=t1 - t0, op_ms=ms)
        return t1

    # -- after the window -------------------------------------------------
    def finish(self):
        run, ref, opt = self.run, self.ref, self.opt
        t = time.perf_counter()
        sampled = self.sampled
        # kept Gets: their keys that the sample holds, against the
        # reference at the Adds acknowledged when each was issued
        err = 0.0
        for i, issued, out in self.kept:
            replay = sampled.follow(self.history, issued)
            at, hit = sampled.plans[i]
            err = max(err, ref.w_error(
                np.asarray(out)[hit],
                ref.weights(replay.z[at], replay.n[at], **replay.opt),
                replay.z[at], replay.steps[at], opt))
        run.compare.add("window_get_error", err, 1.0)
        run.result["gets_checked"] = len(self.kept)
        self.kept.clear()
        # after the window: the sample's state, and its weights through the
        # table's host Get
        replay = sampled.follow(self.history, len(self.history))
        want_z, want_n, want_w, steps = replay.state(self.sample)
        z, n = self._state(self.sample)
        run.compare.add("final_sample_w_error", ref.w_error(
            self.table.get(self.sample), want_w, want_z, steps, opt), 1.0)
        run.compare.add("final_sample_z_error",
                        ref.z_error(z, want_z, steps), 1.0)
        run.compare.add("final_sample_n_mismatch",
                        ref.n_mismatch(n, want_n), 0)
        run.result["keys_checked"] = int(len(self.sample))
        run.result["keys_stepped"] = int((steps > 0).sum())
        run.result["most_steps"] = int(steps.max())
        run.result["adds_replayed"] = len(self.history)
        run.compare.add("unnamed_state_mismatch", self._unnamed_changed(), 0)
        run.result["keys_unnamed"] = self.key_space - int(len(self.named))
        print(json.dumps({"reference_replay_s": time.perf_counter() - t,
                          "keys_a_step": [len(k) for k in self.pool_keys],
                          "bucket": self.bucket}), flush=True)

    def _differing(self):
        """Keys whose ``z`` or ``n`` differs from its initial value in any
        bit, counted on the device: one pass, piece by piece, against the
        hash worked out again; and the scratch entries, which must be
        zero."""
        import jax
        import jax.numpy as jnp

        init_piece, space, piece = (self._init_piece, self.key_space,
                                    self.piece)
        if self._changed is None:
            bits = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)  # noqa

            @jax.jit
            def changed(z, n, lo, first):
                # keys [lo, lo + piece); `first` <= the keys counted (the
                # last piece starts early so as to end at the key space's
                # end)
                z0, n0 = init_piece(lo)
                zs = jax.lax.dynamic_slice(z, (lo,), (piece,))
                ns = jax.lax.dynamic_slice(n, (lo,), (piece,))
                differ = (bits(zs) != bits(z0)) | (bits(ns) != bits(n0))
                counted = lo + jnp.arange(piece, dtype=jnp.int32) >= first
                return jnp.count_nonzero(differ & counted)

            self._changed = changed, jax.jit(
                lambda z, n: jnp.count_nonzero(z[space:])
                + jnp.count_nonzero(n[space:]))
        changed, scratch = self._changed
        z = self.table.get_state_device("z")
        n = self.table.get_state_device("n")
        total = int(scratch(z, n))
        for first in range(0, space, piece):
            lo = min(first, space - piece)
            total += int(changed(z, n, jnp.int32(lo), jnp.int32(first)))
        return total

    def _unnamed_changed(self):
        """Keys no pooled set names that differ from their initial value:
        every key that differs, less the named keys that do."""
        nz, nn = self._state(self.named)
        z0, n0 = self.ref.init_zn(self.named, self.run.seed)
        named = int(((nz.view(np.uint32) != z0.view(np.uint32))
                     | (nn.view(np.uint32) != n0.view(np.uint32))).sum())
        return self._differing() - named

    def end_to_end(self):
        """The four numbers of every in-process cell (`rows_per_s` counts
        keys: a key is this table's row), all of them printed: the cell is
        judged on `add_p50_ms` and `op_p95_ms` alone (BENCHMARK.json), because
        every op here follows a sleep of 24 ms of one of its two threads and
        pays a wake that differs by a third of a millisecond from process to
        process: a tenth of this Get, a hundredth of this Add (PERF.md)."""
        values = rows_table.end_to_end(self.run.result)
        print(json.dumps({"end_to_end_all": values}), flush=True)
        return values

    def close(self):
        import multiverso_tpu as mv
        mv.shutdown()
