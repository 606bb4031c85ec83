"""The plain reference of the embedding-table deployment served under BSP
(upstream's `-sync=true`): a float32 table that takes row Adds and answers
row Gets from workers that move in rounds. Every acknowledged Add is applied
exactly once, and a worker's i-th Get returns the table after exactly
min(i, n_v) Adds of every worker v, n_v being the Adds v had made when it
finished, and no others: a Get is determined in every element, whichever
worker asks and however the requests raced.

Values are whole multiples of 1/UNIT with at most 11 bits (initial) and a
few more after thousands of Adds, so float32 addition is exact in any order
and the reference can be kept in integers: what the table must hold is
``(init_k + sum of the Adds the round rule admits) / UNIT`` to the last bit.
A table or a kernel in bfloat16 (8 bits) cannot hold an 11-bit value and
fails. The one-chip configuration's reference with the round rule, a copy as
every configuration brings its own.

Imports nothing of the program."""

import numpy as np

UNIT = 64          # one unit is 1/64
SPAN = 1024        # initial values and deltas are k/UNIT, -SPAN <= k < SPAN


def init_k(row_ids, cols, seed, scratch=None):
    """Initial table values in units, a hash of (seed, row, column): any
    process can work out any row without holding the table. ``scratch`` is
    a uint32 buffer of at least (rows, cols) to work in; the result is then
    a view of it."""
    r = np.asarray(row_ids).astype(np.uint32)
    s = np.uint32((int(seed) * 2654435761 + 0x9E3779B9) & 0xFFFFFFFF)
    k = (np.empty((len(r), cols), np.uint32) if scratch is None
         else scratch[:len(r)])
    with np.errstate(over="ignore"):
        h = (r ^ s) * np.uint32(2246822519)
        h ^= h >> np.uint32(15)
        h *= np.uint32(3266489917)
        h ^= h >> np.uint32(13)
        h |= np.uint32(1)
        odd = (2 * np.arange(cols, dtype=np.uint32) + 1) * np.uint32(40503)
        np.multiply(h[:, None], odd[None, :], out=k)
    k >>= np.uint32(21)                  # 11 bits: 0 <= k < 2 * SPAN
    k = k.view(np.int32)
    k -= SPAN
    return k


def init_table(rows, cols, seed, block=1 << 14, threads=8):
    """(float32 table, int64 column sums in units), built in row blocks by
    a few threads (numpy releases the interpreter lock), each in a buffer of
    its own that it keeps: every run pays for this in set-up, and on the
    check's machines the first touch of a fresh page costs far more than
    the arithmetic."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    table = np.empty((rows, cols), np.float32)
    mine = threading.local()

    def fill(lo):
        if not hasattr(mine, "scratch"):
            mine.scratch = np.empty((block, cols), np.uint32)
        k = init_k(np.arange(lo, min(lo + block, rows)), cols, seed,
                   mine.scratch)
        np.multiply(k, np.float32(1.0 / UNIT), out=table[lo:lo + len(k)],
                    casting="unsafe")
        return k.sum(axis=0, dtype=np.int64)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        sums = sum(pool.map(fill, range(0, rows, block)))
    return table, sums


def delta_k(rng, n, cols):
    """One Add's deltas in units."""
    return rng.integers(-SPAN, SPAN, size=(n, cols), dtype=np.int16)


def to_float(k):
    return (np.asarray(k, np.float32) * np.float32(1.0 / UNIT))


class Mirror:
    """The table as it must be, row by row, from the initial hash and how
    often each pooled Add (ids, delta_k) was acknowledged."""

    def __init__(self, cols, seed):
        self.cols, self.seed = cols, seed
        self._pools = []

    def add_pool(self, ids, dk):
        order = np.argsort(ids, kind="stable")
        self._pools.append((np.asarray(ids)[order], np.asarray(dk)[order]))
        return len(self._pools) - 1

    def column_sums(self, init_sums, counts):
        total = np.array(init_sums, np.int64)
        for (_, dk), n in zip(self._pools, counts):
            total += int(n) * dk.sum(axis=0, dtype=np.int64)
        return total

    def rows_k(self, row_ids, counts):
        """Units that rows ``row_ids`` hold after pool i was applied
        counts[i] times."""
        row_ids = np.asarray(row_ids)
        out = init_k(row_ids, self.cols, self.seed).astype(np.int64)
        for (ids, dk), n in zip(self._pools, counts):
            if not n:
                continue
            pos = np.minimum(np.searchsorted(ids, row_ids), len(ids) - 1)
            hit = ids[pos] == row_ids
            out[hit] += int(n) * dk[pos[hit]].astype(np.int64)
        return out

    def round_counts(self, i, finals):
        """How often each pooled Add is in the table that a Get of round
        ``i`` must return: worker v (pools v * pool .. (v + 1) * pool - 1,
        in the order they were registered) has made min(i, finals[v])
        Adds, its k-th (from 0) of its pooled set ``k mod pool``.
        ``finals[v]`` is None while v has not finished."""
        pool = len(self._pools) // len(finals)
        counts = []
        for final in finals:
            made = i if final is None else min(i, final)
            counts += [made // pool + (e < made % pool)
                       for e in range(pool)]
        return counts

    def rows_at_round(self, row_ids, i, finals):
        """Units that rows ``row_ids`` hold in a Get of round ``i``."""
        return self.rows_k(row_ids, self.round_counts(i, finals))

    def owners(self, row_ids, groups):
        """For each row, whether only pools of ``groups`` name it (bool)."""
        row_ids = np.asarray(row_ids)
        foreign = np.zeros(len(row_ids), bool)
        for i, (ids, _) in enumerate(self._pools):
            if i in groups:
                continue
            pos = np.minimum(np.searchsorted(ids, row_ids), len(ids) - 1)
            foreign |= ids[pos] == row_ids
        return ~foreign


def mismatches(values, want_k):
    """How many elements of float ``values`` differ from want_k / UNIT."""
    got = np.asarray(values, np.float64) * UNIT
    return int((got != np.asarray(want_k, np.float64)).sum())
