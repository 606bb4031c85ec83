"""The plain reference of the 26-table embedding deployment: float32 tables
of one width and of 3 to 3,000,000 rows (one a categorical feature, told
apart by ``table``: 0 to 25) that take row Adds and answer row Gets, every
acknowledged Add applied exactly once to the table it named, and an op on
one table never reading or changing a row of another.

The first configuration's reference, a table: values are whole multiples of
1/UNIT with at most 11 bits (initial) and a few more after thousands of
Adds, so float32 addition is exact in any order and the reference can be
kept in integers: what table ``t`` must hold is ``(init_k(t) + sum of
acknowledged delta_k to t) / UNIT`` to the last bit. The hash takes the
table's index, so row ``r`` of table ``t`` and row ``r`` of table ``t + 1``
hold different values, and a row read from the wrong table is a wrong
value; plain Adds commute, so counts of acknowledged Adds a pooled set
decide a row. A table or a kernel in bfloat16 (8 bits) cannot hold an
11-bit value and fails.

Imports nothing of the program."""

import numpy as np

UNIT = 64          # one unit is 1/64
SPAN = 1024        # initial values and deltas are k/UNIT, -SPAN <= k < SPAN


def init_k(row_ids, cols, seed, table=0, scratch=None):
    """Initial values of one table in units, a hash of (seed, table, row,
    column): any process can work out any row without holding the table.
    ``scratch`` is a uint32 buffer of at least (rows, cols) to work in; the
    result is then a view of it."""
    r = np.asarray(row_ids).astype(np.uint32)
    s = np.uint32((int(seed) * 2654435761 + 0x9E3779B9
                   + int(table) * 0x85EBCA6B) & 0xFFFFFFFF)
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


_BLOCK = 1 << 14    # rows a thread hashes at a time
_threads = None     # (pool, thread-local scratch), made on first use


def init_rows(lo, n, cols, seed, table=0):
    """Rows ``[lo, lo + n)`` of one table: (float32 rows, int64 column sums
    in units), for a caller that never holds the table. Built in row blocks
    by a few threads (numpy releases the interpreter lock), each in a
    scratch buffer of its own that it keeps from call to call: every run
    pays for this in set-up, and on the check's machines the first touch
    of a fresh page costs far more than the arithmetic."""
    global _threads
    if _threads is None:
        import threading
        from concurrent.futures import ThreadPoolExecutor
        _threads = (ThreadPoolExecutor(max_workers=8), threading.local())
    pool, mine = _threads
    values = np.empty((n, cols), np.float32)

    def fill(at):
        if getattr(mine, "cols", None) != cols:
            mine.cols, mine.scratch = cols, np.empty((_BLOCK, cols),
                                                     np.uint32)
        k = init_k(np.arange(lo + at, lo + min(at + _BLOCK, n)), cols, seed,
                   table, mine.scratch)
        np.multiply(k, np.float32(1.0 / UNIT), out=values[at:at + len(k)],
                    casting="unsafe")
        return k.sum(axis=0, dtype=np.int64)

    sums = sum(pool.map(fill, range(0, n, _BLOCK)),
               np.zeros(cols, np.int64))
    return values, sums


def init_table(rows, cols, seed, table=0):
    """(float32 table, int64 column sums in units) of one whole table."""
    return init_rows(0, rows, cols, seed, table)


def delta_k(rng, n, cols):
    """One Add's deltas in units."""
    return rng.integers(-SPAN, SPAN, size=(n, cols), dtype=np.int16)


def to_float(k):
    return (np.asarray(k, np.float32) * np.float32(1.0 / UNIT))


class Mirror:
    """One table as it must be, row by row, from the initial hash and how
    often each pooled Add (ids, delta_k) to it was acknowledged."""

    def __init__(self, cols, seed, table=0):
        self.cols, self.seed, self.table = cols, seed, table
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
        out = init_k(row_ids, self.cols, self.seed,
                     self.table).astype(np.int64)
        for (ids, dk), n in zip(self._pools, counts):
            if not n:
                continue
            pos = np.minimum(np.searchsorted(ids, row_ids), len(ids) - 1)
            hit = ids[pos] == row_ids
            out[hit] += int(n) * dk[pos[hit]].astype(np.int64)
        return out


def mismatches(values, want_k):
    """How many elements of float ``values`` differ from want_k / UNIT."""
    got = np.asarray(values, np.float64) * UNIT
    return int((got != np.asarray(want_k, np.float64)).sum())
