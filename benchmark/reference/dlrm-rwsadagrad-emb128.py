"""The plain reference of the embedding table trained with server-side
row-wise AdaGrad (`facebookresearch/dlrm`, `--optimizer=rwsadagrad`,
`optim/rwsadagrad.py`): numpy float32, the optimizer's two lines applied Add
by Add in the order the Adds were acknowledged. For every row `r` an Add
names, `g_r` its raw gradient:

    s_r <- s_r + mean_j(g_rj^2)                # one float32 a row, initial 0
    w_r <- w_r - lr * g_r / (sqrt(s_r) + eps)  # eps outside the root

Adds do not commute here, so nothing can be kept as counts: a `Replay` holds
`w` and `s` of the rows a comparison asks for and is handed every
acknowledged Add in order; what it is handed of rows it does not hold costs
it nothing, so a 20 s window (about 2,500 Adds of 100,000 rows) replays in
seconds for some thousands of rows.

**What is exact and what is not.** A row no acknowledged Add names must be
read back to the last bit, table and state (`w_error` gives infinity for
any difference there).

* `s` is compared for **equality** (`s_mismatch`, limit 0), whatever the
  number of steps a row took. A gradient's values are whole multiples of
  1/512 in [-1, 1) (`grad_k`): a square is a multiple of `2**-18`, and a row
  whose 128 squares sum to more than 64 is drawn again (42.7 is the mean, 64
  lies six deviations out), so every partial sum of the 128, in whatever
  order a device adds them, is a multiple of `2**-18` of at most 64: 24 bits,
  exact in float32. The mean is that sum times `2**-7`, exact. So both sides
  hold the same `mean_j(g^2)` to the bit, and `s + mean` is one float32
  addition of the same two numbers: it rounds (from a row's second step on)
  the same way on both sides, for ever. A lost Add, or one applied twice,
  moves `s` by a third: any such fault shows at any step. 1/512 is the one
  grid with this property and a control: bfloat16 (8 bits) holds every
  multiple of 1/256 in [-1, 1), so on a coarser grid a gradient rounded to
  bfloat16 is the same gradient; on a finer one the sum of squares needs 26
  bits and is rounded in an order the device chooses. (The width has to be a
  power of two for the mean to be exact; the cell's is 128.)
* `w`: `|got - want| <= sqrt(k) * W_STEP_TOL`, `W_STEP_TOL = 2**-18`
  (3.8e-6), for a row that took `k` steps, because the arithmetic has a root
  and a quotient that a TPU's float32 units do not round as numpy's do. One
  step moves `w` by `lr * g / sqrt(s)`, at most 0.02 here. The
  two sides' steps differ by a few units in the last place of that (under
  1e-8), which is too small to matter by itself and decides, once in a
  hundred steps or so, which way `w + step` rounds: one unit in the last
  place of `w`, and `|w| < 32` holds a unit of `2**-19`. Those flips have no
  preferred sign, so `k` steps differ by a random walk: a fraction of
  `sqrt(k)` units, against the four (two at `|w| >= 16`) the limit allows.
  It does not grow like `k`: a tolerance that did would, on a hot row,
  swallow an Add that was lost or applied twice (at its 1,000th step a row
  moves by 5e-4 a step; `1000 * 2**-18` is 3.8e-3, `sqrt(1000) * 2**-18`
  1.2e-4; the root-`k` limit sees one lost step of a row until its 4,500th,
  and `s` sees it at any). A gradient rounded to bfloat16 (8 bits of the 9
  these gradients have: a quarter of the values move, by 1/512) moves a
  first step by up to 3.4e-5, nine times the limit, and the same way every
  time its pooled Add returns (PERF.md has the measured margin).

Imports nothing of the program."""

import numpy as np

UNIT = 64          # the table's initial values are k / UNIT, |k| < SPAN
SPAN = 1024
GRAD_UNIT = 512    # a gradient's values are k / GRAD_UNIT, -GRAD_UNIT <= k <
#                    GRAD_UNIT: [-1, 1) in 9 bits, of which bfloat16 keeps 8
GRAD_SQUARES = 64 * GRAD_UNIT ** 2  # the most a row's squares may sum to
W_STEP_TOL = 2.0 ** -18


def init_k(row_ids, cols, seed, scratch=None):
    """Initial table values in units, a hash of (seed, row, column): any
    process can work out any row without holding the table (the first
    configuration's hash). ``scratch`` is a uint32 buffer of at least
    (rows, cols) to work in; the result is then a view of it."""
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


def init_rows(row_ids, cols, seed):
    """Initial float32 values of rows ``row_ids``."""
    return init_k(row_ids, cols, seed).astype(np.float32) * np.float32(
        1.0 / UNIT)


def init_table(rows, cols, seed, block=1 << 14, threads=8):
    """(float32 table, int64 column sums in units), built in row blocks by
    a few threads, each in a buffer of its own that it keeps."""
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


def grad_k(rng, n, cols):
    """One Add's raw gradient in units of 1 / GRAD_UNIT: uniform, a row
    whose squares sum past GRAD_SQUARES drawn again (the module's
    docstring: the sum of a row's squares is then exact in float32)."""
    k = rng.integers(-GRAD_UNIT, GRAD_UNIT, size=(n, cols), dtype=np.int16)
    while True:
        over = np.flatnonzero(
            np.einsum("ij,ij->i", k, k, dtype=np.int32) > GRAD_SQUARES)
        if not len(over):
            return k
        k[over] = rng.integers(-GRAD_UNIT, GRAD_UNIT, size=(len(over), cols),
                               dtype=np.int16)


def to_float(k):
    return np.asarray(k, np.float32) * np.float32(1.0 / GRAD_UNIT)


class Replay:
    """``w``, ``s`` and the number of steps taken of the rows ``row_ids``
    (held sorted, once each), as they must be after the Adds handed to
    ``add`` so far, in that order. ``dtype`` float64 is the test's replay
    that the float32 one is measured against."""

    def __init__(self, row_ids, cols, seed, lr, eps, dtype=np.float32):
        self.ids = np.unique(np.asarray(row_ids))
        self.dtype = np.dtype(dtype)
        self.w = init_rows(self.ids, cols, seed).astype(dtype)
        self.s = np.zeros(len(self.ids), dtype)
        self.steps = np.zeros(len(self.ids), np.int64)
        self.lr, self.eps = self.dtype.type(lr), self.dtype.type(eps)

    def plan(self, ids):
        """Which rows of an Add's ``ids`` (distinct) the replay holds:
        ``(their positions here, their positions in the Add)``; an Add
        sent again is planned once."""
        ids = np.asarray(ids)
        pos = np.minimum(np.searchsorted(self.ids, ids), len(self.ids) - 1)
        hit = np.flatnonzero(self.ids[pos] == ids)
        return pos[hit], hit

    def add(self, plan, grad):
        """One acknowledged Add: ``grad`` its gradient rows (all of them,
        or already cut to ``plan``'s)."""
        at, hit = plan
        g = np.asarray(grad if len(grad) == len(hit) else grad[hit],
                       self.dtype)
        s = self.s[at] + np.mean(g * g, axis=1, dtype=self.dtype)
        self.s[at] = s
        self.w[at] -= self.lr * g / (np.sqrt(s) + self.eps)[:, None]
        self.steps[at] += 1

    def rows(self, row_ids):
        """``(w, s, steps)`` of rows the replay holds."""
        at = np.searchsorted(self.ids, row_ids)
        if not np.array_equal(self.ids[at], row_ids):
            raise KeyError("the replay does not hold every row asked for")
        return self.w[at], self.s[at], self.steps[at]


def _error(diff, allowed):
    """The largest ``|diff| / allowed``; where nothing is allowed (a row
    that took no step) any difference is infinitely wrong."""
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(diff == 0, 0.0, np.abs(diff) / allowed)
    return float(ratio.max()) if ratio.size else 0.0


def w_error(got, want, steps):
    """Largest error of table values in units of what ``steps`` steps of a
    row may differ by (the module's docstring): at most 1 passes."""
    got = np.asarray(got, np.float64)
    return _error(got - np.asarray(want, np.float64),
                  W_STEP_TOL * np.sqrt(np.asarray(steps, np.float64))[:, None])


def s_mismatch(got, want):
    """How many rows' states differ in any bit (the module's docstring:
    none may)."""
    return int((np.asarray(got, np.float32)
                != np.asarray(want, np.float32)).sum())
