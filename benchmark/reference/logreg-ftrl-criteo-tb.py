"""The plain reference of the FTRL table of a click-through logistic
regression (upstream Multiverso `Applications/LogisticRegression`,
`objective_type=ftrl`, `sparse=true`, `util/ftrl_sparse_table.h`; McMahan et
al., "Ad Click Prediction: a View from the Trenches", KDD 2013, Algorithm 1,
per coordinate): numpy float32, the server's step applied Add by Add in the
order the Adds were acknowledged. The server keeps `(z, n)` a key and no
weight; for every key an Add names, `g` its raw gradient:

    w     = -sign(z) * max(|z| - lambda1, 0) / ((beta + sqrt(n)) / alpha + lambda2)
    sigma = (sqrt(n + g^2) - sqrt(n)) / alpha       # from the OLD (z, n)
    z    <- z + (g - sigma * w)                     # upstream's `z += ...`
    n    <- n + g^2

and a Get derives `w` by the first line from the current `(z, n)`.

Adds do not commute, so nothing can be kept as counts: a `Replay` holds `z`,
`n` and the steps taken of the keys a comparison asks for and is handed every
acknowledged Add in order; what it is handed of keys it does not hold costs
it nothing, so a 20 s window (some thousands of Adds of 115,000 keys) replays
in seconds for some thousands of keys.

**The initial state** is a model mid-training, a hash of (seed, key) that
any process (and the device, in the same uint32 arithmetic) works out for any
key without holding the table: `z0 = k / 64`, `-128 <= k < 128` (so `[-2, 2)`,
129 of 256 values with `|z0| <= 1`: half the keys have weight exactly 0 under
`lambda1 = 1`), `n0 = k / 64`, `0 <= k < 1024` (`[0, 16)`).

**What is exact and what is not.**

* A key no acknowledged Add names keeps `z` and `n` to the last bit
  (`z_error` gives infinity for any difference there; `n_mismatch` counts
  it).
* `n` is compared for **equality** (`n_mismatch`, limit 0) after any number
  of steps. A gradient is a whole multiple of 1/512 in [-1, 1) (`grad_k`):
  its square is a multiple of `2**-18` below 1, 18 bits, exact in float32 on
  any device. So both sides hold the same `g^2` to the bit and `n + g^2` is
  one float32 addition of the same two numbers: it rounds the same way on
  both sides, for ever, whatever `n` has grown to (PR 33's argument for its
  accumulator). A lost Add, or one applied twice, moves `n` by `g^2` (a third
  on average; `g = 0`, one value in 1,024, hides it for that key alone): such
  a fault shows at any step. 1/512 is the grid with this property and a
  control: bfloat16 (8 bits) holds every multiple of 1/256 in [-1, 1), so on
  a coarser grid a gradient rounded to bfloat16 is the same gradient; on
  1/512 it moves a quarter of the values, by 1/512, and `n` with them.
* `z`: `|got - want| <= max(sqrt(k) * Z_ROOT_TOL, k * Z_STEP_TOL) * max(1,
  |want|)`, `Z_ROOT_TOL = 2**-19`, `Z_STEP_TOL = 2**-22`, for a key that took
  `k` steps (a key whose weight has been 0 at every step so far takes `z +
  g`, multiples of 1/512 of small magnitude, and meets it with error 0). The
  step has two roots, a quotient and a difference of roots that cancels
  (`sqrt(n + g^2) - sqrt(n)` at `n` of some hundreds keeps three digits of a
  root's last place), and a TPU's float32 root and quotient do not round as
  numpy's do. Over the first steps the two sides differ by a random walk of
  units in the last place of `z` (the root term); from some hundreds of steps
  on the v5e's difference grows nearly like `k`: 2.9, 6.1, 16.9 and 55 units
  of `2**-19 * |z|` after 100, 300, 1,000 and 3,000 steps of 2,000 hot keys
  (my chip run, PR 40: half a unit in the last place of `z` a step, one
  way), so the limit has a term in `k`, about six times that. A limit in
  `k` cannot be what finds a lost or a doubled Add on a hot key, and is not:
  `n` is, by equality, at any step.
* `w`: `|got - want| <= z_allowed / (beta / alpha + lambda2) + W_REL *
  |want|`, `W_REL = 2**-18`: what the allowed error of `z` moves the closed
  form by (its denominator is at least `beta / alpha + lambda2`), and a few
  units in the last place for the root and the quotient of the closed form
  itself, which a key that took no step pays too (a third of `2**-20` on
  the v5e over 115,080 keys, my chip run, PR 40). A key whose `|z|` sits
  within the tolerance of `lambda1` may read exactly 0 on one side and a
  weight of that size on the other: inside the limit.

A gradient rounded to bfloat16 moves `z` by 1/512 for a quarter of the keys
of every Add: a thousand times the limit at a first step (PERF.md has the
measured margins).

Imports nothing of the program."""

import numpy as np

Z_UNIT = 64        # z0 = k / Z_UNIT, -Z_SPAN <= k < Z_SPAN
Z_SPAN = 128
N_UNIT = 64        # n0 = k / N_UNIT, 0 <= k < N_SPAN
N_SPAN = 1024
GRAD_UNIT = 512    # a gradient is k / GRAD_UNIT, -GRAD_UNIT <= k < GRAD_UNIT:
#                    [-1, 1) in 9 bits, of which bfloat16 keeps 8
Z_ROOT_TOL = 2.0 ** -19
Z_STEP_TOL = 2.0 ** -22
W_REL = 2.0 ** -18


def _mix(keys, seed, xp=np):
    """uint32 hash of (seed, key), the first configurations' mixer; ``xp``
    is numpy, or jax.numpy for the same bits on a device."""
    s = xp.uint32((int(seed) * 2654435761 + 0x9E3779B9) & 0xFFFFFFFF)
    h = (xp.asarray(keys).astype(xp.uint32) ^ s) * xp.uint32(2246822519)
    h = h ^ (h >> xp.uint32(15))
    h = h * xp.uint32(3266489917)
    h = h ^ (h >> xp.uint32(13))
    return h | xp.uint32(1)


def init_k(keys, seed, xp=np):
    """Initial ``(z, n)`` of ``keys`` in units, int32: ``-Z_SPAN <= zk <
    Z_SPAN``, ``0 <= nk < N_SPAN``."""
    with np.errstate(over="ignore"):
        h = _mix(keys, seed, xp)
        zk = (h * xp.uint32(40503)) >> xp.uint32(24)          # 8 bits
        nk = (h * xp.uint32(2654435769)) >> xp.uint32(22)     # 10 bits
    return (zk.astype(xp.int32) - xp.int32(Z_SPAN), nk.astype(xp.int32))


def init_zn(keys, seed, xp=np):
    """Initial float32 ``(z, n)`` of ``keys``."""
    zk, nk = init_k(keys, seed, xp)
    return (zk.astype(xp.float32) * xp.float32(1.0 / Z_UNIT),
            nk.astype(xp.float32) * xp.float32(1.0 / N_UNIT))


def grad_k(rng, n):
    """One Add's raw gradient in units of 1 / GRAD_UNIT, a key each."""
    return rng.integers(-GRAD_UNIT, GRAD_UNIT, size=n, dtype=np.int16)


def to_float(k):
    return np.asarray(k, np.float32) * np.float32(1.0 / GRAD_UNIT)


def weights(z, n, alpha, beta, lambda1, lambda2):
    """The closed form, in the dtype of ``z``."""
    t = z.dtype.type
    shrunk = np.sign(z) * np.maximum(np.abs(z) - t(lambda1), t(0))
    return -shrunk / ((t(beta) + np.sqrt(n)) / t(alpha) + t(lambda2))


class Replay:
    """``z``, ``n`` and the number of steps taken of the keys ``keys``
    (held sorted, once each), as they must be after the Adds handed to
    ``add`` so far, in that order. ``opt``: ``alpha``, ``beta``,
    ``lambda1``, ``lambda2``. ``dtype`` float64 is the test's replay that
    the float32 one is measured against."""

    def __init__(self, keys, seed, opt, dtype=np.float32):
        self.ids = np.unique(np.asarray(keys))
        self.dtype = np.dtype(dtype)
        z, n = init_zn(self.ids, seed)
        self.z, self.n = z.astype(dtype), n.astype(dtype)
        self.steps = np.zeros(len(self.ids), np.int64)
        self.opt = {k: float(opt[k])
                    for k in ("alpha", "beta", "lambda1", "lambda2")}

    def plan(self, keys):
        """Which keys of an Add's ``keys`` (distinct) the replay holds:
        ``(their positions here, their positions in the Add)``."""
        keys = np.asarray(keys)
        pos = np.minimum(np.searchsorted(self.ids, keys), len(self.ids) - 1)
        hit = np.flatnonzero(self.ids[pos] == keys)
        return pos[hit], hit

    def add(self, plan, grad):
        """One acknowledged Add: ``grad`` its gradient (a value a key of
        the Add, or already cut to ``plan``'s)."""
        at, hit = plan
        g = np.asarray(grad if len(grad) == len(hit) else grad[hit],
                       self.dtype)
        z, n = self.z[at], self.n[at]
        w = weights(z, n, **self.opt)
        grown = n + g * g
        sigma = (np.sqrt(grown) - np.sqrt(n)) / self.dtype.type(
            self.opt["alpha"])
        self.z[at] = z + (g - sigma * w)
        self.n[at] = grown
        self.steps[at] += 1

    def at(self, keys):
        """Positions of ``keys``, all of which the replay holds."""
        at = np.searchsorted(self.ids, keys)
        if not np.array_equal(self.ids[np.minimum(at, len(self.ids) - 1)],
                              keys):
            raise KeyError("the replay does not hold every key asked for")
        return at

    def state(self, keys):
        """``(z, n, w, steps)`` of keys the replay holds."""
        at = self.at(keys)
        z, n = self.z[at], self.n[at]
        return z, n, weights(z, n, **self.opt), self.steps[at]


def _error(diff, allowed):
    """The largest ``|diff| / allowed``; where nothing is allowed any
    difference is infinitely wrong."""
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(diff == 0, 0.0, np.abs(diff) / allowed)
    return float(ratio.max()) if ratio.size else 0.0


def z_allowed(want_z, steps):
    """What ``z`` of a key that took ``steps`` steps may differ by (the
    module's docstring); 0 for a key that took none."""
    steps = np.asarray(steps, np.float64)
    return (np.maximum(Z_ROOT_TOL * np.sqrt(steps), Z_STEP_TOL * steps)
            * np.maximum(1.0, np.abs(np.asarray(want_z, np.float64))))


def z_error(got, want, steps):
    """Largest error of ``z`` in units of what is allowed: at most 1
    passes."""
    return _error(np.asarray(got, np.float64) - np.asarray(want, np.float64),
                  z_allowed(want, steps))


def w_error(got, want_w, want_z, steps, opt):
    """Largest error of weights in units of what is allowed: at most 1
    passes."""
    want_w = np.asarray(want_w, np.float64)
    floor = float(opt["beta"]) / float(opt["alpha"]) + float(opt["lambda2"])
    return _error(np.asarray(got, np.float64) - want_w,
                  z_allowed(want_z, steps) / floor + W_REL * np.abs(want_w))


def n_mismatch(got, want):
    """How many keys' ``n`` differ in any bit (the module's docstring: none
    may)."""
    return int((np.asarray(got, np.float32).view(np.uint32)
                != np.asarray(want, np.float32).view(np.uint32)).sum())
