"""Test helper (not a package module: nothing in multiverso_tpu/ uses it):
the Zipf key stream of the train-while-serve drill in test_overload.py."""

import numpy as np


class TrafficGen:
    """Zipfian key skew over a permuted key space. Deterministic per
    seed, so a drill replays the identical key stream."""

    def __init__(self, key_space, zipf_s=1.2, seed=0):
        self.key_space = int(key_space)
        self._rng = np.random.default_rng(seed)
        ranks = np.arange(1, self.key_space + 1, dtype=np.float64)
        pmf = ranks ** -float(zipf_s)
        self._cdf = np.cumsum(pmf / pmf.sum())
        # hot ranks land on scattered keys, not 0..k (a real keyspace's
        # hot set is not contiguous)
        self._perm = self._rng.permutation(self.key_space)

    def draw_key(self):
        return int(self._perm[int(np.searchsorted(
            self._cdf, self._rng.random()))])
