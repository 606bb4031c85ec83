"""Top-k retrieval kernels per table kind + the shard-merge algebra.

One ordering contract rules every path in this module AND the shard
router's merge: candidates rank by score DESCENDING, ties by global id
ASCENDING (``np.lexsort((ids, -scores))`` per query row). Because the
single-table engine and the per-shard merge both finish with exactly
this ordering, a global top-k assembled from per-shard partials names
the ids, in the order, of a single-shard oracle over the same rows,
including at tie boundaries. Scores are float32 at full precision on
every path; where every float32 sum is exact (``dot`` over integer-valued
rows) they agree to the bit, and ``cosine`` scores agree to 1e-6 (a cosine
is at most 1): a shard's block, the whole table's and numpy's sum in
different orders.

Three serving shapes:

* **MatrixServer** — the logical ``[:num_row, :num_col]`` block stays
  device-resident; one jitted fused kernel scores all query rows and
  runs ``jax.lax.top_k`` on device (``lax.top_k`` breaks ties toward
  the lower index, which IS the lower row id — consistent with the
  contract before the host-side reorder even runs).
* **SparseServer** — live rows stack (key-sorted, so index order = id
  order) into one block through the same jitted kernel.
* **TieredSparseServer** — hot rows score as one host block; cold
  segments stream batch-wise through :meth:`TieredStore.scan_blocks`
  under the ``query_scan`` wait-site, scoring **in the compressed
  domain** when the segment is quantized at >= 4 bits:
  ``dot(q, lo + c*step) = lo*sum(q) + step*(q @ c.T)`` (and the row
  norm for cosine from the code moments), decoding otherwise. Scans
  never touch the promotion sketch, the fetch cache, or the hot dict —
  the same no-promotion cold iteration the PR-15 digest path proves —
  so a query leaves the tier hit-rate exactly where it found it.

Host scoring is float32 end-to-end to match the jitted kernels' dtype.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.dashboard import count
from multiverso_tpu.obs.profiler import wait_site
from multiverso_tpu.tables.matrix_table import MatrixServer
from multiverso_tpu.tables.sparse_table import (SparseFTRLServer,
                                                SparseServer,
                                                TieredSparseServer)

_METRICS = ("dot", "cosine")
# zero-norm guard: a zero row/query cosine-scores 0.0 (its dot is 0)
# instead of dividing by zero; shared by the jitted and host paths so
# shard and oracle scores agree bitwise on the raw-row paths
_EPS = np.float32(1e-30)

# compressed-domain floor: below 4 bits the code grid is so coarse that
# scoring it buys nothing over decoding (and 1/2-bit segments are rare
# spill shapes); the ISSUE contract — compressed where bits >= 4
_COMPRESSED_MIN_BITS = 4


def check_request(request) -> Tuple[np.ndarray, int, str]:
    """Validate/normalize one wire query: ``(vecs, k, metric)`` ->
    ``(float32 (n_q, dim) contiguous, k >= 1, metric)``. Raises
    ValueError (-> Reply_Error on the wire) on malformed input."""
    try:
        vecs, k, metric = request
    except (TypeError, ValueError):
        raise ValueError(
            f"query request must be (vecs, k, metric), got {type(request)}")
    vecs = np.ascontiguousarray(vecs, dtype=np.float32)
    if vecs.ndim == 1:
        vecs = vecs.reshape(1, -1)
    if vecs.ndim != 2:
        raise ValueError(f"query vecs must be (n_q, dim), got {vecs.shape}")
    k = int(k)
    if k < 1:
        raise ValueError(f"query k must be >= 1, got {k}")
    metric = str(metric)
    if metric not in _METRICS:
        raise ValueError(f"query metric must be one of {_METRICS}, "
                         f"got {metric!r}")
    return vecs, k, metric


def order_rows(ids: np.ndarray, scores: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Impose THE ordering contract per query row: score descending,
    ties by ascending id. The one piece of algebra the engine and the
    shard merge must share for shard-vs-oracle identity to hold."""
    order = np.lexsort((ids, -scores), axis=-1)
    ids = np.take_along_axis(ids, order, axis=1)
    scores = np.take_along_axis(scores, order, axis=1)
    return (ids.astype(np.int64, copy=False),
            scores.astype(np.float32, copy=False))


def merge_topk(parts: List[Tuple[np.ndarray, np.ndarray]], k: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Merge per-shard (or per-block) partial top-k replies — possibly
    ragged (a shard owning fewer than k rows replies narrower) — into
    the global top-k under the ordering contract."""
    ids = np.concatenate(
        [np.asarray(p[0], dtype=np.int64).reshape(len(p[0]), -1)
         for p in parts], axis=1)
    scores = np.concatenate(
        [np.asarray(p[1], dtype=np.float32).reshape(len(p[1]), -1)
         for p in parts], axis=1)
    ids, scores = order_rows(ids, scores)
    return ids[:, :k], scores[:, :k]


# -- jitted fused score + top-k (matrix block, sparse block) -----------------

@functools.partial(jax.jit, static_argnames=("k", "cosine"))
def _topk_kernel(block, vecs, k: int, cosine: bool):
    """ONE fused program: score every query row against every table row,
    then ``lax.top_k`` the scored block. Ties break toward the lower
    row index (lax.top_k's contract) — index order is id order at every
    call site, so this agrees with the lexsort contract."""
    q = vecs.astype(jnp.float32)
    b = block.astype(jnp.float32)
    if cosine:
        q = q / jnp.maximum(
            jnp.linalg.norm(q, axis=1, keepdims=True), _EPS)
        b = b / jnp.maximum(
            jnp.linalg.norm(b, axis=1, keepdims=True), _EPS)
    # float32 all the way: a TPU's default matmul is one bfloat16 pass,
    # which cannot hold the ordering contract against a float32 oracle
    scores = jnp.matmul(q, b.T, precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(scores, k)


def _jit_block_topk(block, vecs: np.ndarray, k: int, metric: str
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Run the fused kernel, host-fetch, return (row_indices, scores)
    already in contract order."""
    scores, idx = _topk_kernel(block, vecs, k, metric == "cosine")
    scores = np.asarray(jax.device_get(scores), dtype=np.float32)
    idx = np.asarray(jax.device_get(idx), dtype=np.int64)
    return order_rows(idx, scores)


# -- host scoring (tiered hot block + cold segments) -------------------------

def _score_rows(vecs: np.ndarray, rows: np.ndarray, metric: str
                ) -> np.ndarray:
    """(n_q, n) float32 scores of decoded host rows."""
    if metric == "cosine":
        vecs = vecs / np.maximum(
            np.linalg.norm(vecs, axis=1, keepdims=True), _EPS)
        rows = rows / np.maximum(
            np.linalg.norm(rows, axis=1, keepdims=True), _EPS)
    return (vecs @ rows.T).astype(np.float32, copy=False)


def _score_codes(vecs: np.ndarray, codes: np.ndarray, lo: np.float32,
                 step: np.float32, metric: str) -> np.ndarray:
    """Compressed-domain scores: every row is ``lo + codes*step``
    elementwise, so the dot folds to
    ``lo*sum(q) + step*(q @ codes.T)`` and the row norm (cosine) comes
    from the code moments — no per-element dequantize materializes."""
    lo = np.float32(lo)
    step = np.float32(step)
    if metric == "cosine":
        vecs = vecs / np.maximum(
            np.linalg.norm(vecs, axis=1, keepdims=True), _EPS)
    numer = (lo * vecs.sum(axis=1, keepdims=True)
             + step * (vecs @ codes.T)).astype(np.float32, copy=False)
    if metric == "dot":
        return numer
    width = np.float32(codes.shape[1])
    norm_sq = (width * lo * lo
               + np.float32(2.0) * lo * step * codes.sum(axis=1)
               + step * step * (codes * codes).sum(axis=1))
    norms = np.sqrt(np.maximum(norm_sq, np.float32(0.0)),
                    dtype=np.float32)
    return (numer / np.maximum(norms, _EPS)).astype(np.float32,
                                                    copy=False)


def _block_topk_np(keys: np.ndarray, scores: np.ndarray, k: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-block host top-k in contract order; keys map score columns
    back to global ids."""
    k_eff = min(k, scores.shape[1])
    ids = np.broadcast_to(keys.reshape(1, -1), scores.shape)
    ids, scores = order_rows(np.ascontiguousarray(ids),
                             np.ascontiguousarray(scores))
    return ids[:, :k_eff], scores[:, :k_eff]


# -- per-kind serving --------------------------------------------------------

def _empty(n_q: int) -> Tuple[np.ndarray, np.ndarray]:
    return (np.zeros((n_q, 0), np.int64), np.zeros((n_q, 0), np.float32))


def _query_matrix(table: MatrixServer, vecs: np.ndarray, k: int,
                  metric: str) -> Tuple[np.ndarray, np.ndarray]:
    if vecs.shape[1] != table.num_col:
        raise ValueError(f"query dim {vecs.shape[1]} != table width "
                         f"{table.num_col}")
    if table.num_row == 0:
        return _empty(len(vecs))
    # logical block only: the padded scratch rows must never rank
    block = table.data[:table.num_row, :table.num_col]
    return _jit_block_topk(block, vecs, min(k, table.num_row), metric)


def _query_sparse(table: SparseServer, vecs: np.ndarray, k: int,
                  metric: str) -> Tuple[np.ndarray, np.ndarray]:
    if vecs.shape[1] != table.width:
        raise ValueError(f"query dim {vecs.shape[1]} != table width "
                         f"{table.width}")
    store = table._store
    if not store:
        return _empty(len(vecs))
    keys = np.fromiter(store.keys(), dtype=np.int64, count=len(store))
    keys.sort()  # index order = id order, for the top_k tie contract
    block = np.stack([store[key] for key in keys.tolist()]).astype(
        np.float32, copy=False)
    idx, scores = _jit_block_topk(block, vecs, min(k, len(keys)), metric)
    return keys[idx], scores


def _query_tiered(table: TieredSparseServer, vecs: np.ndarray, k: int,
                  metric: str) -> Tuple[np.ndarray, np.ndarray]:
    if vecs.shape[1] != table.width:
        raise ValueError(f"query dim {vecs.shape[1]} != table width "
                         f"{table.width}")
    parts: List[Tuple[np.ndarray, np.ndarray]] = []
    with wait_site("query_scan"):
        for keys, rows, quant in table._tier.scan_blocks():
            if not len(keys):
                continue
            if quant is not None:
                lo, step, bits, codes = quant
                if bits >= _COMPRESSED_MIN_BITS:
                    count("QUERY_COMPRESSED_SEGMENTS")
                    scores = _score_codes(vecs, codes, lo, step, metric)
                else:
                    # too coarse to fold: dequantize (identical values
                    # to the fetch path's quant_decode) and score plain
                    rows = (np.float32(lo)
                            + codes * np.float32(step)).astype(
                                np.float32, copy=False)
                    scores = _score_rows(vecs, rows, metric)
                count("QUERY_COLD_SEGMENTS_SCANNED")
            else:
                if rows.dtype != np.float32:
                    rows = rows.astype(np.float32)
                scores = _score_rows(vecs, rows, metric)
            parts.append(_block_topk_np(keys, scores, k))
            # running merge: the candidate set stays <= 2k wide however
            # many cold segments the scan streams through
            if len(parts) > 1:
                parts = [merge_topk(parts, k)]
    if not parts:
        return _empty(len(vecs))
    return merge_topk(parts, k)


def query_table(server_table, request) -> Tuple[np.ndarray, np.ndarray]:
    """Serve one query against one server table: ``(vecs, k, metric)``
    -> ``(ids int64 (n_q, k'), scores float32 (n_q, k'))`` with
    ``k' = min(k, rows)``, in contract order. Matrix ids are
    shard-local row indices, sparse/tiered ids are keys — the shard
    router re-globalizes. Refuses kinds without row-shaped scorable
    state loudly."""
    vecs, k, metric = check_request(request)
    table = server_table._unwrapped()
    if isinstance(table, MatrixServer):
        return _query_matrix(table, vecs, k, metric)
    if isinstance(table, TieredSparseServer):
        return _query_tiered(table, vecs, k, metric)
    if isinstance(table, SparseFTRLServer):
        raise TypeError("top-k query is unsupported on FTRL tables: the "
                        "stored (z, n) state is not the weight vector")
    if isinstance(table, SparseServer):
        return _query_sparse(table, vecs, k, metric)
    raise TypeError(f"top-k query needs row-shaped table state; "
                    f"{type(table).__name__} has none")
