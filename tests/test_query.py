"""Query plane: server-side top-k retrieval pushdown (multiverso_tpu/
query/, docs/serving.md §8).

The acceptance properties from the plane's charter:

* **ordering contract** — every path ranks by score descending, ties by
  ascending global id; the engine's answer over integer-valued data is
  bit-identical to a plain numpy lexsort oracle;
* **sharded correctness** — the global top-k merged from per-shard
  partials (split_request + merge_topk) has the ids, in the order, of a
  single-shard oracle over the same rows, for dot and cosine, on matrix
  and sparse (hash and range) tables, including tie boundaries and
  ragged (shard-smaller-than-k) replies; dot scores are bit-identical,
  cosine scores bit-identical on sparse tables and within
  ``COSINE_SCORE_ATOL`` on the matrix table (float32 sums of normalised
  rows reorder between block shapes);
* **tiered scans never promote** — a query over a beyond-RAM tiered
  table streams the cold segments without touching the promotion
  sketch, the fetch cache or the hot dict: TIER_PROMOTIONS and the
  hot/cold hit counters stay flat, and a lossless (cold_bits=0) tier
  answers as an all-in-RAM SparseServer does, under the same rule;
* **replica serving** — a replica-routed query is answered by the read
  tier with ZERO Query dispatches on the primary.

``make query`` runs this file plus the examples/word2vec_query.py
neighbor drill.
"""

import time

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.query.engine import (check_request, merge_topk,
                                         order_rows, query_table)
from multiverso_tpu.runtime.message import MsgType
from multiverso_tpu.runtime.read import cache_key
from multiverso_tpu.shard.partition import (HashPartitioner,
                                            RangePartitioner)
from multiverso_tpu.shard.router import split_request
from multiverso_tpu.updaters import AddOption

OPT = AddOption(worker_id=0)


def _int_block(rng, n, dim):
    """Integer-valued float32 rows: float32 dot products of these are
    exact, so oracle comparisons can demand bitwise equality."""
    return rng.integers(-8, 9, size=(n, dim)).astype(np.float32)


def _numpy_oracle(ids, rows, vecs, k, metric="dot"):
    """Plain-numpy top-k under THE ordering contract — no engine code."""
    rows = rows.astype(np.float32)
    vecs = vecs.astype(np.float32)
    if metric == "cosine":
        eps = np.float32(1e-30)
        vecs = vecs / np.maximum(
            np.linalg.norm(vecs, axis=1, keepdims=True), eps)
        rows = rows / np.maximum(
            np.linalg.norm(rows, axis=1, keepdims=True), eps)
    scores = vecs @ rows.T
    ids = np.broadcast_to(np.asarray(ids, np.int64).reshape(1, -1),
                          scores.shape)
    order = np.lexsort((ids, -scores), axis=-1)
    ids = np.take_along_axis(np.ascontiguousarray(ids), order, axis=1)
    scores = np.take_along_axis(scores, order, axis=1)
    k = min(k, scores.shape[1])
    return ids[:, :k], scores[:, :k].astype(np.float32)


# float32 sums of normalised rows legitimately reorder between block
# shapes (a 12-row shard and the 37-row whole compile to different dot
# loops) and between numpy and the jitted kernel: one unit in the last
# place of a term, 6e-8. The tolerance is absolute because a cosine is at
# most 1 and a sum that cancels (orthogonal rows: 0 on one side, 5.6e-8 on
# the other) has no relative error to bound.
COSINE_SCORE_ATOL = 1e-6


def _assert_same_topk(got, want, metric, msg="", exact_scores=False):
    """ids exactly; scores exactly for ``dot`` (integer-valued rows:
    every float32 sum is exact) and wherever ``exact_scores`` is asked,
    else to ``COSINE_SCORE_ATOL`` for ``cosine``."""
    np.testing.assert_array_equal(got[0], want[0], err_msg=msg)
    if metric == "cosine" and not exact_scores:
        np.testing.assert_allclose(got[1], want[1], rtol=0,
                                   atol=COSINE_SCORE_ATOL, err_msg=msg)
    else:
        np.testing.assert_array_equal(got[1], want[1], err_msg=msg)


# -- units: request validation + merge algebra --------------------------------

def test_check_request_normalizes_and_rejects():
    vecs, k, metric = check_request(([1.0, 2.0, 3.0], 4, "dot"))
    assert vecs.shape == (1, 3) and vecs.dtype == np.float32
    assert k == 4 and metric == "dot"
    with pytest.raises(ValueError, match="vecs, k, metric"):
        check_request("nope")
    with pytest.raises(ValueError, match="k must be >= 1"):
        check_request((np.ones((1, 3)), 0, "dot"))
    with pytest.raises(ValueError, match="metric"):
        check_request((np.ones((1, 3)), 2, "euclid"))
    with pytest.raises(ValueError, match="n_q, dim"):
        check_request((np.ones((2, 2, 2)), 2, "dot"))


def test_merge_topk_ragged_and_ties():
    # shard A replies 1 candidate (fewer than k), shard B replies 3;
    # ids 7 and 2 tie at score 5 -> the LOWER id must rank first
    a = (np.array([[7]], np.int64), np.array([[5.0]], np.float32))
    b = (np.array([[2, 9, 4]], np.int64),
         np.array([[5.0, 1.0, 3.0]], np.float32))
    ids, scores = merge_topk([a, b], 3)
    np.testing.assert_array_equal(ids, [[2, 7, 4]])
    np.testing.assert_array_equal(scores, [[5.0, 5.0, 3.0]])
    # k wider than the union: reply stays at the union width
    ids, _ = merge_topk([a, b], 99)
    assert ids.shape == (1, 4)


def test_order_rows_contract_matches_lexsort():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 50, size=(3, 12)).astype(np.int64)
    scores = rng.integers(-3, 4, size=(3, 12)).astype(np.float32)
    got_ids, got_scores = order_rows(ids.copy(), scores.copy())
    order = np.lexsort((ids, -scores), axis=-1)
    np.testing.assert_array_equal(got_ids,
                                  np.take_along_axis(ids, order, axis=1))
    np.testing.assert_array_equal(got_scores,
                                  np.take_along_axis(scores, order, axis=1))


def test_query_cache_key_is_namespaced_and_exact():
    vecs = np.ones((2, 3), np.float32)
    q1 = cache_key(5, ("query", (vecs, 4, "dot")))
    q2 = cache_key(5, ("query", (vecs.copy(), 4, "dot")))
    assert q1 is not None and q1 == q2  # bytes-exact: same query hits
    assert q1 != cache_key(5, ("query", (vecs, 5, "dot")))  # k differs
    assert q1 != cache_key(5, ("query", (vecs, 4, "cosine")))
    assert q1 != cache_key(5, (vecs, 4, "dot"))  # no Get collision


# -- engine vs numpy oracle, per table kind -----------------------------------

def test_matrix_query_matches_numpy_oracle(mv_env):
    from multiverso_tpu.tables.matrix_table import MatrixServer
    rows, cols = 23, 6
    rng = np.random.default_rng(1)
    data = _int_block(rng, rows, cols)
    data[11] = data[3]  # planted tie: equal scores, ids 3 < 11
    server = MatrixServer(rows, cols, np.float32)
    server.process_add((None, data, OPT))
    vecs = _int_block(rng, 4, cols)
    for k in (1, 5, rows + 10):  # k past num_row clamps to num_row
        ids, scores = query_table(server, (vecs, k, "dot"))
        want_ids, want_scores = _numpy_oracle(
            np.arange(rows), data, vecs, k, "dot")
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(scores, want_scores)
    with pytest.raises(ValueError, match="dim"):
        query_table(server, (np.ones((1, cols + 1)), 2, "dot"))


def test_matrix_query_cosine_finds_self(mv_env):
    from multiverso_tpu.tables.matrix_table import MatrixServer
    rows, cols = 16, 8
    rng = np.random.default_rng(2)
    data = rng.standard_normal((rows, cols)).astype(np.float32)
    server = MatrixServer(rows, cols, np.float32)
    server.process_add((None, data, OPT))
    # scaling preserves cosine: 3x a row still cosine-matches itself
    probes = np.array([0, 7, 15])
    ids, scores = query_table(server, (3.0 * data[probes], 1, "cosine"))
    np.testing.assert_array_equal(ids[:, 0], probes)
    np.testing.assert_allclose(scores[:, 0], 1.0, atol=1e-5)


def test_sparse_query_matches_numpy_oracle(mv_env):
    from multiverso_tpu.tables.sparse_table import SparseServer
    rng = np.random.default_rng(3)
    keys = np.array([2, 5, 11, 40, 41, 97], np.int64)
    vals = _int_block(rng, len(keys), 4)
    server = SparseServer(100, 4)
    server.process_add((keys, vals, None))
    vecs = _int_block(rng, 3, 4)
    ids, scores = query_table(server, (vecs, 4, "dot"))
    want_ids, want_scores = _numpy_oracle(keys, vals, vecs, 4, "dot")
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(scores, want_scores)


def test_empty_and_unsupported_tables(mv_env):
    from multiverso_tpu.tables.sparse_table import (SparseFTRLServer,
                                                    SparseServer)
    empty = SparseServer(100, 4)
    ids, scores = query_table(empty, (np.ones((2, 4)), 3, "dot"))
    assert ids.shape == (2, 0) and scores.shape == (2, 0)
    ftrl = SparseFTRLServer(100, 4)
    with pytest.raises(TypeError, match="FTRL"):
        query_table(ftrl, (np.ones((1, 4)), 1, "dot"))


# -- tiered: beyond-RAM scans that never promote ------------------------------

def _tiered_pair(tmp_path, key_space, width, cold_bits, resident_rows,
                 rng, plant=None):
    """A TieredSparseServer (mostly cold) and a plain SparseServer with
    the SAME rows; ``plant=(idx, row)`` overwrites one row pre-seed."""
    from multiverso_tpu.tables.sparse_table import (SparseServer,
                                                    TieredSparseServer)
    tiered = TieredSparseServer(
        key_space, width, resident_bytes=resident_rows * width * 4,
        cold_bits=cold_bits, tier_dir=str(tmp_path))
    plain = SparseServer(key_space, width)
    keys = np.arange(key_space, dtype=np.int64)
    vals = _int_block(rng, key_space, width)
    if plant is not None:
        vals[plant[0]] = plant[1]
    for start in range(0, key_space, 16):
        sl = slice(start, start + 16)
        tiered.process_add((keys[sl], vals[sl], None))
        plain.process_add((keys[sl], vals[sl], None))
    return tiered, plain, keys, vals


def test_tiered_lossless_query_matches_plain_and_never_promotes(
        mv_env, tmp_path):
    """A lossless tier answers as the all-in-RAM table does: the same ids
    in the same order, ``dot`` scores to the bit, ``cosine`` scores to
    ``COSINE_SCORE_ATOL`` (the tier scores in numpy, the plain table in
    the jitted kernel), and the scan promotes nothing."""
    rng = np.random.default_rng(4)
    tiered, plain, _keys, _vals = _tiered_pair(
        tmp_path, key_space=96, width=4, cold_bits=0, resident_rows=8,
        rng=rng)
    try:
        stats = tiered.tier_stats()
        assert stats["cold_rows"] > 0, "tier never demoted — test is moot"
        hot_before = stats["hot_rows"]
        promo0 = Dashboard.counter_value("TIER_PROMOTIONS")
        hot0 = Dashboard.counter_value("TIER_HOT_HITS")
        cold0 = Dashboard.counter_value("TIER_COLD_HITS")
        vecs = _int_block(rng, 3, 4)
        for metric in ("dot", "cosine"):
            got = query_table(tiered, (vecs, 7, metric))
            want = query_table(plain, (vecs, 7, metric))
            _assert_same_topk(got, want, metric, metric)
        # the scan left the tier exactly where it found it
        assert Dashboard.counter_value("TIER_PROMOTIONS") == promo0
        assert Dashboard.counter_value("TIER_HOT_HITS") == hot0
        assert Dashboard.counter_value("TIER_COLD_HITS") == cold0
        assert tiered.tier_stats()["hot_rows"] == hot_before
    finally:
        tiered._tier.close()


def test_tiered_compressed_domain_scan(mv_env, tmp_path):
    """cold_bits=8 >= the compressed floor: segments score as raw codes
    (QUERY_COMPRESSED_SEGMENTS moves), still without promoting, and a
    planted dominant row is still ranked first."""
    rng = np.random.default_rng(5)
    # plant a dominant row: every element 50 vs |8| elsewhere, so its
    # dot with an all-ones probe (200) clears the field (<= 32) by far
    # more than any 8-bit quantization error can move a score
    tiered, _plain, keys, _vals = _tiered_pair(
        tmp_path, key_space=96, width=4, cold_bits=8, resident_rows=8,
        rng=rng, plant=(17, np.full(4, 50.0, np.float32)))
    try:
        comp0 = Dashboard.counter_value("QUERY_COMPRESSED_SEGMENTS")
        scan0 = Dashboard.counter_value("QUERY_COLD_SEGMENTS_SCANNED")
        promo0 = Dashboard.counter_value("TIER_PROMOTIONS")
        probe = np.ones((1, 4), np.float32)
        ids, _scores = query_table(tiered, (probe, 1, "dot"))
        assert int(ids[0, 0]) == int(keys[17])
        assert (Dashboard.counter_value("QUERY_COMPRESSED_SEGMENTS")
                > comp0)
        assert (Dashboard.counter_value("QUERY_COLD_SEGMENTS_SCANNED")
                > scan0)
        assert Dashboard.counter_value("TIER_PROMOTIONS") == promo0
    finally:
        tiered._tier.close()


# -- sharded: per-shard partials merge to the single-shard oracle -------------

def _run_split_query(kind, part, servers, request, params):
    parts, merge = split_request(kind, part, MsgType.Request_Query,
                                 request, params)
    return merge([query_table(servers[shard], sub)
                  for shard, sub in parts])


def _seed_split(kind, part, servers, keys, vals, params):
    parts, _merge = split_request(kind, part, MsgType.Request_Add,
                                  (keys, vals, OPT if kind == "matrix"
                                   else None), params)
    for shard, sub in parts:
        servers[shard].process_add(sub)


@pytest.mark.parametrize("metric", ["dot", "cosine"])
def test_matrix_shard_query_matches_oracle(mv_env, metric):
    """Per-shard partials merge to the single-shard answer: ids exactly
    (the tie across the shard boundary included), ``dot`` scores to the
    bit, ``cosine`` scores to ``COSINE_SCORE_ATOL``, because a
    shard's block and the whole table's compile to different float32
    summation orders."""
    from multiverso_tpu.tables.matrix_table import MatrixServer
    rows, cols, shards = 37, 5, 3
    part = RangePartitioner(rows, shards)
    whole = MatrixServer(rows, cols, np.float32)
    locals_ = [MatrixServer(part.local_size(s), cols, np.float32)
               for s in range(shards)]
    params = {"num_row": rows, "num_col": cols, "dtype": "<f4"}
    rng = np.random.default_rng(6)
    data = _int_block(rng, rows, cols)
    data[30] = data[2]  # tie straddling a shard boundary: id 2 wins
    ids_all = np.arange(rows, dtype=np.int32)
    whole.process_add((ids_all, data, OPT))
    _seed_split("matrix", part, locals_, ids_all, data, params)
    vecs = _int_block(rng, 4, cols)
    for k in (1, 6, 20):  # 20 > the 12-row shards: ragged merge
        got = _run_split_query("matrix", part, locals_,
                               (vecs, k, metric), params)
        want = query_table(whole, (vecs, k, metric))
        _assert_same_topk(got, want, metric, f"{metric} k={k}")


@pytest.mark.parametrize("part_kind", ["hash", "range"])
@pytest.mark.parametrize("metric", ["dot", "cosine"])
def test_sparse_shard_query_matches_oracle(mv_env, part_kind, metric):
    from multiverso_tpu.tables.sparse_table import SparseServer
    key_space, width, shards = 200, 4, 3
    if part_kind == "range":
        part = RangePartitioner(key_space, shards)
        locals_ = [SparseServer(part.local_size(s), width)
                   for s in range(shards)]
    else:
        part = HashPartitioner(shards)
        locals_ = [SparseServer(key_space, width) for _ in range(shards)]
    whole = SparseServer(key_space, width)
    params = {"key_space": key_space, "width": width}
    rng = np.random.default_rng(7)
    keys = np.sort(rng.choice(key_space, 40, replace=False)).astype(
        np.int64)
    vals = _int_block(rng, len(keys), width)
    vals[31] = vals[4]  # planted cross-shard tie
    whole.process_add((keys, vals, None))
    _seed_split("sparse", part, locals_, keys, vals, params)
    vecs = _int_block(rng, 3, width)
    for k in (1, 7, 60):  # 60 > the 40 live rows: everything, ragged
        got = _run_split_query("sparse", part, locals_,
                               (vecs, k, metric), params)
        want = query_table(whole, (vecs, k, metric))
        # held to the bit, as it has held on every run of the records
        _assert_same_topk(got, want, metric,
                          f"{part_kind} {metric} k={k}", exact_scores=True)


def test_split_query_rejects_rowless_kinds(mv_env):
    part = RangePartitioner(10, 2)
    with pytest.raises(mv.log.FatalError, match="unsupported"):
        split_request("array", part, MsgType.Request_Query,
                      (np.ones((1, 4)), 2, "dot"), {"size": 10})


# -- worker front door + replica serving --------------------------------------

def test_worker_table_query_front_door(mv_env):
    """mv.query against a live in-process table: one pushdown round trip
    through the dispatcher, bit-identical to the numpy oracle."""
    rows, cols = 24, 6
    rng = np.random.default_rng(8)
    data = _int_block(rng, rows, cols)
    table = mv.create_table("matrix", num_row=rows, num_col=cols)
    table.add(data)
    vecs = _int_block(rng, 2, cols)
    ids, scores = mv.query(table, vecs, 5)
    want_ids, want_scores = _numpy_oracle(np.arange(rows), data, vecs, 5)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(scores, want_scores)
    # the WorkerTable method is the same path
    ids2, scores2 = table.query(vecs, 5, metric="dot")
    np.testing.assert_array_equal(ids2, ids)
    np.testing.assert_array_equal(scores2, scores)


def test_replica_served_query_zero_primary_dispatches():
    """A replica-routed query is answered by the read tier: correct
    against the oracle, QUERIES_VIA_REPLICA moves, and the PRIMARY's
    Query dispatch histogram stays exactly flat."""
    from multiverso_tpu.shard.group import ShardGroup
    rows, cols = 48, 6
    rng = np.random.default_rng(9)
    data = _int_block(rng, rows, cols)
    group = ShardGroup(
        [{"kind": "matrix", "num_row": rows, "num_col": cols}],
        shards=1, replicas=1,
        flags={"remote_workers": 4, "heartbeat_seconds": 0.2}).start()
    try:
        mv.set_flag("read_staleness_records", 1 << 30)
        mv.set_flag("client_cache_bytes", 0)
        seed = group.connect(read_preference="primary")
        seed.table(0).add(data, row_ids=np.arange(rows, dtype=np.int32))
        deadline = time.monotonic() + 60
        read_ep = group.replica_endpoints[0][0]
        while time.monotonic() < deadline:
            probe = mv.watermark(read_ep)
            if probe["watermark"] >= 1 and probe["lag"] == 0:
                break
            time.sleep(0.1)

        def primary_query_msgs():
            hist = mv.stats(group.endpoints[0]).histogram(
                "SERVER_PROCESS_QUERY_MSG")
            return hist.count if hist else 0

        primary0 = primary_query_msgs()
        via0 = Dashboard.counter_value("QUERIES_VIA_REPLICA")
        client = mv.remote_connect(group.endpoints[0],
                                   read_endpoints=[read_ep],
                                   read_preference="replica")
        vecs = _int_block(rng, 3, cols)
        ids, scores = client.table(0).query(vecs, 5)
        want_ids, want_scores = _numpy_oracle(np.arange(rows), data,
                                              vecs, 5)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(scores, want_scores)
        assert Dashboard.counter_value("QUERIES_VIA_REPLICA") > via0
        assert primary_query_msgs() == primary0, (
            "replica-routed query dispatched on the PRIMARY")
        client.close()
        seed.close()
    finally:
        group.stop()
