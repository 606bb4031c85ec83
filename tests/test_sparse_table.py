"""Sparse-key table tests — arbitrary integer keys, O(nnz) traffic
(reference: Applications/LogisticRegression/src/util/sparse_table.h:17-168,
util/ftrl_sparse_table.h:12-90)."""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.io import MemoryStream
from multiverso_tpu.models.logreg import LogRegConfig, make_model, minibatches
from multiverso_tpu.tables.sparse_table import (SparseServer, SparseWorker,
                                                make_sparse_ftrl)


def _register():
    mv.register_table_type("sparse", SparseWorker)
    mv.register_table_type("sparse_ftrl", make_sparse_ftrl)


def test_sparse_huge_keyspace_add_get(mv_env):
    """Keys live in a 1e9 space; memory and traffic are ∝ live keys."""
    _register()
    t = mv.create_table("sparse", 1_000_000_000, width=3)
    t.add([5, 999_999_999], np.array([[1, 2, 3], [4, 5, 6]], np.float32))
    out = t.get([5, 7, 999_999_999])
    np.testing.assert_allclose(out, [[1, 2, 3], [0, 0, 0], [4, 5, 6]])
    # accumulation on an existing key
    t.add([5], np.array([[1, 1, 1]], np.float32))
    np.testing.assert_allclose(t.get([5]), [[2, 3, 4]])
    # get-all returns live entries only, sorted
    live, vals = t.get()
    np.testing.assert_array_equal(live, [5, 999_999_999])
    assert vals.shape == (2, 3)
    assert len(t._server_table._store) == 2  # memory ∝ live keys


def test_sparse_sgd_updater_sign(mv_env):
    _register()
    t = mv.create_table("sparse", 100, width=1, updater_type="sgd")
    t.add([3], np.array([[2.0]], np.float32))
    np.testing.assert_allclose(t.get([3]), [[-2.0]])


def test_sparse_key_out_of_range_fatal(mv_env):
    _register()
    t = mv.create_table("sparse", 10, width=1)
    with pytest.raises(Exception):
        t.add([10], np.array([[1.0]], np.float32))


def test_sparse_ftrl_matches_dense_ftrl(mv_env):
    """The struct-valued sparse FTRL server must produce the same weights as
    the dense FTRL table for the same gradient stream."""
    from multiverso_tpu.tables.ftrl_table import FTRLWorker
    _register()
    mv.register_table_type("ftrl", FTRLWorker)
    kw = dict(alpha=0.5, beta=1.0, lambda1=0.02, lambda2=0.1)
    dense = mv.create_table("ftrl", 4, **kw)
    sparse = mv.create_table("sparse_ftrl", 1_000_000, width=1, **kw)
    rng = np.random.default_rng(0)
    keys = np.array([0, 2, 3], np.int64)
    for _ in range(5):
        g = rng.normal(0, 1, 3).astype(np.float32)
        gd = np.zeros(4, np.float32)
        gd[keys] = g
        dense.add(gd)
        sparse.add(keys * 1000, g.reshape(-1, 1))  # scattered keys
    wd = dense.get()
    ws = sparse.get(keys * 1000).reshape(-1)
    np.testing.assert_allclose(ws, wd[keys], rtol=1e-5)
    # untouched key reads as zero weight
    np.testing.assert_allclose(sparse.get([999]), [[0.0]])


def test_sparse_checkpoint_roundtrip(mv_env):
    _register()
    t = mv.create_table("sparse", 10_000, width=2)
    t.add([7, 4242], np.array([[1, 2], [3, 4]], np.float32))
    buf = MemoryStream()
    t._server_table.store(buf)
    buf.seek(0)
    t2 = mv.create_table("sparse", 10_000, width=2)
    t2._server_table.load(buf)
    np.testing.assert_allclose(t2.get([7, 4242]), [[1, 2], [3, 4]])


def test_sparse_ftrl_checkpoint_roundtrip(mv_env):
    _register()
    t = mv.create_table("sparse_ftrl", 1000, width=1, alpha=0.5)
    t.add([3, 9], np.array([[1.0], [2.0]], np.float32))
    buf = MemoryStream()
    t._server_table.store(buf)
    buf.seek(0)
    t2 = mv.create_table("sparse_ftrl", 1000, width=1, alpha=0.5)
    t2._server_table.load(buf)
    np.testing.assert_allclose(t2.get([3, 9]), t.get([3, 9]))


def test_remote_sparse_table():
    """Sparse table served over the wire: O(nnz) payloads cross processes."""
    _register()
    mv.init(remote_workers=1)
    t = mv.create_table("sparse", 1_000_000, width=2)
    endpoint = mv.serve("127.0.0.1:0")
    client = mv.remote_connect(endpoint)
    rt = client.tables()[0]
    rt.add([123_456], np.array([[1.5, 2.5]], np.float32))
    np.testing.assert_allclose(rt.get([123_456, 777]),
                               [[1.5, 2.5], [0, 0]])
    live, vals = rt.get()
    np.testing.assert_array_equal(live, [123_456])
    # server sees the same state locally
    np.testing.assert_allclose(t.get([123_456]), [[1.5, 2.5]])
    client.close()
    mv.shutdown()


# -- sparse PS logreg: the O(nnz) push contract ------------------------------

def _scattered_sparse_blobs(rng, n=1200, dim=10, input_size=1000):
    """Separable blobs whose features live at scattered high ids."""
    half = n // 2
    x0 = rng.normal(-1.0, 1.0, (half, dim)).astype(np.float32)
    x1 = rng.normal(+1.0, 1.0, (half, dim)).astype(np.float32)
    x = np.concatenate([x0, x1])
    y = np.concatenate([np.zeros(half, np.int32), np.ones(half, np.int32)])
    order = rng.permutation(n)
    feat_ids = (np.arange(dim, dtype=np.int32) * 97 + 13)  # scattered
    idx = np.tile(feat_ids, (n, 1))
    return {"idx": idx[order], "val": x[order], "y": y[order]}


def test_ps_sparse_push_is_o_nnz_and_learns(mv_env):
    rng = np.random.default_rng(0)
    input_size = 1000
    data = _scattered_sparse_blobs(rng, input_size=input_size)
    config = LogRegConfig(input_size=input_size, sparse=True, max_nnz=10,
                          use_ps=True, sync_frequency=2, lr=0.1)
    model = make_model(config)
    n_updates = 0
    for _ in range(5):
        for mb in minibatches(data, 128, rng):
            model.update(mb)
            n_updates += 1
    model.finish()
    assert model.test(data) > 0.95
    # push payload ∝ nnz: 10 touched features + bias per minibatch, width 1
    expected = n_updates * 11
    assert model.table.elements_pushed == expected
    dense_would_be = n_updates * (input_size + 1)
    assert model.table.elements_pushed < dense_would_be / 50


def test_ps_sparse_ftrl_learns(mv_env):
    rng = np.random.default_rng(1)
    input_size = 5000
    data = _scattered_sparse_blobs(rng, input_size=input_size)
    config = LogRegConfig(input_size=input_size, sparse=True, max_nnz=10,
                          objective="ftrl", use_ps=True, alpha=0.5,
                          lambda1=0.02, lambda2=0.1)
    model = make_model(config)
    for _ in range(5):
        for mb in minibatches(data, 128, rng):
            model.update(mb)
    model.finish()
    assert model.test(data) > 0.9
    # since PR 40 one output trains through the keyed FTRL table, whose
    # (z, n) hold the key space on the device: the keys stepped are the
    # live ones (10 features and the bias), the rest keep their zeros
    assert model.w is None
    assert np.count_nonzero(np.asarray(
        model.table.get_state_device("n"))) == 11