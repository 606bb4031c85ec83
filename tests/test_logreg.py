"""LogisticRegression app tests: objectives, regularizers, sparse features,
PS mode incl. FTRL extension table (reference: Applications/LogisticRegression)."""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.models.logreg import (LogReg, LogRegConfig, PSLogReg,
                                          load_libsvm, make_model, minibatches,
                                          parse_libsvm_line)


def dense_blobs(rng, n=1200, dim=10):
    """Two separable gaussian blobs."""
    half = n // 2
    x0 = rng.normal(-1.0, 1.0, (half, dim)).astype(np.float32)
    x1 = rng.normal(+1.0, 1.0, (half, dim)).astype(np.float32)
    x = np.concatenate([x0, x1])
    y = np.concatenate([np.zeros(half, np.int32), np.ones(half, np.int32)])
    order = rng.permutation(n)
    return {"x": x[order], "y": y[order]}


def sparse_from_dense(data, max_nnz):
    n, dim = data["x"].shape
    idx = np.tile(np.arange(dim, dtype=np.int32), (n, 1))
    pad = max_nnz - dim
    if pad > 0:
        idx = np.concatenate([idx, np.full((n, pad), -1, np.int32)], axis=1)
        val = np.concatenate(
            [data["x"], np.zeros((n, pad), np.float32)], axis=1)
    else:
        val = data["x"]
    return {"idx": idx, "val": val, "y": data["y"]}


def _train(model, data, epochs=5, batch=128, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(epochs):
        for mb in minibatches(data, batch, rng):
            model.update(mb)
    return model


def test_sigmoid_dense_learns(mv_env):
    rng = np.random.default_rng(0)
    data = dense_blobs(rng)
    model = _train(LogReg(LogRegConfig(input_size=10)), data)
    assert model.test(data) > 0.95


def test_softmax_multiclass_learns(mv_env):
    rng = np.random.default_rng(0)
    n, dim, classes = 1500, 8, 3
    centers = rng.normal(0, 3.0, (classes, dim))
    y = rng.integers(0, classes, n).astype(np.int32)
    x = (centers[y] + rng.normal(0, 1.0, (n, dim))).astype(np.float32)
    data = {"x": x, "y": y}
    config = LogRegConfig(input_size=dim, output_size=classes,
                          objective="softmax", lr=0.5)
    model = _train(LogReg(config), data)
    assert model.test(data) > 0.9


def test_l2_shrinks_weights(mv_env):
    rng = np.random.default_rng(0)
    data = dense_blobs(rng)
    plain = _train(LogReg(LogRegConfig(input_size=10)), data)
    reg = _train(LogReg(LogRegConfig(input_size=10, regular="l2",
                                     regular_coef=0.5)), data)
    assert np.linalg.norm(reg.weights()) < np.linalg.norm(plain.weights())


def test_sparse_matches_dense(mv_env):
    rng = np.random.default_rng(0)
    data = dense_blobs(rng, dim=6)
    sdata = sparse_from_dense(data, max_nnz=8)
    dense = _train(LogReg(LogRegConfig(input_size=6, seed=1)), data)
    sparse = _train(LogReg(LogRegConfig(input_size=6, sparse=True, max_nnz=8,
                                        seed=1)), sdata)
    np.testing.assert_allclose(dense.weights(), sparse.weights(),
                               rtol=1e-3, atol=1e-4)


def test_ps_mode_learns(mv_env):
    rng = np.random.default_rng(0)
    data = dense_blobs(rng)
    config = LogRegConfig(input_size=10, use_ps=True, sync_frequency=2)
    model = _train(make_model(config), data)
    assert isinstance(model, PSLogReg)
    model.finish()
    assert model.test(data) > 0.95


def test_ps_pipeline_mode(mv_env):
    rng = np.random.default_rng(0)
    data = dense_blobs(rng)
    config = LogRegConfig(input_size=10, use_ps=True, sync_frequency=2,
                          pipeline=True)
    model = _train(make_model(config), data)
    model.finish()
    assert model.test(data) > 0.95


def test_ftrl_table_learns_and_is_sparse(mv_env):
    rng = np.random.default_rng(0)
    data = dense_blobs(rng)
    # only 10 informative features + 20 noise features
    noise = rng.normal(0, 0.01, (len(data["y"]), 20)).astype(np.float32)
    data = {"x": np.concatenate([data["x"], noise], axis=1), "y": data["y"]}
    config = LogRegConfig(input_size=30, objective="ftrl", use_ps=True,
                          alpha=0.5, lambda1=0.02, lambda2=0.1)
    model = _train(make_model(config), data, epochs=5)
    model.finish()
    assert model.test(data) > 0.9
    w = model.weights()[0, :-1]
    # L1 shrinkage must zero out some of the pure-noise coordinates
    assert (w[10:] == 0.0).sum() > 5


def test_libsvm_parsing(tmp_path):
    path = str(tmp_path / "data.svm")
    with open(path, "w") as fp:
        fp.write("1 0:0.5 3:1.5\n0 1:2.0\n")
    data = load_libsvm(path, max_nnz=4)
    np.testing.assert_array_equal(data["y"], [1, 0])
    np.testing.assert_array_equal(data["idx"][0], [0, 3, -1, -1])
    np.testing.assert_allclose(data["val"][0], [0.5, 1.5, 0, 0])
    label, idx, val = parse_libsvm_line("1 2:3", 2)
    assert label == 1 and idx[0] == 2 and val[0] == 3.0


def test_sparse_ftrl_trains_through_the_keyed_table(mv_env, monkeypatch):
    """Sparse FTRL with one output pulls its batch's keys from the keyed
    FTRL table and pushes their gradients (no dense replica of the key
    space: `model.w` is None). Over a seeded stream its losses equal those
    of the path it replaces (the host dictionaries of `sparse_ftrl` behind a
    dense replica pulled after every batch) within 2e-5, the two tables'
    float32 roundings; the weights agree, and it predicts as well."""
    from multiverso_tpu.models.logreg import PSLogReg

    rng = np.random.default_rng(12)
    features, nnz, samples = 400, 6, 1536
    true_w = np.zeros(features, np.float32)
    true_w[:24] = rng.normal(0, 3.0, 24)
    idx = np.stack([rng.choice(features, nnz, replace=False)
                    for _ in range(samples)]).astype(np.int32)
    idx[rng.random(idx.shape) < 0.2] = -1          # ragged samples
    val = np.ones(idx.shape, np.float32)
    logits = (np.where(idx >= 0, true_w[np.maximum(idx, 0)], 0)).sum(axis=1)
    y = (rng.random(samples) < 1 / (1 + np.exp(-logits))).astype(np.int32)
    data = {"idx": idx, "val": val, "y": y}
    config = LogRegConfig(input_size=features, objective="ftrl", use_ps=True,
                          sparse=True, max_nnz=nnz, minibatch=64, alpha=0.5,
                          lambda1=0.01, lambda2=0.1, updater_type="ftrl")
    keyed = make_model(config)
    assert keyed.w is None and keyed.table.size == features + 1
    monkeypatch.setattr(PSLogReg, "_keyed_ftrl", lambda self: False)
    replica = make_model(config)
    monkeypatch.undo()
    assert replica.w is not None
    losses = {"keyed": [], "replica": []}
    for _ in range(2):
        for batch in minibatches(data, config.minibatch):
            losses["keyed"].append(keyed.update(batch))
            losses["replica"].append(replica.update(batch))
    keyed.finish()
    replica.finish()
    want_w = replica.weights()
    want_acc = replica.test(data)
    assert len(losses["keyed"]) == 48
    np.testing.assert_allclose(losses["keyed"], losses["replica"], atol=2e-5,
                               rtol=0)
    assert np.mean(losses["keyed"][-8:]) < losses["keyed"][0] - 0.005
    np.testing.assert_allclose(keyed.weights(), want_w, atol=1e-5)
    assert keyed.test(data) == pytest.approx(want_acc, abs=0.005) \
        and want_acc > 0.55
