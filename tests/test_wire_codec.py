"""The wire codec's choice between a float32 array and its sparse form:
the frames are the bytes the rule "run ``sparse_encode``, keep its output
where shorter" gives, a dense array never reaches the encoder, and the
counters say which way each array went."""

import json

import numpy as np
import pytest

from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.runtime import wire
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import quantization

SHAPES = {63: (63,), 64: (64,), 65: (5, 13), 4096: (64, 64),
          131072: (1024, 128)}
# nonzero counts by name, around the two thresholds: the encoder goes
# sparse while 2 * nnz < size, and its 24 + 8 * nnz bytes are the shorter
# up to size/2 - 4 nonzeros of an even size
NNZ = {"none": lambda size: 0,
       "half-4": lambda size: size // 2 - 4,
       "half-3": lambda size: size // 2 - 3,
       "half-2": lambda size: size // 2 - 2,
       "half": lambda size: size // 2,
       "all": lambda size: size}
GRID = [(size, name) for size in SHAPES for name in NNZ]


@pytest.fixture(params=["numpy", "native"])
def codec_path(request, monkeypatch, native_lib):
    """Pin ``sparse_encode`` to one implementation for the test. The
    library is built by then (``native_lib``), on any tree and whichever
    worker runs this file."""
    if request.param == "numpy":
        monkeypatch.setattr(quantization, "_native_load_attempted", True)
        monkeypatch.setattr(quantization, "_native", None)
        return request.param
    if not quantization.native_available():
        pytest.skip("native library does not load")
    return request.param


def values_of(size, nnz_name, seed=0):
    """A float32 array of SHAPES[size] with exactly that many nonzeros at
    seeded places: a NaN and a negative among them, ``-0.0`` among the
    zeros."""
    nnz = NNZ[nnz_name](size)
    rng = np.random.default_rng(size * 31 + nnz + seed)
    order = rng.permutation(size)
    flat = np.zeros(size, np.float32)
    live = order[:nnz]
    flat[live] = (rng.integers(1, 1024, nnz) / 64).astype(np.float32)
    if nnz >= 2:
        flat[live[0]] = np.nan
        flat[live[1]] = -2.5
    if size - nnz >= 1:
        flat[order[nnz]] = -0.0
    assert np.count_nonzero(flat != 0) == nnz
    return flat.reshape(SHAPES[size])


def add_request(values):
    """What a remote row Add sends: ``(ids, values, option)``."""
    ids = np.arange(values.shape[0], dtype=np.int32)
    return (ids, values, AddOption(3, 0.0, 0.5, 0.0, 0.0))


def parent_frames(request, force_numpy):
    """The frames of the rule as it stood: every float32 array of 64
    elements or more goes through ``sparse_encode`` first, and the result
    is kept where it is shorter than the array."""
    ids, values, option = request
    blobs = [ids]
    payload = quantization.sparse_encode(values, force_numpy=force_numpy)
    if values.size >= 64 and len(payload) < values.nbytes:
        blobs.append(np.frombuffer(payload, dtype=np.uint8))
        leaf = {"t": "sparse", "i": 1, "shape": list(values.shape)}
    else:
        blobs.append(values)
        leaf = {"t": "arr", "i": 1}
    tree = {"t": "tuple", "items": [
        {"t": "arr", "i": 0}, leaf,
        {"t": "addopt", "v": [option.worker_id, option.momentum,
                              option.learning_rate, option.rho,
                              option.lambda_]}]}
    head = np.frombuffer(json.dumps(tree).encode(), dtype=np.uint8)
    return [head] + blobs


@pytest.mark.parametrize("size,nnz_name", GRID)
def test_frames_identical_to_encode_first_rule(codec_path, size, nnz_name):
    request = add_request(values_of(size, nnz_name))
    expected = parent_frames(request, force_numpy=codec_path == "numpy")
    frames = wire.encode(request, compress=True)
    assert len(frames) == len(expected)
    for got, want in zip(frames, expected):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    # and the rule's own arithmetic, from the count
    nnz = NNZ[nnz_name](size)
    sparse = b'"sparse"' in frames[0].tobytes()
    assert sparse == (size >= 64 and 2 * nnz < size
                      and 24 + 8 * nnz < 4 * size)


@pytest.mark.parametrize("size,nnz_name", GRID)
def test_decode_returns_the_bits(size, nnz_name):
    request = add_request(values_of(size, nnz_name, seed=1))
    frames = wire.encode(request, compress=True)
    ids, values, option = wire.decode(frames)
    assert values.dtype == np.float32 and values.shape == request[1].shape
    # bit for bit: NaN payloads and the sign of -0.0 are only visible so.
    # The sparse form drops -0.0 for +0.0, as it did: compare those as 0
    want = request[1].view(np.uint32).copy()
    got = np.ascontiguousarray(values).view(np.uint32)
    if b'"sparse"' in frames[0].tobytes():
        want[want == 0x80000000] = 0
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ids, request[0])
    assert option == request[2]


@pytest.mark.parametrize("size,nnz_name", [
    (size, name) for size in (64, 65, 4096, 131072)
    for name in ("half-2", "half", "all")])
def test_dense_array_never_reaches_the_encoder(monkeypatch, size, nnz_name):
    def refuse(*args, **kwargs):
        raise AssertionError("sparse_encode ran for a dense array")
    monkeypatch.setattr(wire, "sparse_encode", refuse)
    monkeypatch.setattr(quantization, "sparse_encode", refuse)
    values = values_of(size, nnz_name)
    frames = wire.encode(add_request(values), compress=True)
    assert np.shares_memory(frames[2], values)
    assert frames[2].shape == values.shape


def counters():
    return (Dashboard.counter_value("WIRE_FLOAT_DENSE"),
            Dashboard.counter_value("WIRE_FLOAT_SPARSE"))


@pytest.mark.parametrize("case,dense,sparse", [
    ("dense", 1, 0), ("sparse", 0, 1), ("one of each", 1, 1),
    ("compress off", 0, 0), ("float64", 0, 0), ("under 64", 0, 0),
    ("int ids only", 0, 0)])
def test_counters_move_by_one_an_array(case, dense, sparse):
    full = values_of(4096, "all")
    empty = values_of(4096, "none")
    payload, compress = {
        "dense": (add_request(full), True),
        "sparse": (add_request(empty), True),
        "one of each": ([full, {"rows": empty}], True),
        "compress off": ([full, empty], False),
        "float64": (full.astype(np.float64), True),
        "under 64": ((values_of(63, "all"), values_of(63, "none")), True),
        "int ids only": (np.arange(4096, dtype=np.int32), True),
    }[case]
    before = counters()
    wire.encode(payload, compress=compress)
    after = counters()
    assert (after[0] - before[0], after[1] - before[1]) == (dense, sparse)


@pytest.mark.parametrize("size,nnz_name", GRID)
def test_sparse_is_shorter_is_the_length_comparison(size, nnz_name):
    """The count's answer against the encoder's actual length, below the
    wire's 64-element floor too."""
    values = values_of(size, nnz_name)
    shorter = len(quantization.sparse_encode(values, force_numpy=True)) \
        < values.nbytes
    assert quantization.sparse_is_shorter(values) == shorter


def test_sparse_is_shorter_counts_in_pieces(monkeypatch):
    """Several pieces give the one-piece answer on both sides of the
    threshold, and a dense array stops the count early."""
    monkeypatch.setattr(quantization, "_COUNT_STEP", 1000)
    for name in NNZ:
        values = values_of(4096, name)
        shorter = len(quantization.sparse_encode(values, force_numpy=True)) \
            < values.nbytes
        assert quantization.sparse_is_shorter(values) == shorter
    full = values_of(4096, "all")
    seen = []
    real = np.count_nonzero
    monkeypatch.setattr(np, "count_nonzero",
                        lambda a: seen.append(a.size) or real(a))
    assert not quantization.sparse_is_shorter(full)
    assert seen == [1000, 1000, 1000]  # 3,000 of 4,096: over half
    assert not quantization.sparse_is_shorter(np.zeros(0, np.float32))
