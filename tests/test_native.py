"""Native layer tests: build libmultiverso_tpu.so, exercise the C API from a
real C client (subprocess), the allocator, and the SparseFilter codec
(native + numpy implementations agree byte-for-byte)."""

import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "multiverso_tpu",
                          "native")
C_TEST = os.path.join(NATIVE_DIR, "test_c_api")


@pytest.fixture(scope="session")
def c_test_bin(native_lib, make_native):
    make_native("test_c_api", "CC=gcc")
    return C_TEST


def test_c_api_end_to_end(c_test_bin):
    """A plain C program links the .so, embeds Python, and drives tables."""
    env = dict(os.environ)
    repo = os.path.abspath(os.path.join(NATIVE_DIR, "..", ".."))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    result = subprocess.run([c_test_bin], env=env, capture_output=True,
                            text=True, timeout=240)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "c_api smoke test passed" in result.stdout


def test_native_allocator_pools(native_lib):
    lib = ctypes.CDLL(native_lib)
    lib.MVTPU_Alloc.restype = ctypes.c_void_p
    lib.MVTPU_Alloc.argtypes = [ctypes.c_size_t]
    lib.MVTPU_Free.argtypes = [ctypes.c_void_p]
    lib.MVTPU_Refer.argtypes = [ctypes.c_void_p]

    p = lib.MVTPU_Alloc(100)  # bucketed to 128
    assert p
    # refcounting: a second reference keeps the block live across one Free
    lib.MVTPU_Refer(p)
    lib.MVTPU_Free(p)
    ctypes.memset(p, 0x5A, 100)  # still valid
    pooled_before = lib.MVTPU_AllocatorPooledBlocks()
    lib.MVTPU_Free(p)
    assert lib.MVTPU_AllocatorPooledBlocks() == pooled_before + 1
    # reuse from the pool
    q = lib.MVTPU_Alloc(120)
    assert q == p  # same 128-byte bucket, LIFO reuse
    lib.MVTPU_Free(q)


@pytest.mark.parametrize("force_numpy", [True, False])
def test_sparse_filter_roundtrip(native_lib, force_numpy):
    from multiverso_tpu.utils import quantization as q
    rng = np.random.default_rng(0)
    # sparse case
    data = np.zeros(1000, np.float32)
    idx = rng.choice(1000, 50, replace=False)
    data[idx] = rng.normal(size=50).astype(np.float32)
    payload = q.sparse_encode(data, force_numpy=force_numpy)
    assert len(payload) < 1000 * 4  # actually compressed
    out = q.sparse_decode(payload, 1000, force_numpy=force_numpy)
    np.testing.assert_array_equal(out, data)
    # dense case passes through
    dense = rng.normal(size=256).astype(np.float32)
    payload = q.sparse_encode(dense, force_numpy=force_numpy)
    out = q.sparse_decode(payload, 256, force_numpy=force_numpy)
    np.testing.assert_array_equal(out, dense)


def test_sparse_filter_native_numpy_agree(native_lib):
    from multiverso_tpu.utils import quantization as q
    if not q.native_available():
        pytest.skip("native lib unavailable")
    data = np.zeros(64, np.float32)
    data[[3, 9]] = [1.5, -2.5]
    assert q.sparse_encode(data) == q.sparse_encode(data, force_numpy=True)


def test_sparse_decode_rejects_garbage():
    from multiverso_tpu.utils import quantization as q
    with pytest.raises(ValueError):
        q.sparse_decode(b"garbagegarbagegarbage", 4, force_numpy=True)


ALLOC_TYPE_SNIPPET = r"""
import ctypes, sys
lib = ctypes.CDLL(sys.argv[1])
lib.MVTPU_ConfigureAllocator.restype = ctypes.c_int
lib.MVTPU_ConfigureAllocator.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
lib.MVTPU_AllocatorType.restype = ctypes.c_char_p
lib.MVTPU_Alloc.restype = ctypes.c_void_p
lib.MVTPU_Alloc.argtypes = [ctypes.c_size_t]
lib.MVTPU_Free.argtypes = [ctypes.c_void_p]
assert lib.MVTPU_ConfigureAllocator(b"zzz", 16) == -2
assert lib.MVTPU_ConfigureAllocator(b"default", 64) == 0
assert lib.MVTPU_AllocatorType() == b"default"
p = lib.MVTPU_Alloc(100)
assert p % 64 == 0, "alignment flag not honored"
assert lib.MVTPU_AllocatorLiveBlocks() == 1
lib.MVTPU_Free(ctypes.c_void_p(p))
# default allocator releases memory: nothing pooled, nothing live
assert lib.MVTPU_AllocatorLiveBlocks() == 0
assert lib.MVTPU_AllocatorPooledBlocks() == 0
# reconfiguration after first use: same config ok, different config refused
assert lib.MVTPU_ConfigureAllocator(b"default", 64) == 0
assert lib.MVTPU_ConfigureAllocator(b"smart", 16) == -1
print("alloc type ok")
"""


def test_allocator_type_flag(native_lib):
    """allocator_type/allocator_alignment are real configuration: the
    `default` allocator frees immediately (no pool) and honors alignment.
    Run in a subprocess — the singleton latches on first use per process."""
    result = subprocess.run(
        [sys.executable, "-c", ALLOC_TYPE_SNIPPET, os.path.abspath(native_lib)],
        capture_output=True, text=True, timeout=60)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "alloc type ok" in result.stdout


INIT_PLUMB_SNIPPET = r"""
import ctypes, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
import multiverso_tpu as mv
mv.init(allocator_type="default")
from multiverso_tpu.utils.quantization import _load_native
lib = _load_native()
lib.MVTPU_AllocatorType.restype = ctypes.c_char_p
assert lib.MVTPU_AllocatorType() == b"default", lib.MVTPU_AllocatorType()
mv.shutdown()
print("init plumb ok")
"""


def test_init_plumbs_allocator_flags(native_lib):
    """mv.init() pushes the allocator flags into the native lib."""
    repo = os.path.abspath(os.path.join(NATIVE_DIR, "..", ".."))
    result = subprocess.run(
        [sys.executable, "-c", INIT_PLUMB_SNIPPET, repo],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "init plumb ok" in result.stdout
