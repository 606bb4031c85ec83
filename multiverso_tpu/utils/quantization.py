"""Wire compression: SparseFilter + the quantized delta codec (the
OneBits slot) with error feedback.

Reference capability (not copied): ``SparseFilter<data,index>`` encodes a
blob as (index, value) pairs when >50% zeros, with a size side-channel;
``OneBitsFilter`` — the 1-bit-SGD wire codec the DMTK era was known for —
was an empty stub (``include/multiverso/util/quantization_util.h:37-161``).
Implemented for real here: deltas quantize to 1/2/4/8 bits per value with
client-side residual accumulation (error feedback), so the quantization
error feeds into the next push instead of being lost — the property that
makes 1-bit SGD converge.

TPU-era role: only host hops (C-API bridge, external clients) benefit —
on-mesh traffic is XLA collectives. Codecs are native C++
(``native/sparse_filter.cpp``, ``native/quant_filter.cpp``) loaded via
ctypes, with pure numpy fallbacks producing byte-identical output
(magics 'MVSF' / 'MVQF'). Quantization scale derivation uses only
order-independent reductions (min/max), so native and numpy agree
bit-for-bit; the elementwise quantize/dequantize is float32 with
round-half-to-even on both sides.
"""

from __future__ import annotations

import ctypes
import os
import struct
from typing import Optional

import numpy as np

_MAGIC = 0x4653564D  # 'MVSF'

_native: Optional[ctypes.CDLL] = None
_native_load_attempted = False


def _load_native() -> Optional[ctypes.CDLL]:
    # cache failure too: without the .so built, retrying dlopen on every
    # encode/decode would tax the hot wire-compression path
    global _native, _native_load_attempted
    if _native_load_attempted:
        return _native
    _native_load_attempted = True
    path = os.path.join(os.path.dirname(__file__), "..", "native",
                        "libmultiverso_tpu.so")
    try:
        lib = ctypes.CDLL(os.path.abspath(path))
        # size_t SparseEncodeC(const float*, size_t, uint8_t*, size_t)
        lib.MVTPU_SparseEncode.restype = ctypes.c_size_t
        lib.MVTPU_SparseEncode.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t]
        lib.MVTPU_SparseDecode.restype = ctypes.c_int
        lib.MVTPU_SparseDecode.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_float), ctypes.c_size_t]
        _native = lib
    except (OSError, AttributeError):
        _native = None
    return _native


# elements compared per pass of sparse_is_shorter: a 256 KB temporary
_COUNT_STEP = 1 << 18


def sparse_is_shorter(data: np.ndarray) -> bool:
    """Whether :func:`sparse_encode` would return fewer bytes than the
    float32 array itself holds, decided from a count alone: the sparse
    form is ``24 + 8 * nnz`` bytes, and where that is under the array's
    ``4 * size`` the encoders' own test (numpy and native alike go sparse
    when ``2 * nnz < size``) holds with it. ``-0.0`` is a zero and NaN is
    not, as in both encoders. The count is a boolean compare a piece at a
    time, no index array, and stops once the answer is no: a dense
    payload costs about half a pass and builds nothing."""
    flat = np.ascontiguousarray(data, dtype=np.float32).reshape(-1)
    room = flat.nbytes - 24  # bytes the (index, value) pairs may take
    nnz = 0
    for start in range(0, flat.size, _COUNT_STEP):
        nnz += int(np.count_nonzero(flat[start:start + _COUNT_STEP] != 0))
        if 8 * nnz >= room:
            return False
    return 8 * nnz < room


def sparse_encode(data: np.ndarray, force_numpy: bool = False) -> bytes:
    """Encode a float32 array; sparse form when <50% nonzero. A caller
    that sends the result only where it is shorter than the array asks
    :func:`sparse_is_shorter` first and does not call this for nothing."""
    data = np.ascontiguousarray(data, dtype=np.float32).reshape(-1)
    lib = None if force_numpy else _load_native()
    if lib is not None:
        # worst case: header(16) + nnz(8) + count*(4+4)
        cap = 24 + data.size * 8
        out = np.empty(cap, dtype=np.uint8)
        n = lib.MVTPU_SparseEncode(
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), data.size,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap)
        return out[:n].tobytes()
    nz = np.nonzero(data)[0]
    sparse = 2 * len(nz) < data.size
    header = struct.pack("<IIQ", _MAGIC, 1 if sparse else 0, data.size)
    if not sparse:
        return header + data.tobytes()
    pairs = np.empty((len(nz), 2), dtype=np.uint32)
    pairs[:, 0] = nz.astype(np.uint32)
    pairs[:, 1] = data[nz].view(np.uint32)
    return header + struct.pack("<Q", len(nz)) + pairs.tobytes()


def sparse_decode(payload: bytes, count: int,
                  force_numpy: bool = False) -> np.ndarray:
    lib = None if force_numpy else _load_native()
    if lib is not None:
        out = np.zeros(count, dtype=np.float32)
        buf = np.frombuffer(payload, dtype=np.uint8)
        ok = lib.MVTPU_SparseDecode(
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(payload),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), count)
        if not ok:
            raise ValueError("malformed sparse payload")
        return out
    magic, kind, n = struct.unpack_from("<IIQ", payload, 0)
    if magic != _MAGIC or n != count:
        raise ValueError("malformed sparse payload")
    if kind == 0:
        return np.frombuffer(payload, dtype=np.float32, count=count,
                             offset=16).copy()
    (nnz,) = struct.unpack_from("<Q", payload, 16)
    pairs = np.frombuffer(payload, dtype=np.uint32, count=nnz * 2,
                          offset=24).reshape(nnz, 2)
    out = np.zeros(count, dtype=np.float32)
    out[pairs[:, 0]] = pairs[:, 1].view(np.float32)
    return out


def native_available() -> bool:
    return _load_native() is not None


# -- quantized delta codec (the OneBits slot) --------------------------------

_QMAGIC = 0x4651564D  # 'MVQF'
_QBITS = (1, 2, 4, 8)


def _quant_params(data: np.ndarray, bits: int):
    """(lo, step, inv_step) as float32 — min/max based so the derivation
    is order-independent (byte-identical native/numpy)."""
    lo = np.float32(data.min()) if data.size else np.float32(0.0)
    hi = np.float32(data.max()) if data.size else np.float32(0.0)
    levels = (1 << bits) - 1
    step = np.float32((hi - lo) / np.float32(levels))
    inv = np.float32(0.0) if step == 0 else np.float32(1.0) / step
    return lo, step, inv


def quant_encode(data: np.ndarray, bits: int,
                 force_numpy: bool = False) -> bytes:
    """Quantize a float32 array to ``bits`` (1|2|4|8) per value.

    Layout: <u32 magic><u32 bits><u64 count><f32 lo><f32 step> + packed
    codes (little-endian within each byte). Lossy by design — pair with
    :class:`ErrorFeedback` so the error re-enters the next delta."""
    if bits not in _QBITS:
        raise ValueError(f"quant bits must be one of {_QBITS}, got {bits}")
    data = np.ascontiguousarray(data, dtype=np.float32).reshape(-1)
    lo, step, inv = _quant_params(data, bits)
    header = struct.pack("<IIQff", _QMAGIC, bits, data.size, float(lo),
                         float(step))
    per_byte = 8 // bits
    n_bytes = -(-data.size // per_byte)
    lib = None if force_numpy else _load_native()
    if lib is not None and hasattr(lib, "MVTPU_QuantPack"):
        out = np.zeros(n_bytes, dtype=np.uint8)
        lib.MVTPU_QuantPack.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_size_t,
            ctypes.c_float, ctypes.c_float, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8)]
        lib.MVTPU_QuantPack(
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), data.size,
            ctypes.c_float(float(lo)), ctypes.c_float(float(inv)), bits,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        return header + out.tobytes()
    levels = (1 << bits) - 1
    # float32 elementwise + rint (round-half-to-even): mirrors the C++
    # nearbyintf path exactly
    q = np.rint((data - lo) * inv).astype(np.float32)
    q = np.clip(q, 0, levels).astype(np.uint8)
    pad = n_bytes * per_byte - data.size
    if pad:
        q = np.concatenate([q, np.zeros(pad, np.uint8)])
    q = q.reshape(-1, per_byte)
    shifts = (np.arange(per_byte, dtype=np.uint16) * bits)
    packed = (q.astype(np.uint16) << shifts).sum(axis=1).astype(np.uint8)
    return header + packed.tobytes()


def quant_decode(payload: bytes, count: int,
                 force_numpy: bool = False) -> np.ndarray:
    """Decode a quant payload back to float32 (count values)."""
    magic, bits, n = struct.unpack_from("<IIQ", payload, 0)
    if magic != _QMAGIC or n != count or bits not in _QBITS:
        raise ValueError("malformed quant payload")
    lo, step = struct.unpack_from("<ff", payload, 16)
    lo, step = np.float32(lo), np.float32(step)
    per_byte = 8 // bits
    n_bytes = -(-count // per_byte)
    lib = None if force_numpy else _load_native()
    if lib is not None and hasattr(lib, "MVTPU_QuantUnpack"):
        out = np.zeros(count, dtype=np.float32)
        buf = np.frombuffer(payload, dtype=np.uint8, offset=24,
                            count=n_bytes)
        lib.MVTPU_QuantUnpack.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
            ctypes.c_float, ctypes.c_float, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float)]
        lib.MVTPU_QuantUnpack(
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), count,
            ctypes.c_float(float(lo)), ctypes.c_float(float(step)), bits,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        return out
    packed = np.frombuffer(payload, dtype=np.uint8, offset=24,
                           count=n_bytes)
    shifts = (np.arange(per_byte, dtype=np.uint16) * bits)
    mask = np.uint16((1 << bits) - 1)
    q = ((packed[:, None].astype(np.uint16) >> shifts) & mask).reshape(-1)
    q = q[:count].astype(np.float32)
    return (lo + q * step).astype(np.float32)


def quant_codes(payload: bytes, count: int):
    """Unpack a quant payload's integer codes WITHOUT dequantizing:
    ``(codes float32 (count,), lo, step, bits)``. The compressed-domain
    scoring path (multiverso_tpu/query/) folds lo/step into the score
    algebra — ``dot(q, lo + c*step) = lo*sum(q) + step*(q @ c.T)`` —
    instead of materializing ``lo + c*step`` per element. Codes come
    back as float32 (the dtype the fold multiplies in); exact, since
    every code is an integer <= 255."""
    magic, bits, n = struct.unpack_from("<IIQ", payload, 0)
    if magic != _QMAGIC or n != count or bits not in _QBITS:
        raise ValueError("malformed quant payload")
    lo, step = struct.unpack_from("<ff", payload, 16)
    per_byte = 8 // bits
    n_bytes = -(-count // per_byte)
    packed = np.frombuffer(payload, dtype=np.uint8, offset=24,
                           count=n_bytes)
    shifts = (np.arange(per_byte, dtype=np.uint16) * bits)
    mask = np.uint16((1 << bits) - 1)
    q = ((packed[:, None].astype(np.uint16) >> shifts) & mask).reshape(-1)
    return (q[:count].astype(np.float32), np.float32(lo),
            np.float32(step), int(bits))


class QuantizedDelta:
    """Marker a worker proxy hands to the wire codec: an already-encoded
    quant payload riding as one uint8 blob (tag 'quant'); the server side
    decodes back to plain float32 before process_add."""

    __slots__ = ("payload", "shape")

    def __init__(self, payload: bytes, shape) -> None:
        self.payload = payload
        self.shape = tuple(shape)


class ErrorFeedback:
    """Client-side residual accumulator for quantized pushes: each delta
    is quantized TOGETHER with the residual of all previous quantization
    errors for the touched rows, and the new error replaces it — the
    1-bit-SGD convergence recipe, generalized to 1/2/4/8 bits."""

    def __init__(self, shape, bits: int) -> None:
        self.residual = np.zeros(shape, np.float32)
        self.bits = int(bits)

    def compress(self, values: np.ndarray, ids=None) -> QuantizedDelta:
        values = np.asarray(values, np.float32)
        if ids is None:
            x = values.reshape(self.residual.shape) + self.residual
        else:
            # explicit trailing dims: reshape(0, -1) rejects empty batches
            x = (values.reshape((len(ids),) + self.residual.shape[1:])
                 + self.residual[np.asarray(ids, np.int64)])
        payload = quant_encode(x, self.bits)
        dec = quant_decode(payload, x.size).reshape(x.shape)
        if ids is None:
            self.residual = x - dec
        else:
            self.residual[np.asarray(ids, np.int64)] = x - dec
        return QuantizedDelta(payload, x.shape)
