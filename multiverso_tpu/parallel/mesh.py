"""Device mesh construction and sharding helpers.

This is the substrate that replaces the reference's rank topology: server
"shards" are device shards of a :class:`jax.sharding.Mesh` axis instead of
MPI ranks (reference range sharding: ``src/table/array_table.cpp:13-19``,
``src/table/matrix_table.cpp:25-45``).

Design: one global *table mesh* (axis ``server``) owns parameter-table
placement; applications build richer meshes (data/model/pipeline axes) for
their own compute and the tables interoperate because Get/Add results cross
via host or via resharding.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu import log


def parse_mesh_shape(text: str) -> Optional[Tuple[int, ...]]:
    """Parse '2x4'-style mesh shape flags; empty → None (auto 1-D)."""
    text = text.strip()
    if not text:
        return None
    return tuple(int(tok) for tok in text.replace("*", "x").split("x"))


def build_mesh(devices: Optional[Sequence[jax.Device]] = None,
               shape: Optional[Tuple[int, ...]] = None,
               axis_names: Sequence[str] = ("server",)) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    want, have = int(np.prod(shape)), len(devs)
    if want > have:
        log.fatal("mesh shape %s needs %d devices, have %d",
                  "x".join(map(str, shape)), want, have)
    if want < have:
        devs = devs[:want]
        log.info("mesh shape %s takes the first %d of %d devices: %s",
                 "x".join(map(str, shape)), want, have, devs)
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, axis_names=tuple(axis_names))


def table_sharding(mesh: Mesh, ndim: int, shard_dim: int = 0,
                   axis: str = "server") -> NamedSharding:
    """Sharding for a table state array: dimension ``shard_dim`` split over
    the server axis (reference analog: range sharding over server ranks)."""
    spec = [None] * ndim
    spec[shard_dim] = axis
    return NamedSharding(mesh, P(*spec))


# host bytes of a shard's rows on their way up in one piece: a larger shard
# goes up in pieces of this size and is put together on its device
PIECE_BYTES = 256 << 20


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_rows(shard: jax.Array, piece: jax.Array, at):
    """``shard`` with ``piece`` written at row ``at``, in place (the shard
    is donated): row and column offsets apart, never an element offset, so
    a shard of more than 2^31 elements takes it. Also ``at`` again, which is
    ready when the write has run and the piece's buffer is free."""
    return jax.lax.dynamic_update_slice(shard, piece, (at, 0)), at + 0


def _put_shard(lo: int, hi: int, cols: int, step: int, device,
               block_of) -> jax.Array:
    """Rows ``[lo, hi)`` on ``device``, asked of ``block_of`` ``step`` rows
    at a time in row order: a shard of one piece goes up as that piece, a
    larger one is zeros made ON the device (a program placed there:
    ``jnp.zeros(device=)`` makes them on the default device and moves
    them) that each piece is written into (``_set_rows``). A piece is
    asked for only when the piece two before it has been written: the host
    and the device hold two pieces beside the shard."""
    first = block_of(lo, min(hi, lo + step))
    if hi - lo <= step:
        return jax.device_put(first, device)
    shard = jax.jit(
        lambda: jax.numpy.zeros((hi - lo, cols), first.dtype),
        out_shardings=jax.sharding.SingleDeviceSharding(device))()
    written: List[jax.Array] = []
    for at in range(lo, hi, step):
        if len(written) == 2:
            written.pop(0).block_until_ready()
        piece = jax.device_put(
            first if at == lo else block_of(at, min(hi, at + step)), device)
        shard, done = _set_rows(shard, piece, np.int32(at - lo))
        written.append(done)
    return shard


def put_row_blocks(mesh: Mesh, rows: int, cols: int, block_of,
                   axis: str = "server", itemsize: int = 4) -> jax.Array:
    """A ``(rows, cols)`` table state sharded by rows over ``axis``, put up
    shard by shard and a shard piece by piece: ``block_of(lo, hi)`` gives
    rows ``[lo, hi)`` as a host array of ``itemsize`` bytes a value and is
    asked in row order for at most ``PIECE_BYTES`` at a time, a shard's
    first piece only when the shards before it are on their devices. The
    host holds two pieces beside whatever ``block_of`` reads from, never a
    second table, on one device as on several. The last transfers are left
    in flight, as one ``device_put`` of the whole state would be."""
    sharding = table_sharding(mesh, ndim=2, shard_dim=0, axis=axis)
    spans = sorted(
        ((index[0].start or 0, rows if index[0].stop is None
          else index[0].stop, device)
         for device, index in sharding.addressable_devices_indices_map(
             (rows, cols)).items()), key=lambda span: span[:2])
    step = max(1, PIECE_BYTES // (cols * itemsize))
    shards, last = [], None
    for lo, hi, device in spans:
        if last is not None and last[0] == (lo, hi):
            # replicas share a block: from the device that has it
            shards.append(jax.device_put(last[1], device))
            continue
        for shard in shards:
            shard.block_until_ready()
        last = ((lo, hi), _put_shard(lo, hi, cols, step, device, block_of))
        shards.append(last[1])
    return jax.make_array_from_single_device_arrays((rows, cols), sharding,
                                                    shards)


def replicated(mesh: Mesh, ndim: int = 0) -> NamedSharding:
    return NamedSharding(mesh, P(*([None] * ndim)))


def pad_to_multiple(n: int, k: int) -> int:
    """Smallest multiple of k that is >= n (shard-divisibility padding)."""
    return ((n + k - 1) // k) * k


def shard_ranges(total: int, num_shards: int) -> List[Tuple[int, int]]:
    """Equal-chunk ranges with remainder to the last shard — mirrors the
    reference's server offset computation so `server_id`-indexed APIs
    (e.g. checkpoint-per-shard naming) agree with its layout."""
    chunk = total // num_shards
    ranges = []
    for i in range(num_shards):
        begin = chunk * i
        end = total if i == num_shards - 1 else chunk * (i + 1)
        ranges.append((begin, end))
    return ranges
