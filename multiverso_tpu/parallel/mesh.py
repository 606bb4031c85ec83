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


def put_row_blocks(mesh: Mesh, rows: int, cols: int, block_of,
                   axis: str = "server") -> jax.Array:
    """A ``(rows, cols)`` table state sharded by rows over ``axis``, put up
    block by block: ``block_of(lo, hi)`` gives rows ``[lo, hi)`` as a host
    array and is asked for one block at a time in row order, each only when
    the blocks before it are on their devices. The host holds one block
    beside whatever ``block_of`` reads from, never a second table. The
    last block's transfer is left in flight, as one ``device_put`` of the
    whole state would be: a table on one device goes up as it always did."""
    sharding = table_sharding(mesh, ndim=2, shard_dim=0, axis=axis)
    spans = sorted(
        ((index[0].start or 0, rows if index[0].stop is None
          else index[0].stop, device)
         for device, index in sharding.addressable_devices_indices_map(
             (rows, cols)).items()), key=lambda span: span[:2])
    pieces, last = [], None
    for lo, hi, device in spans:
        if last is None or last[0] != (lo, hi):  # replicas share a block
            for piece in pieces:
                piece.block_until_ready()
            last = ((lo, hi), block_of(lo, hi))
        pieces.append(jax.device_put(last[1], device))
    return jax.make_array_from_single_device_arrays((rows, cols), sharding,
                                                    pieces)


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
