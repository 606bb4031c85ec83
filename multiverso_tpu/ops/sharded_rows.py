"""Row Add and row Get of a table whose rows are sharded over the chips of
one process: each shard does the work of the rows it owns.

The table is one ``jax.Array`` of ``(padded_rows, lanes)``, sharded by rows
over the mesh axis ``server`` in contiguous blocks of ``padded_rows /
shards`` (``get_device()[:num_row]`` is the table, whatever the mesh). Row
``g`` belongs to shard ``g // block`` and is its row ``g % block``.
``pallas_call`` has no partitioning rule, so the programs here are written
for one shard and mapped over the axis (``shard_map``); what crosses chips
is named, not left to the partitioner.

**Routing** happens on the chip that holds the op, the mesh's first (a
device delta is committed there, a device Get's contract (``RowPlan._out_device``), and a Get's
rows are wanted there). The host uploads the ids once, to that chip alone,
an Add's and a Get's in one form (the slots a Get gathers: the ids as they
came, then ids past the table, the sentinel last; ``RowPlan.launch_ids``
keeps the array, and an op that names the same rows launches on it and
uploads nothing), and counts how many fall into each shard's range
(:func:`shard_counts`, in ``TABLE_ROW_ROUTE``) to pick the static size of a
shard's segment (:func:`shard_capacity`: the fullest shard's count, rounded
up in steps, so traffic whose ids spread evenly compiles once and an op
whose ids all fall in one shard compiles one more program and is still
right). On the chip the ids are sorted together with their positions (a
sort by id is a sort by owner: the ranges are contiguous); a shard's run
starts where the sorted ids reach its first row, and its segment is the
``capacity`` slots from there: its live slots, then whatever follows, which
nobody touches. Measured on the v5e (PERF.md, Findings, PR 30): routing on
the host (a radix sort on the owner, ten numpy passes, three sharded
uploads) cost 4.9 ms an Add on the dispatcher thread; the sort on the chip
costs 0.11 ms of device time and the host's count 0.13.

**The Add** (one device program). The ids named are the slots the delta
has rows for, a static slice of the array that came. On the first chip each
shard's piece of the delta is gathered in the order of its segment and
sent to its owner, with the segment's shard-local ids and its live count, by
``collective-permute`` with the single pair ``(0, s)``: every row crosses
the interconnect at most once and nobody receives a row it does not own.
Then every shard runs the row kernel (``pallas_rows._scatter_add``) on its
block with its ids and the count: only the last shard has scratch rows
behind ``num_row``, so pad slots have nowhere to aim, and the slots past
the count issue no descriptor. A host delta is uploaded to the first chip
and takes the same route.

**The Get** (one device program): the first chip sends each shard its
segment's ids, each shard gathers them from its block (XLA's gather) and
sends the rows back (pairs ``(s, 0)``), and the first chip lays the
segments' live rows end to end and puts them back in the order asked with
one more gather (by the inverse of the sort), filling the bucket's tail from
the sentinel row's value, which rides as the last id, as a one-chip table's
``_row_gather`` does. The ids come padded to a step of the bucket
(``_live_slots``) with an id past the table, which no shard owns, so that a
bucket size compiles a few programs and not one an id count. The result is
``(bucket, lanes)`` on the mesh's first device.

The programs run on every chip of the mesh (SPMD): where only the first
chip's work matters (the sort, the delta's pieces, the Get's rows in order)
the others work on placeholders of the same shape, in parallel, and what
they make is dropped.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from multiverso_tpu.ops import pallas_rows
from multiverso_tpu.utils import next_pow2

AXIS = "server"
# shapes whose placeholders (see `ShardedRows.on_first`) are kept on the
# devices other than the first
PLACEHOLDER_SHAPES = 16


def shard_capacity(fullest: int, n: int, shards: int) -> int:
    """Slots of one shard's segment for an op of ``n`` ids whose fullest
    shard owns ``fullest``: rounded up to a step of a thirty-second of the
    shard's even share of the op's power-of-two bucket (whole row groups),
    so that a bucket size compiles at most 32 programs a shard count and
    evenly spread ids leave a few percent of the slots empty (103,424
    slots for 100,000 ids over four shards, at steps of 1,024 and a group
    of 256). Then one row group more: XLA's
    TPU gather takes its ids in tiles of 1,024 and moves a row 2.4 times as
    fast where they do not fill their last tile (``_live_slots``, PERF.md,
    Findings, PR 27), and a whole number of steps is a whole number of
    tiles from a bucket of 131,072 up."""
    group = pallas_rows.ROW_GROUP
    bucket = max(next_pow2(n), group)
    step = max(bucket // (32 * shards) // group * group, group)
    return -(-fullest // step) * step + group


def shard_counts(row_ids: np.ndarray, block: int, shards: int) -> np.ndarray:
    """How many of an op's ids each shard owns (shard ``s`` owns rows
    ``[s x block, (s + 1) x block)``; an id past the last shard's rows is
    nobody's): one pass over the ids a boundary."""
    return np.diff([0] + [int(np.count_nonzero(row_ids < s * block))
                          for s in range(1, shards + 1)])


def launched_slots(counts: np.ndarray) -> np.ndarray:
    """Id slots each shard's scatter-add issues descriptors for: its live
    slots in whole row groups."""
    group = pallas_rows.ROW_GROUP
    return -(-counts // group) * group


def launch_waits(counts: np.ndarray) -> int:
    """Semaphore waits the shards' scatter-adds issue together: two a whole
    group of live slots, and two a slot of a shard's last, partial one."""
    group = pallas_rows.ROW_GROUP
    return int(2 * (counts // group + counts % group).sum())


class ShardedRows:
    """The row programs of one table mesh (:func:`programs` hands every
    table of the mesh the same ones; jit keys them by shape)."""

    def __init__(self, mesh, interpret: bool, sign: float) -> None:
        self.mesh = mesh
        self.shards = int(mesh.shape[AXIS])
        self.first = mesh.devices.flat[0]
        self.by_rows = NamedSharding(mesh, P(AXIS))
        self._interpret, self._sign = interpret, sign
        self._placeholders = collections.OrderedDict()
        smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                                 in_specs=P(AXIS), out_specs=P(AXIS))

        def sharded_row_add(data, ids, delta, capacity):
            return smap(functools.partial(
                self._add_from_first, capacity=capacity))(data, ids, delta)

        def sharded_row_get(data, ids, capacity, bucket):
            return smap(functools.partial(
                self._get_to_first, capacity=capacity,
                bucket=bucket))(data, ids)

        # named, like their parameters, for the module and operand names a
        # trace is read by. `add(data, ids, delta, capacity=...)`: the table
        # after `delta`'s rows were added to the rows `ids` names; `data` is
        # donated; `ids` and `delta` as `on_first` gives them, the ids in
        # a Get's form: the first `delta.shape[0]` name the rows
        self.add = jax.jit(sharded_row_add, donate_argnums=(0,),
                           static_argnames=("capacity",))
        self._get = jax.jit(sharded_row_get,
                            static_argnames=("capacity", "bucket"))

    # -- the programs, as one shard sees them ------------------------------
    def _segments(self, ids, block_rows: int, capacity: int):
        """On the chip that holds an op's ``ids``, the op laid out for the
        shards: ``source`` (the ids' positions in the order of the sorted
        ids), ``starts`` (where each shard's run begins in that order, and
        where the last ends) and for each shard its segment ``(meta,
        positions)``, ``capacity`` slots from its run's start: ``meta`` is
        the slots' shard-local ids, then the count of live slots;
        ``positions`` the slots' entries of ``source``. Slots past the live
        ones hold the next shards' ids, or nothing."""
        n = ids.shape[0]
        ordered, source = lax.sort((ids, lax.iota(jnp.int32, n)), num_keys=1)
        firsts = block_rows * jnp.arange(self.shards + 1, dtype=jnp.int32)
        starts = jnp.sum(ordered[None, :] < firsts[:, None], axis=1,
                         dtype=jnp.int32)
        room = jnp.zeros((capacity,), jnp.int32)
        ordered_room = jnp.concatenate([ordered, room])
        source_room = jnp.concatenate([source, room])
        segments = []
        for s in range(self.shards):
            local = lax.dynamic_slice(ordered_room, (starts[s],),
                                      (capacity,)) - firsts[s]
            count = starts[s + 1] - starts[s]
            segments.append((
                jnp.concatenate([local, count[None]]),
                lax.dynamic_slice(source_room, (starts[s],), (capacity,))))
        return source, starts, segments

    def _to_owners(self, pieces):
        """Piece ``s`` of the first chip's ``pieces`` sent to shard ``s``;
        every shard is handed its own."""
        me = lax.axis_index(AXIS)
        mine = pieces[0]
        for s in range(1, self.shards):
            sent = lax.ppermute(pieces[s], AXIS, [(0, s)])
            mine = jax.tree.map(functools.partial(jnp.where, me == s),
                                sent, mine)
        return mine

    def _add_from_first(self, block, ids, delta, *, capacity):
        with jax.named_scope("shard_route"):
            # the ids come as a Get's do (`RowPlan.launch_ids`: one form,
            # so that either op can launch on the other's): the slots the
            # delta has rows for name the rows, a static slice
            _, _, segments = self._segments(ids[: delta.shape[0]],
                                            block.shape[0], capacity)
            # the delta's rows in the order of each shard's segment
            pieces = [(meta, delta[positions].astype(block.dtype))
                      for meta, positions in segments]
        with jax.named_scope("shard_exchange"):
            meta, rows = self._to_owners(pieces)
        with jax.named_scope("shard_scatter"):
            return pallas_rows._scatter_add(
                block, meta[:capacity], rows, self._interpret, self._sign,
                meta[capacity:])

    def _get_to_first(self, block, ids, *, capacity, bucket):
        live = ids.shape[0]
        with jax.named_scope("shard_route"):
            source, starts, segments = self._segments(ids, block.shape[0],
                                                      capacity)
        with jax.named_scope("shard_exchange"):
            meta = self._to_owners([meta for meta, _ in segments])
        with jax.named_scope("shard_gather"):
            mine = block[meta[:capacity]]
        with jax.named_scope("shard_exchange"):
            parts = [mine] + [lax.ppermute(mine, AXIS, [(s, 0)])
                              for s in range(1, self.shards)]
        with jax.named_scope("shard_unroute"):
            # the segments' live rows end to end, in the sorted order: each
            # segment is laid over the dead tail of the one before
            ordered = jnp.zeros((live + capacity, block.shape[1]),
                                block.dtype)
            for s, part in enumerate(parts):
                ordered = lax.dynamic_update_slice(ordered, part,
                                                   (starts[s], 0))
            # where each position of the op went in the sort; a pad slot
            # (an id past the table) is answered like the last, the sentinel
            rank = lax.sort((source, lax.iota(jnp.int32, live)),
                            num_keys=1)[1]
            asked = jnp.where(ids >= self.shards * block.shape[0],
                              rank[live - 1], rank)
            rows = ordered[asked]
            if bucket == live:
                return rows
            fill = jnp.broadcast_to(ordered[rank[live - 1]],
                                    (bucket - live, block.shape[1]))
            return jnp.concatenate([rows, fill])

    # -- what the table calls ----------------------------------------------
    def on_first(self, values) -> jax.Array:
        """``values`` (a host array, or a device array wherever it is) as
        the first shard's piece of an array the programs can take:
        committed to the mesh's first device (where an in-process worker's
        deltas already are), with placeholders of its shape, kept for the
        newest shapes, on the other devices."""
        if not (isinstance(values, jax.Array)
                and values.devices() == {self.first}):
            values = jax.device_put(values, self.first)
        key = (values.shape, values.dtype)
        rest = self._placeholders.get(key)
        if rest is None:
            rest = self._placeholders[key] = [
                jax.device_put(np.zeros(values.shape, values.dtype), d)
                for d in self.mesh.devices.flat[1:]]
            # a trainer names another count of rows every block: keep the
            # newest shapes' (a new shape compiles anyway)
            while len(self._placeholders) > PLACEHOLDER_SHAPES:
                self._placeholders.popitem(last=False)
        else:
            self._placeholders.move_to_end(key)
        shape = (self.shards * values.shape[0],) + values.shape[1:]
        return jax.make_array_from_single_device_arrays(
            shape, self.by_rows, [values] + rest)

    def get(self, data, ids, capacity: int, bucket: int) -> jax.Array:
        """``(bucket, lanes)`` on the mesh's first device: the rows ``ids``
        names, in that order, then copies of the row its last names (the
        sentinel, where the ids do not fill the bucket); a slot whose id
        lies past the table is a pad and is answered like the last. The
        ids' slots are gathered, the rest of the bucket filled."""
        out = self._get(data, ids, capacity=capacity, bucket=bucket)
        return next(s.data for s in out.addressable_shards
                    if s.device == self.first)


@functools.lru_cache(maxsize=None)
def programs(mesh, interpret: bool, sign: float) -> ShardedRows:
    return ShardedRows(mesh, interpret, sign)
