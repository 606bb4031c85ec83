"""Pallas TPU kernels for sparse row Get/Add on HBM-resident tables.

Replaces XLA's scatter/gather on the MatrixTable row path (reference hot
path: per-row ``updater_->Update`` loops, ``src/table/matrix_table.cpp:
387-417``; worker scatter-back ``317-341``). XLA lowers `data.at[ids].add`
to a serialized scatter (~µs per row); these kernels instead issue a group
of row DMAs per grid step so the row-fetch latencies overlap, turning the
op bandwidth-bound.

Contracts (enforced by the caller, `tables.matrix_table.MatrixServer`):

* ids are int32 in ``[0, table_rows)`` — pad slots point at the table's
  sentinel scratch row (never a live row) with zero deltas.
* for ``scatter_add_rows`` the *live* ids are unique within the call
  (duplicates pre-combined); pad slots may repeat the sentinel because a
  zero delta leaves its bytes unchanged, so racing identical writes are
  benign.
* batch size is a multiple of the row group (bucket sizes are powers of
  two ≥ the group).

Interpret mode is the caller's explicit choice, made once from the
platform of the devices that hold the table (:func:`interpret_for`): ``cpu``
interprets (the test mesh), ``tpu`` compiles, anything else is an error.

Optimization record (measured at PR 5 on a v5e, single core, 1024-row x
128-col update on a 1M-row table, scan-slope timing; not re-measured on the
current machine):

* group-size sweep: 8→83us, 16→49us, 32→32us, 64→26.4us, 128→27.2us;
  256 exceeds the semaphore-flag memory (sflag 2KB). The 64-group asymptote
  is the per-row DMA issue cost (~13ns/descriptor on the scalar core), not
  transfer latency.
* software pipelining (double-buffered scratch, group g+1 reads overlapped
  with group g writes): 35.8us — SLOWER than the simple kernel. Two causes:
  the dynamic buffer indexing taxes every descriptor, and the overlap
  window (one group's processing, <1us) barely covers a write's latency.
  A read-only variant measures 18.2us vs 26.4us read+write, i.e. the write
  phase already overlaps ~70% behind the next group's reads via the DMA
  engine's own queueing. The simple kernel is kept.
* remaining headroom would need fewer/larger descriptors (rows are 512B —
  per-descriptor cost dominates); with arbitrary row ids there is no
  contiguity to merge, so this is the v5e floor for this op shape.
* descriptor coalescing (r3): sorted-unique ids do contain contiguous runs
  on zipf workloads, so a variant merges each all-consecutive 4-row segment
  into ONE 4-row DMA. Measured (1M×128 table, 1024-id batches,
  scan-slope): simple 27.2-27.3µs vs coalesced 36.5-39.6µs on BOTH
  sorted-zipf and sorted-uniform ids — a 34-45% LOSS. Two reasons, both
  structural: (a) zipf-1024-of-1M contiguity is only 13% of segments (the
  dense head of the distribution is ~100 ids; the tail is sparse), and
  (b) the per-segment `pl.when` pair costs ~12.6µs/call on the scalar core
  (64 conditionals: 16 segments × read/write × start/wait) while the best
  possible descriptor saving is 96 × ~13ns ≈ 1.2µs even at 100%
  contiguity. Conclusion: on v5e the branch cost exceeds the descriptor
  cost by ~10×, so run-merging cannot win at 512B rows regardless of
  workload. The coalesced kernel was deleted; this paragraph is its record.
"""

from __future__ import annotations

import functools
import os

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows (= concurrent DMAs) per grid step; env-overridable for sweeps —
# see the optimization record above for the measured sweep
ROW_GROUP = int(os.environ.get("MVTPU_ROW_GROUP", "64"))
if ROW_GROUP <= 0 or ROW_GROUP & (ROW_GROUP - 1):
    # bucket sizes are powers of two >= the group; a non-power-of-two group
    # would silently violate the batch-multiple contract and drop updates
    raise ValueError(f"MVTPU_ROW_GROUP must be a power of two, got {ROW_GROUP}")


def interpret_for(platform: str) -> bool:
    """Whether the row kernels interpret on ``platform`` — the platform of
    the devices that hold the table, not the process's default backend."""
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise ValueError(
        f"pallas row kernels run compiled on tpu and interpreted on cpu; "
        f"the table lives on {platform!r}")


def _gather_kernel(ids_ref, table_ref, out_ref, sems):
    g = pl.program_id(0)
    base = g * ROW_GROUP

    def row_dma(k):
        rid = ids_ref[base + k]
        return pltpu.make_async_copy(table_ref.at[rid], out_ref.at[k],
                                     sems.at[k])

    for k in range(ROW_GROUP):
        row_dma(k).start()
    for k in range(ROW_GROUP):
        row_dma(k).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_call(table, ids, interpret):
    batch = ids.shape[0]
    cols = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch // ROW_GROUP,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ROW_GROUP, cols), lambda g, ids: (g, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA((ROW_GROUP,))],
    )
    return pl.pallas_call(
        _gather_kernel,
        out_shape=jax.ShapeDtypeStruct((batch, cols), table.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(ids, table)


def gather_rows(table: jax.Array, ids: jax.Array, *,
                interpret: bool) -> jax.Array:
    """``table[ids]`` via overlapped row DMAs. ids: int32, len % ROW_GROUP == 0."""
    if ids.shape[0] % ROW_GROUP:
        raise ValueError(
            f"gather_rows: batch {ids.shape[0]} not a multiple of {ROW_GROUP}")
    return _gather_call(table, ids, interpret)


def _scatter_add_kernel(ids_ref, delta_ref, table_in_ref, table_ref,
                        scratch, read_sems, write_sems):
    del table_in_ref  # aliased with table_ref; all access goes through out
    g = pl.program_id(0)
    base = g * ROW_GROUP

    def read_dma(k):
        rid = ids_ref[base + k]
        return pltpu.make_async_copy(table_ref.at[rid], scratch.at[k],
                                     read_sems.at[k])

    def write_dma(k):
        rid = ids_ref[base + k]
        return pltpu.make_async_copy(scratch.at[k], table_ref.at[rid],
                                     write_sems.at[k])

    for k in range(ROW_GROUP):
        read_dma(k).start()
    for k in range(ROW_GROUP):
        read_dma(k).wait()
    scratch[:, :] = scratch[:, :] + delta_ref[:, :]
    for k in range(ROW_GROUP):
        write_dma(k).start()
    # write-backs must land before the next grid step may read these rows
    # (live ids are unique per call, but a later *call* may touch them)
    for k in range(ROW_GROUP):
        write_dma(k).wait()


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def _scatter_add_call(table, ids, deltas, interpret):
    batch = ids.shape[0]
    cols = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch // ROW_GROUP,),
        in_specs=[
            pl.BlockSpec((ROW_GROUP, cols), lambda g, ids: (g, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((ROW_GROUP, cols), table.dtype),
            pltpu.SemaphoreType.DMA((ROW_GROUP,)),
            pltpu.SemaphoreType.DMA((ROW_GROUP,)),
        ],
    )
    return pl.pallas_call(
        _scatter_add_kernel,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        grid_spec=grid_spec,
        # operand order: ids (scalar prefetch), deltas, table → alias table
        input_output_aliases={2: 0},
        interpret=interpret,
    )(ids, deltas, table)


def scatter_add_rows(table: jax.Array, ids: jax.Array, deltas: jax.Array,
                     *, interpret: bool) -> jax.Array:
    """In-place ``table.at[ids].add(deltas)`` for unique live ids; the input
    table buffer is donated."""
    if ids.shape[0] % ROW_GROUP:
        raise ValueError(
            f"scatter_add_rows: batch {ids.shape[0]} not a multiple of {ROW_GROUP}")
    return _scatter_add_call(table, ids, deltas, interpret)
