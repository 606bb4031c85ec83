"""MatrixTable — 2-D dense distributed table with row-subset Get/Add and
sparse staleness tracking.

Reference capability (not copied): row-range-sharded dense matrix with
per-row or whole-table Get/Add (``src/table/matrix_table.cpp``), the gen-2
unified table with ``is_sparse`` per-worker × per-row ``up_to_date_``
staleness tracking so sparse Gets return only stale rows
(``src/table/matrix.cpp:517-572``), and the SparseMatrixTable wire
compression variant (``src/table/sparse_matrix_table.cpp``).

TPU-native re-design:

* Server state is ONE row-sharded ``jax.Array`` in HBM; row Get is a jitted
  device gather, row Add a scatter-add (linear updaters), a
  gather→apply→scatter (a state shaped like the table) or a state step in
  front of that scatter-add (``Updater.row_state``), whichever program the
  table's row plan chose at its creation (``tables/row_plan.py``) — the
  client-side per-server ``Partition`` bucketing loop is gone.
* Row-id batches are padded to power-of-two buckets aimed at a sentinel
  scratch row, so jit traces are reused across batch sizes and the MXU sees
  static shapes. The work follows the rows named, not the bucket: an Add's
  kernel walks the delta's row groups, and a Get gathers its ids rounded up
  to a thirty-second of the bucket and 8 (``_live_slots``) and fills the
  rest of its ``(bucket, padded_cols)`` result with one read of the sentinel
  row, so a bucket size has at most 16 gather programs and callers see the
  shape and the sentinel tail they always saw.
* ``up_to_date`` staleness tracking is host-side metadata (numpy bools):
  it gates *what crosses the host boundary*, which is exactly the resource it
  existed to save; wire compression (SparseFilter) only ever mattered on a
  host hop and lives in ``multiverso_tpu.utils.quantization``.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu import log
from multiverso_tpu.dashboard import Dashboard, monitor, span
from multiverso_tpu.parallel import mesh as mesh_lib
from multiverso_tpu.runtime.message import MsgType, PendingHostRead
from multiverso_tpu.runtime.zoo import Zoo
from multiverso_tpu.tables.base import (RowOccurrences, ServerTable,
                                        WorkerTable, sum_duplicate_rows)
from multiverso_tpu.tables.device_ids import (  # noqa: F401
    DeviceIdsServer, DeviceIdsWorker, LaunchIds, live_slots as _live_slots)
# the device programs, which this module had before the plan took them
from multiverso_tpu.tables.row_plan import (  # noqa: F401
    _make_row_state_add, _row_gather, _row_gather_jit, _xla_scatter_add,
    row_plan)
from multiverso_tpu.updaters import AddOption, GetOption, get_updater
from multiverso_tpu.utils import async_upload, next_pow2 as _next_pow2


def _use_pallas_scatter(platform: str, num_shards: int, lanes: int = 128,
                        itemsize: int = 4) -> bool:
    """Whether the Pallas row-DMA scatter serves a table's row Adds.
    ``platform`` is that of the table mesh's devices: the kernel compiles
    for the TPU. Any number of shards goes: ``pallas_call`` has no SPMD
    partitioning rule, so a table sharded over the chips of one process
    routes an op's ids to the shards that own them and runs the kernel on
    every shard's block (``ops/sharded_rows``). Any number of lane tiles
    goes (a row wider than one tile is one strided descriptor,
    ``ops/pallas_rows``) up to the width whose row group still fits the
    kernel's VMEM; past it XLA's scatter serves, and the table's creation
    line and every launch record say which."""
    del num_shards
    from multiverso_tpu.ops.pallas_rows import fits_vmem
    return platform == "tpu" and fits_vmem(lanes, itemsize)


class _StageSlot:
    """A host Add's padded ids and values as they are uploaded, kept and
    refilled: ``ids`` and ``vals`` hold the largest bucket seen; rows of
    ``vals`` past ``rows``, and lanes past the table's columns, are zero;
    ``read`` says a launch has read these arrays since they were last
    known to be free."""

    __slots__ = ("ids", "vals", "rows", "read")

    def __init__(self, lanes: int, dtype) -> None:
        self.ids = np.empty(0, np.int32)
        self.vals = np.zeros((0, lanes), dtype)
        self.rows = 0
        self.read = False


class RowPieces(list):
    """The value rows of a fused host Add, one ``(rows, num_col)`` array of
    the table's dtype a request, in the order of its concatenated ids:
    what ``merge_add_requests`` hands ``process_add`` in place of one
    array, so that a row is copied once, into the array that is
    uploaded."""


class MatrixServer(DeviceIdsServer, ServerTable):
    def __init__(self, num_row: int, num_col: int, dtype: Any = np.float32,
                 updater_type: str = "", num_workers: Optional[int] = None,
                 init_value: Optional[np.ndarray] = None,
                 init_range: Optional[Tuple[float, float]] = None,
                 seed: int = 0, is_sparse: bool = False,
                 is_pipelined: Optional[bool] = None) -> None:
        super().__init__()
        zoo = Zoo.instance()
        self.num_row = int(num_row)
        self.num_col = int(num_col)
        self.dtype = np.dtype(dtype)
        self.mesh = zoo.mesh
        self.num_workers = num_workers if num_workers is not None else zoo.num_workers
        num_shards = zoo.num_servers
        # Keep >=1 scratch row past num_row: padded id buckets aim there.
        self.padded_rows = mesh_lib.pad_to_multiple(self.num_row, num_shards)
        if self.padded_rows == self.num_row:
            self.padded_rows += num_shards
        self.sentinel_row = self.num_row
        # Pad cols to the 128-lane width: XLA's physical TPU layout already
        # tiles the minor dim to 128, so this costs no extra HBM — and it
        # unlocks the Pallas row-DMA scatter path (ops/pallas_rows), which
        # is ~8x faster than XLA's serialized scatter for row Adds.
        self.padded_cols = mesh_lib.pad_to_multiple(self.num_col, 128)
        if self.padded_cols > 128:
            # the row kernel reaches a row of several lane tiles through
            # the table's (8, 128) tiles: whole tiles of rows on every
            # shard (HBM holds them anyway; the extra rows are scratch like
            # the sentinel). Decided from the shape alone: the gate below
            # imports Pallas, seconds that belong beside the upload, not
            # before it
            self.padded_rows = mesh_lib.pad_to_multiple(self.padded_rows,
                                                        8 * num_shards)
        self._block_rows = self.padded_rows // num_shards

        if init_value is not None and not callable(init_value):
            init_value = np.asarray(init_value, dtype=self.dtype).reshape(
                self.num_row, self.num_col)
        # a callable is a block source, ``(lo, n) -> rows [lo, lo + n)``
        self.data = self._put_rows(
            init_value if init_range is None or init_value is not None
            else functools.partial(self._uniform_rows, init_range, seed))

        self.updater = get_updater(self.dtype, updater_type)
        # one value of state a row (`Updater.row_state`): ``(rows,)``,
        # lane-dense in HBM (40 MB for 10,000,000 rows; as ``(rows, 1)`` a
        # TPU would tile it to 128 lanes a row), sharded like the table's
        # rows, with no worker dimension
        row_state = self.updater.row_state
        worker_dim = self.num_workers if self.updater.per_worker_state else 1
        self.states: Dict[str, jax.Array] = {}
        # a row state is whole lane tiles on every shard (`state_of_slots`)
        state_rows = mesh_lib.pad_to_multiple(
            self.padded_rows, 1024 * num_shards) if row_state \
            else self.padded_rows
        for name, (shape_suffix, sdtype) in self.updater.state_spec(
                (state_rows, self.padded_cols), self.dtype).items():
            shape = tuple(shape_suffix) if row_state \
                else (worker_dim,) + tuple(shape_suffix)
            # zeros made on the device: no host array of the state's size
            self.states[name] = jnp.zeros(shape, sdtype,
                                          device=self._state_sharding())

        # staleness metadata (gen-2 `up_to_date_`): host-side control plane.
        # is_pipelined doubles the planes (reference matrix.cpp:407-418):
        # each worker owns TWO staleness identities — worker_id and
        # worker_id + num_workers — which its double-buffered client
        # alternates between, so an in-flight pipelined Get and the next Get
        # each track their own stale set.
        self.is_sparse = bool(is_sparse)
        if is_pipelined is None:
            from multiverso_tpu import config as config_mod
            is_pipelined = bool(config_mod.get_flag("is_pipelined"))
        self.is_pipelined = bool(is_pipelined)
        if self.is_sparse:
            self.num_slots = self.num_workers * (2 if self.is_pipelined else 1)
            self._up_to_date = np.zeros((self.num_slots, self.num_row), dtype=bool)
            self._std_lock = threading.Lock()

        # which device program serves a row Add and a row Get, chosen once;
        # the op methods ask it. After the upload: the gate imports Pallas
        self.plan = row_plan(
            self.mesh, zoo.multihost is not None, dtype=self.dtype,
            lanes=self.padded_cols, updater=self.updater, cols=self.num_col,
            padded_rows=self.padded_rows, sentinel=self.sentinel_row)
        log.info("MatrixTable %dx%d on %d %s device(s): row scatter = %s",
                 self.num_row, self.num_col, num_shards,
                 self.mesh.devices.flat[0].platform, self.plan.why)
        # the ids' way up (`tables/device_ids.py`)
        self._init_device_ids(self.sentinel_row, num_shards == 1)
        self._duplicates_summed = Dashboard.counter(
            "ROW_ADD_DUPLICATES_SUMMED")
        self._stage_waits = Dashboard.counter("ROW_STAGE_WAITS")
        self._stage = _StageSlot(self.padded_cols, self.dtype)

    def _state_sharding(self):
        """Sharding of an updater state: its row dimension split like the
        table's rows."""
        if self.updater.row_state:
            return mesh_lib.table_sharding(self.mesh, ndim=1, shard_dim=0)
        return mesh_lib.table_sharding(self.mesh, ndim=3, shard_dim=1)

    def row_apply_traceable(self):
        """The per-row update as a TRACEABLE function
        ``(data, states, ids, delta, worker, scalars) -> (data, states)``
        for embedding in a caller's fused jit (device transactions).
        Same semantics as the add path: linear updaters reduce to a
        scatter-add (sign folded in), stateful updaters run the row
        update (a row-state updater its state step and the scatter-add).
        ``ids`` must be unique apart from sentinel pads with zero
        deltas."""
        return self.plan.row_apply

    # -- helpers -----------------------------------------------------------
    def _put_rows(self, rows=None) -> jax.Array:
        """The table's device state from its logical rows, put up shard by
        shard and a shard piece by piece (``mesh_lib.put_row_blocks``):
        ``rows`` is the ``(num_row, num_col)`` host array, a block source
        ``(lo, n) -> rows [lo, lo + n)`` asked in row order, or None for
        zeros. A block is padded (scratch rows, lanes) only where it needs
        it: a block of whole rows at the table's own width goes up as it
        came, a view of ``rows`` or the source's own array, so the host
        never holds a second table, nor a first one under a source."""
        def block_of(lo: int, hi: int) -> np.ndarray:
            live = max(0, min(hi, self.num_row) - lo)
            part = (None if rows is None or not live
                    else rows(lo, live) if callable(rows)
                    else rows[lo:lo + live])
            if part is not None and part.dtype == self.dtype \
                    and part.shape == (hi - lo, self.padded_cols):
                return part
            block = np.zeros((hi - lo, self.padded_cols), self.dtype)
            if part is not None:
                block[:live, : self.num_col] = part
            return block

        return mesh_lib.put_row_blocks(self.mesh, self.padded_rows,
                                       self.padded_cols, block_of,
                                       itemsize=self.dtype.itemsize)

    def _uniform_rows(self, init_range, seed: int, lo: int,
                      n: int) -> np.ndarray:
        """Rows ``[lo, lo + n)`` of the random-init server ctor overload
        (reference: matrix_table.cpp:372-384): the rows a draw of the whole
        table from ``seed`` would hold there (one 64-bit step of the
        generator a value, so a block starts ``lo * num_col`` steps in)."""
        rng = np.random.default_rng(seed)
        rng.bit_generator.advance(lo * self.num_col)
        return rng.uniform(*init_range,
                           size=(n, self.num_col)).astype(self.dtype)

    def _get_bucket(self, n: int, ensure_pad: bool) -> int:
        """The power-of-two bucket of a Get's result, so a caller's jit over
        it is shape-stable; ``ensure_pad`` keeps at least one sentinel slot
        in it (device-out gets hand the bucket itself to the caller as a
        compact training space; its masked ops need a guaranteed non-live
        row)."""
        # min bucket = pallas ROW_GROUP (batch must be a group multiple)
        return max(_next_pow2(n + 1 if ensure_pad else n), self.plan.group)

    def _staging(self, bucket: int) -> _StageSlot:
        """The slot a row Add's padded ids and values are written into and
        uploaded from, its arrays ``bucket`` slots or more: kept by the
        table and refilled (fresh megabytes an Add cost the dispatcher
        more than the rows it copied into them; PERF.md, Findings, PR 31).

        An uploaded array may not change while the runtime can still read
        it, and a CPU client's device array can be the host memory it was
        put from for as long as it lives: so the slot is refilled only
        after the launch that read it has run, which the table's newest
        state being ready says; ``ROW_STAGE_WAITS`` counts the times it
        had to be waited for."""
        slot = self._stage
        if slot.read:
            if not self.data.is_ready():
                self._stage_waits.add()
                self.data.block_until_ready()
            slot.read = False
        if len(slot.ids) < bucket:
            slot.ids = np.empty(bucket, np.int32)
            slot.vals = np.zeros((bucket, self.padded_cols), self.dtype)
            slot.rows = 0
        return slot

    def _gather_rows(self, row_ids: np.ndarray, device_out: bool = False,
                     took: Optional[LaunchIds] = None) -> jax.Array:
        """The rows ``row_ids`` names as ``(bucket, padded_cols)`` on the
        device (``device_out``: the mesh's first), the slots past them
        copies of the sentinel row. ``took``: the ids as the caller sent
        them up at submit; without it they go up here."""
        return self.plan.launch_get(self, (self.data, self.states), row_ids,
                                    took, device_out)

    # -- server ops --------------------------------------------------------
    def merge_add_requests(self, requests):
        """Fuse queued host row-Adds into ONE scatter: hand back one
        request whose apply is a single jitted/pallas scatter_add, its ids
        the group's ids concatenated (12 KB for three requests of 1,024)
        and its values the group's arrays as they came (``RowPieces``):
        ``process_add`` copies each value row once, into the array it
        uploads, and sums the rows two requests both name on the way,
        exactly when the apply path requires unique ids (the pallas
        in-place row-DMA kernel and stateful updaters; XLA's scatter-add
        handles duplicates natively).
        Linear updaters only — a stateful updater (momentum/adagrad)
        applied once to a summed delta is a different operator than N
        sequential applies. Whole-table, device-resident, and transact
        forms stop the scan (None when FIRST — per-message dispatch;
        otherwise the compatible prefix fuses and the rest waits for the
        next call). The ``apply_batch_rows`` flag bounds the fused row
        count so the power-of-two id bucket (and its zero-padded upload)
        cannot blow up under backlog."""
        if not self.plan.merge:
            return None
        from multiverso_tpu import config as config_mod
        rows_cap = int(config_mod.get_flag("apply_batch_rows"))
        ids_list, pieces = [], RowPieces()
        total = 0
        for request in requests:
            if not (isinstance(request, tuple) and len(request) == 3):
                break
            row_ids, values, _option = request
            if row_ids is None or isinstance(values, jax.Array):
                break
            row_ids = np.asarray(row_ids, dtype=np.int32).reshape(-1)
            values = np.asarray(values, dtype=self.dtype).reshape(
                -1, self.num_col)
            if len(row_ids) != len(values):
                break  # per-message path reports the real error
            if ids_list and rows_cap > 0 \
                    and total + len(row_ids) > rows_cap:
                break
            ids_list.append(row_ids)
            pieces.append(values)
            total += len(row_ids)
        if not ids_list:
            return None
        return ((np.concatenate(ids_list), pieces, requests[0][2]),
                total, len(ids_list))

    def process_add(self, request):
        with span("TABLE_PROCESS_ADD"):
            return self._process_add(request)

    def _process_add(self, request):
        if isinstance(request[0], str) and request[0] == "transact":
            return self._process_transact(request)
        if isinstance(request[0], str) and request[0] == "transact_named":
            return self._process_transact(self._resolve_named(request))
        row_ids, values, option = request
        option = option or AddOption()
        self.updater.check_option(option)
        # administrative access (worker id -1) charges slot 0, not slot n-1
        worker, scalars = self._option_consts(option)
        if isinstance(values, jax.Array):
            # Device add (the LocalForward analog: an in-process worker's
            # delta never touches the host — reference local messages
            # skipped serialization the same way, communicator.cpp:93-105).
            # Caller contract: ids unique; pad slots aim at sentinel_row
            # with exactly-zero deltas.
            self._process_add_device(row_ids, values, worker, scalars)
            return
        if row_ids is None:
            delta = np.zeros((self.padded_rows, self.padded_cols), dtype=self.dtype)
            delta[: self.num_row, : self.num_col] = np.asarray(
                values, dtype=self.dtype).reshape(self.num_row, self.num_col)
            self.data, self.states = self.plan.whole_update(
                self.data, self.states, async_upload(delta), worker,
                scalars)
            touched: Optional[np.ndarray] = None
        else:
            with span("TABLE_ROW_PREP") as prep:
                row_ids = np.asarray(row_ids, dtype=np.int32).reshape(-1)
                self._check_row_range(row_ids, "add")
                pieces = values if isinstance(values, RowPieces) else [
                    np.asarray(values, dtype=self.dtype).reshape(
                        -1, self.num_col)]
                total = sum(len(piece) for piece in pieces)
                if len(row_ids) != total:
                    log.fatal("Matrix.add: %d ids but %d value rows", len(row_ids), total)
                # where the plan needs distinct ids; otherwise a fused
                # group's rows are copied in arrival order, nothing sorted
                found = RowOccurrences(row_ids) if self.plan.unique_ids \
                    else None
                prep.n = n = total if found is None else found.n
                if n < total:
                    prep.dups = total - n
                    self._duplicates_summed.add(total - n)
                bucket = self._get_bucket(n, False)
                slot = self._staging(bucket)
                # what an earlier, longer Add left past these rows
                slot.vals[n:slot.rows, : self.num_col] = 0
                slot.rows = n
                row_ids = sum_duplicate_rows(row_ids, pieces, found,
                                             slot.vals[:n, : self.num_col])
                slot.ids[:n] = row_ids
                slot.ids[n:bucket] = self.sentinel_row
                took, delta = self.plan.host_operands(
                    self, prep, slot.ids, slot.vals, n, bucket)
            # the whole bucket went up, and the program walks it
            self.data, self.states = self.plan.launch_add(
                (self.data, self.states), took, delta, bucket, "dispatcher",
                worker, scalars)
            slot.read = True
            touched = row_ids
        self._stale(touched)

    def _stale(self, ids: Optional[np.ndarray]) -> None:
        """Rows ``ids`` changed (None: every row; an id past the table, a
        pad slot's, names none): no worker's copy of them is fresh."""
        if self.is_sparse:
            with self._std_lock:
                self._up_to_date[:, slice(None) if ids is None
                                 else ids[ids < self.num_row]] = False

    def _process_add_device(self, row_ids, values, worker, scalars) -> None:
        """A device Add, launched on the ids its caller sent up at submit
        (``SentIds``); ids that come without go up here. A delta of more
        rows than ids (a caller's buffer of one shape for every count of
        rows: ``MatrixWorker.add_device_async``) is applied as far as the
        ids go, by the one program of that shape."""
        took = getattr(row_ids, "took", None)
        row_ids = np.asarray(row_ids, dtype=np.int32).reshape(-1)
        n, rows = len(row_ids), values.shape[0]
        if rows < n:
            log.fatal("Matrix.add(device): %d ids but %d value rows", n, rows)
        if rows > n and not self.plan.longer_delta:
            log.fatal("Matrix.add(device): %d ids but %d value rows: a "
                      "delta longer than its ids is served under default / "
                      "sgd, and not where the Add is routed to the row "
                      "kernels of several chips", n, rows)
        took, ids_from = self.plan.took_ids(self, row_ids, "add", took,
                                            rows=rows)
        # the program walks the row groups of the rows named, not the
        # bucket's: one device program an Add
        self.data, self.states = self.plan.launch_add(
            (self.data, self.states), took,
            self.plan.device_delta(values, took.bucket),
            self.plan.launched(n), ids_from, worker, scalars)
        self._stale(row_ids)

    def _check_row_range(self, row_ids: np.ndarray, op: str) -> None:
        """Host-path ids must be in [0, num_row). Worker proxies already
        guard this, so only a routing bug (e.g. a shard router sending
        GLOBAL ids to a span-local member) reaches here — and it must die
        loudly: jax's clamping gather/scatter would otherwise silently
        misdirect the rows to the last local row."""
        if row_ids.size and (int(row_ids.min()) < 0
                             or int(row_ids.max()) >= self.num_row):
            log.fatal("Matrix.%s: row id out of range [0, %d) (offset %d "
                      "of the global table) — sharded routers must send "
                      "shard-local ids (docs/sharding.md)", op,
                      self.num_row, self.row_offset)

    def _resolve_named(self, request):
        """Rehydrate a named transaction descriptor into the live form:
        resolve the program name to this rank's locally-built jit and the
        table ids to this rank's server tables — the host-serializable
        indirection that lets device transactions ride the multihost
        lockstep stream (see runtime/programs.py)."""
        from multiverso_tpu.runtime.programs import resolve_program
        from multiverso_tpu.runtime.zoo import Zoo

        _, name, other_ids, args, touched = request
        server = Zoo.instance().server
        others = [server.table(tid)._unwrapped() for tid in other_ids]
        return ("transact", resolve_program(name), others, args, touched)

    def _process_transact(self, request):
        """Device transaction: ONE dispatcher op that reads several tables'
        device state, runs a caller-built fused jit over all of it, and
        writes the results back atomically (w.r.t. the dispatcher's
        serialization). The TPU-era answer to the reference's multi-table
        block protocols (pull rows from 2+ tables, train, push deltas —
        communicator.cpp RequestParameter/AddDeltaParameter): instead of
        2N messages and 2N+1 device dispatches, the whole block is one
        message and one dispatch with donated table buffers.

        request = ("transact", fn, other_servers, args, touched):
        ``fn(datas, states, *args) -> (new_datas, new_states, extra)``
        over lists ordered [this table, *other_servers]; ``extra`` is the
        reply (stays on device). ``touched`` (per-table id arrays or None)
        drives sparse-staleness invalidation."""
        _, fn, others, args, touched = request
        tables = [self] + list(others)
        datas = [t.data for t in tables]
        states = [t.states for t in tables]
        with monitor("SERVER_PROCESS_TRANSACT"):
            out = fn(datas, states, *args)
        try:
            new_datas, new_states, extra = out
            if (len(new_datas) != len(tables)
                    or len(new_states) != len(tables)):
                raise ValueError("result lists do not match table count")
        except (TypeError, ValueError) as exc:
            # the fn's jit has already executed and DONATED every table's
            # live buffers — there is nothing to roll back to. Die loudly
            # with the reason rather than serving dead buffers forever.
            log.fatal("transact fn must return (new_datas, new_states, "
                      "extra) matching the %d-table list (%s); the tables' "
                      "donated state is unrecoverable — recreate them",
                      len(tables), exc)
        for t, d, s in zip(tables, new_datas, new_states):
            t.data, t.states = d, s
        for t, ids in zip(tables, touched or [None] * len(tables)):
            if getattr(t, "is_sparse", False) and ids is not None:
                t._stale(ids)
        return extra

    def _is_worker(self, option) -> bool:
        """Administrative access (worker id outside [0, num_slots), e.g.
        checkpoint reads on a server-only node) must not touch any worker's
        staleness bitmap — aliasing it onto slot 0 would serve worker 0
        stale rows from its client cache (mirrors SyncServer._is_admin).
        num_slots covers the pipelined second plane (worker_id+num_workers)."""
        return option is not None and 0 <= option.worker_id < self.num_slots

    def process_get(self, request):
        return PendingHostRead.fetched(self.launch_get(request))

    def launch_get(self, request):
        with span("TABLE_PROCESS_GET"):
            return self._process_get(request)

    def _process_get(self, request):
        device_out = False
        if len(request) == 3:  # in-process device-out form
            row_ids, option, device_out = request
        else:
            row_ids, option = request
        if row_ids is None:
            if self.is_sparse and self._is_worker(option):
                return self._sparse_get(option)
            # admin whole-table reads take the dense path
            out = self.updater.access(self.data)
            return self._host_read(out)[: self.num_row, : self.num_col]
        # what an in-process device-path caller sent up at submit
        took = getattr(row_ids, "took", None)
        row_ids = np.asarray(row_ids, dtype=np.int32).reshape(-1)
        if not device_out:
            # device gets may carry sentinel-aimed pad ids (the compact
            # training space contract); host/wire gets may not
            self._check_row_range(row_ids, "get")
        n = len(row_ids)
        gathered = self._gather_rows(row_ids, device_out, took)
        if self.is_sparse and self._is_worker(option):
            with self._std_lock:
                self._up_to_date[option.worker_id, row_ids] = True
        if device_out:
            # rows stay in HBM: (bucket, padded_cols), slots >= n are
            # sentinel copies — the caller's compact training space
            return gathered
        # launched; fetched by whoever finishes the Get
        return self._host_read_behind(
            gathered, (slice(n), slice(self.num_col)))

    def _sparse_get(self, option: GetOption):
        """Return only the rows stale for this worker: (ids, rows)."""
        w = option.worker_id
        with self._std_lock:
            stale = np.where(~self._up_to_date[w])[0].astype(np.int32)
            self._up_to_date[w, stale] = True
        if len(stale) == 0:
            return stale, np.zeros((0, self.num_col), dtype=self.dtype)
        if len(stale) == self.num_row:
            return stale, self._host_read(
                self.data)[: self.num_row, : self.num_col]
        rows = self._host_read(
            self._gather_rows(stale))[: len(stale), : self.num_col]
        return stale, rows

    def remote_spec(self):
        return {"kind": "matrix", "num_row": self.num_row,
                "num_col": self.num_col, "dtype": self.dtype.str,
                "is_sparse": self.is_sparse,
                "is_pipelined": self.is_pipelined,
                "num_workers": self.num_workers}

    # -- checkpoint --------------------------------------------------------
    def _state_logical(self):
        """The index of a state array's logical part (padding is a function
        of the restoring mesh, not checkpoint content)."""
        if self.updater.row_state:
            return slice(0, self.num_row)
        return (slice(None), slice(0, self.num_row), slice(0, self.num_col))

    def store(self, stream) -> None:
        from multiverso_tpu.checkpoint import write_array, write_state_dict
        write_array(stream,
                    self._host_read(self.data)[: self.num_row,
                                               : self.num_col])
        # updater state sliced to logical dims
        write_state_dict(stream, {
            name: self._host_read(arr)[self._state_logical()]
            for name, arr in self.states.items()})

    def load(self, stream) -> None:
        from multiverso_tpu.checkpoint import read_array, read_state_dict
        arr = read_array(stream).astype(self.dtype).reshape(self.num_row, self.num_col)
        self.data = self._put_rows(arr)
        loaded = read_state_dict(stream)
        for name, cur in self.states.items():
            got = loaded.get(name)
            if got is None:
                continue  # v1 checkpoint: that state resets (pre-v2 behavior)
            if not self.updater.row_state \
                    and got.shape[0] != cur.shape[0]:
                # per-worker state from a world with a different worker
                # count: elastic restarts keep working — reset like v1
                log.info("checkpoint: %s worker dim %d != %d; resetting "
                         "that updater state", name, got.shape[0],
                         cur.shape[0])
                continue
            full = np.zeros(cur.shape, np.dtype(cur.dtype))
            full[self._state_logical()] = got
            self.states[name] = jax.device_put(full, self._state_sharding())
        # staleness is NOT restorable state: it certifies worker-side
        # client caches the snapshot does not cover — a restored table must
        # serve every row fresh once (values re-pulled, resume-exactness
        # preserved; claiming freshness against unknown caches would serve
        # stale rows silently)
        self._stale(None)

    # -- live migration (shard/reshard.py) ---------------------------------
    def extract_range(self, lo: int, hi: int):
        """Raw values of shard-local rows [lo, hi) — the migration
        transfer unit. Updater state deliberately excluded (documented
        reset on migration, like a v1 checkpoint restore)."""
        return self._host_read(self.data)[lo:hi, : self.num_col]

    def absorb_range(self, start: int, values) -> None:
        """Install raw rows at [start, start+len) — the recipient side of
        extract_range. Bypasses updaters: migrated values are state, not
        gradients (an updater would rescale them)."""
        values = np.asarray(values, dtype=self.dtype)
        n = values.shape[0]
        if start < 0 or start + n > self.num_row:
            log.fatal("absorb_range [%d, %d) outside [0, %d)",
                      start, start + n, self.num_row)
        padded = np.array(self._host_read(self.data))
        padded[start:start + n, : self.num_col] = values
        self.data = jax.device_put(
            padded, mesh_lib.table_sharding(self.mesh, ndim=2, shard_dim=0))
        self._stale(np.arange(start, start + n))


class MatrixWorker(DeviceIdsWorker, WorkerTable):
    """Client proxy for a 2-D table: whole or row-subset Get/Add; in sparse
    mode keeps a local row cache refreshed with only-stale-rows Gets."""

    # in-process proxies exchange device arrays with the dispatcher; the
    # remote subclass overrides this (and the device methods) — callers
    # must branch on the flag, not on hasattr
    supports_device_io = True
    def __init__(self, num_row: int, num_col: int, dtype: Any = np.float32,
                 updater_type: str = "", init_value: Optional[np.ndarray] = None,
                 init_range: Optional[Tuple[float, float]] = None,
                 is_sparse: bool = False, seed: int = 0,
                 is_pipelined: Optional[bool] = None,
                 server: Optional[MatrixServer] = None) -> None:
        super().__init__()
        self.num_row = int(num_row)
        self.num_col = int(num_col)
        self.dtype = np.dtype(dtype)
        self.is_sparse = bool(is_sparse)
        self._server_table = server or MatrixServer(
            num_row, num_col, dtype, updater_type, init_value=init_value,
            init_range=init_range, seed=seed, is_sparse=is_sparse,
            is_pipelined=is_pipelined)
        self._register(self._server_table)
        if Zoo.instance().multihost is not None:
            # device IO exchanges jax.Arrays with the dispatcher; lockstep
            # descriptors must be host-serializable — host paths only
            self.supports_device_io = False
        self._init_client_state(self._server_table.is_pipelined
                                if self.is_sparse else False,
                                self._server_table.num_workers)

    def _init_client_state(self, pipelined: bool, num_workers: int) -> None:
        """Sparse-mode client caches: one per staleness plane. In pipelined
        mode whole-table Gets alternate planes so an in-flight prefetch and
        the next Get never consume each other's stale sets."""
        self._pipelined = bool(pipelined)
        self._num_workers = int(num_workers)
        self._n_phases = 2 if self._pipelined else 1
        self._caches = [np.zeros((self.num_row, self.num_col), self.dtype)
                        for _ in range(self._n_phases)] if self.is_sparse else []
        self._phase = 0
        self._phase_of: Dict[int, int] = {}  # msg_id -> phase (async gets)
        # observability: rows actually fetched from the server by this proxy
        # (the resource candidate-row pulls exist to bound — tests assert on it)
        self.rows_pulled = 0

    # -- get ---------------------------------------------------------------
    def get(self, row_ids: Optional[np.ndarray] = None,
            option: Optional[GetOption] = None) -> np.ndarray:
        with self._public_op():
            option, phase = self._prep_get_option(option, row_ids)
            raw = super().get((self._norm_ids(row_ids), option))
            return self._finish_get(raw, row_ids, phase)

    def get_async(self, row_ids: Optional[np.ndarray] = None,
                  option: Optional[GetOption] = None) -> int:
        with self._public_op(), span("WORKER_SUBMIT") as submit:
            option, phase = self._prep_get_option(option, row_ids)
            ids = self._named_ids(row_ids, submit)
            msg_id = self._submit(MsgType.Request_Get, (ids, option), submit)
        self._phase_of[msg_id] = phase
        return msg_id

    def wait_get(self, msg_id: int, row_ids: Optional[np.ndarray] = None) -> np.ndarray:
        phase = self._phase_of.pop(msg_id, 0)
        return self._finish_get(self.wait(msg_id), row_ids, phase)

    def _prep_get_option(self, option: Optional[GetOption],
                         row_ids) -> Tuple[GetOption, int]:
        """Default option + pipelined plane selection: whole-table sparse
        Gets alternate between the worker's two staleness identities
        (worker_id, worker_id + num_workers — reference matrix.cpp:407-418)."""
        phase = 0
        if option is None:
            wid = self._channel.worker_id()
            if (self.is_sparse and self._pipelined and row_ids is None
                    and 0 <= wid < self._num_workers):
                phase = self._phase
                self._phase = 1 - self._phase
                wid += phase * self._num_workers
            option = GetOption(worker_id=wid)
        return option, phase

    def _finish_get(self, raw, row_ids, phase: int = 0) -> np.ndarray:
        if self.is_sparse and row_ids is None and isinstance(raw, np.ndarray):
            # admin-bypass reply (worker id out of range): dense whole table,
            # no staleness bookkeeping — do not touch the client cache
            self.rows_pulled += self.num_row
            return raw
        if self.is_sparse and row_ids is None:
            stale_ids, rows = raw
            cache = self._caches[phase]
            if len(stale_ids):
                cache[stale_ids] = rows
            self.rows_pulled += len(stale_ids)
            return np.array(cache, copy=True)
        if row_ids is None:
            self.rows_pulled += self.num_row
            return raw
        ids = np.asarray(row_ids).reshape(-1)
        self.rows_pulled += len(ids)
        if self.is_sparse:
            # the server marked these rows fresh for this worker (plane 0) —
            # mirror them into the plane-0 cache or a later whole-table
            # sparse get would serve stale values for exactly these rows
            self._caches[0][ids] = raw
        return raw

    # -- device IO (in-process workers only) --------------------------------
    # The LocalForward analog: a worker sharing the process with the table
    # exchanges DEVICE arrays with the dispatcher — candidate rows are
    # gathered in HBM and deltas scattered from HBM, no host copy on either
    # side. Remote proxies keep the host/wire path. Not available on
    # is_sparse tables (their client cache is host-resident).

    def get_device_async(self, row_ids: np.ndarray,
                         option: Optional[GetOption] = None) -> int:
        """Async candidate-row pull that stays in HBM. The reply (via
        ``wait_device``) is a ``(bucket, padded_cols)`` jax.Array, the
        bucket the next power of two above ``len(row_ids)``, whose slots
        ``>= len(row_ids)`` are sentinel copies (at least one) — usable
        directly as a compact training space. The device gathers the rows
        named, rounded up to a step of the bucket (``_live_slots``), and
        fills the rest from one read of the sentinel row: the result's
        shape follows the bucket alone, so a caller's own jit over it sees
        one shape a bucket whatever the count of rows it names.

        On a table on one device the ids are copied and their upload
        begins here, on the caller's thread, before the message is queued
        (``_ids_at_submit``): the caller may reuse or overwrite
        ``row_ids`` as soon as this returns. The proxy keeps that copy and
        the array on the device until its next device-path op, and a Get
        that names the rows of the op before it (the Get after a push to
        the same rows, the same pull again) sends nothing up and launches
        on it; which it is, is decided by comparing ``row_ids`` with the
        proxy's copy, never by the array's identity. On a mesh the request
        holds ``row_ids`` itself and the dispatcher sends the ids up: leave
        the array alone until ``wait_device`` returns. There the table's
        row plan keeps what it sent up by the same rule
        (``RowPlan.launch_ids``): a routed Get that names the rows of the
        routed op before it launches on that op's ids, compared with the
        plan's own copy."""
        if self.is_sparse:
            log.fatal("device IO is not available on is_sparse tables")
        self._require_device_io()
        with span("WORKER_SUBMIT") as submit:
            option, _ = self._prep_get_option(option, row_ids)
            ids = np.asarray(row_ids, np.int32).reshape(-1)
            submit.n = len(ids)
            sent = self._ids_at_submit(ids, "get")
            # every Get's range check, made while the upload is in flight
            # (0.05 ms for 100,000 ids): ids that fail it are never launched
            self._check_range(ids)
            return self._submit(MsgType.Request_Get, (sent, option, True),
                                submit)

    def wait_device(self, msg_id: int, row_ids: np.ndarray) -> "jax.Array":
        raw = self.wait(msg_id)
        self._phase_of.pop(msg_id, None)
        self.rows_pulled += len(np.asarray(row_ids).reshape(-1))
        return raw

    def add_device_async(self, values: "jax.Array", row_ids: np.ndarray,
                         option: Optional[AddOption] = None) -> int:
        """Async device-resident add. ``values`` is a jax.Array of shape
        ``(len(row_ids), <=num_col)``; live ids unique, pad slots (if the
        caller pads) aim at ``num_row`` (the sentinel) with zero deltas.

        ``values`` may have MORE rows than there are ids (under ``default``
        / ``sgd``; not on a table whose Adds are routed to the row kernels
        of several chips, which refuses it by name): a caller's buffer of one
        shape, such as the gradient of a device Get's ``(bucket, lanes)``
        result. Its rows past the ids are not applied, whatever they hold,
        and one device program serves every count of ids under that shape;
        a delta of exactly ``len(row_ids)`` rows compiles a program a
        count.

        On a table on one device the ids are copied and their upload
        begins here, on the caller's thread, before the message is queued
        (``_ids_at_submit``): the caller may reuse or overwrite
        ``row_ids`` as soon as this returns. An Add that names the rows of
        this proxy's last device-path op, a trainer's push of the rows it
        pulled, sends nothing up and launches on the ids that op left on
        the device (compared element for element with the proxy's own
        copy): hand over the ids as they were pulled, not padded to
        another count. A delta longer than its ids takes a Get's array
        only from an earlier such Add (its last slot holds the count). On
        a mesh the request holds
        ``row_ids`` itself and the dispatcher sends the ids up: leave the
        array alone until ``wait`` returns. Where the Add is routed to the
        chips' row kernels (``default`` / ``sgd``) its ids go up in a Get's
        form and the table's row plan keeps them, so the push of the rows
        just pulled, and the pull of the rows just pushed, launch on the
        ids already on the first chip (``RowPlan.launch_ids``). A count of
        ids that differs from the value rows' fails the op at its ``wait``,
        as on every Add path."""
        if self.is_sparse:
            log.fatal("device IO is not available on is_sparse tables")
        self._require_device_io()
        with span("WORKER_SUBMIT") as submit:
            option = self._default_add_option(option)
            ids = np.asarray(row_ids, np.int32).reshape(-1)
            submit.n = len(ids)
            return self._submit(
                MsgType.Request_Add,
                (self._ids_at_submit(ids, "add", values.shape[0]), values,
                 option), submit)

    def transact_device_async(self, fn, others: Sequence["MatrixWorker"],
                              args: tuple = (),
                              touched: Optional[Sequence] = None) -> int:
        """Submit a fused multi-table device transaction (one dispatcher
        op, one device dispatch): ``fn(datas, states, *args) ->
        (new_datas, new_states, extra)`` over the device state of
        ``[this table, *others]``, with ``extra`` as the (device) reply.
        ``fn`` should be jitted with ``donate_argnums=(0, 1)`` — the
        tables' buffers are updated in place.

        ``fn`` may be a NAME registered via
        :func:`multiverso_tpu.runtime.programs.register_program` — the
        only form that works across a multihost mesh (a closure cannot
        ride a lockstep descriptor; a name resolves on every rank to the
        locally-built identical jit, and ``args`` must then be host data:
        numpy/scalars). Raw-callable form is in-process only.

        Plain async server only: round-gated/deferred servers
        (BSP/deterministic) account per-table clocks that a cross-table
        transaction cannot honor — callers check the server's
        ``gates_gets``/``defers_adds`` and use the staged pull/push path
        there."""
        if self.is_sparse:
            log.fatal("device IO is not available on is_sparse tables")
        named = isinstance(fn, str)
        multihost = Zoo.instance().multihost is not None
        if not named:
            self._require_device_io()  # closures are in-process-only
        server = Zoo.instance().server
        if not (getattr(server, "plain_async", False)
                or (named and getattr(server, "supports_named_transact",
                                      False))):
            log.fatal("transact_device_async requires the plain async "
                      "server (BSP/deterministic servers keep per-table "
                      "clocks a cross-table transaction cannot honor)")
        other_ids = []
        for o in others:
            st = getattr(o, "_server_table", None)
            if st is None:
                log.fatal("transact_device_async: %r is not an in-process "
                          "table", o)
            if getattr(o, "is_sparse", False) or getattr(st, "is_sparse",
                                                         False):
                # same guard as self: a transaction with touched=None
                # would silently skip staleness invalidation and serve
                # other workers stale rows from their client caches
                log.fatal("device IO is not available on is_sparse tables")
            other_ids.append((o.table_id, st))
        if named:
            if multihost:
                import jax
                for a in args:
                    if isinstance(a, jax.Array):
                        log.fatal("named transaction args must be host "
                                  "data under a multihost mesh (numpy/"
                                  "scalars) — device arrays cannot ride "
                                  "the lockstep control plane")
            # the named request carries table IDS, not live objects:
            # host-serializable, resolved rank-locally at execution
            return super().add_async(
                ("transact_named", fn, tuple(tid for tid, _ in other_ids),
                 tuple(args), touched))
        return super().add_async(("transact", fn,
                                  [st for _, st in other_ids],
                                  tuple(args), touched))

    @property
    def sentinel_row(self) -> int:
        return self._server_table.sentinel_row

    # -- add ---------------------------------------------------------------
    def _auto_sparse_rows(self, values, row_ids):
        """Worker-side nonzero-row auto-detect (reference matrix.cpp:148-182):
        a whole-table Add to a sparse table scans the delta and ships only
        the nonzero rows — the caller keeps the dense API."""
        if row_ids is not None or not self.is_sparse:
            return row_ids, values
        values = np.asarray(values, dtype=self.dtype).reshape(
            self.num_row, self.num_col)
        nz = np.nonzero(values.any(axis=1))[0].astype(np.int32)
        if len(nz) == self.num_row:
            return None, values
        return nz, values[nz]

    def add(self, values: np.ndarray, row_ids: Optional[np.ndarray] = None,
            option: Optional[AddOption] = None) -> None:
        with self._public_op():
            row_ids, values = self._auto_sparse_rows(values, row_ids)
            option = self._default_add_option(option)
            super().add((self._norm_ids(row_ids), values, option))

    def add_async(self, values: np.ndarray, row_ids: Optional[np.ndarray] = None,
                  option: Optional[AddOption] = None) -> int:
        with self._public_op(), span("WORKER_SUBMIT") as submit:
            row_ids, values = self._auto_sparse_rows(values, row_ids)
            option = self._default_add_option(option)
            ids = self._named_ids(row_ids, submit)
            return self._submit(MsgType.Request_Add, (ids, values, option),
                                submit)

    # -- helpers -----------------------------------------------------------
    def _named_ids(self, row_ids, submit) -> Optional[np.ndarray]:
        """``_norm_ids``, counted on the op's WORKER_SUBMIT section (0: the
        whole table)."""
        ids = self._norm_ids(row_ids)
        submit.n = 0 if ids is None else len(ids)
        return ids

    def _norm_ids(self, row_ids) -> Optional[np.ndarray]:
        """A host-path op's ids as its request carries them: int32, inside
        the table, the server table's own (``_table_ids``)."""
        if row_ids is None:
            return None
        ids = np.asarray(row_ids, dtype=np.int32).reshape(-1)
        self._check_range(ids)
        return self._table_ids(ids)

    def _check_range(self, ids: np.ndarray) -> None:
        if ids.size and (ids.min() < 0 or ids.max() >= self.num_row):
            log.fatal("Matrix row id out of range [0, %d)", self.num_row)

    def _default_add_option(self, option: Optional[AddOption]) -> AddOption:
        if option is None:
            option = AddOption()
            option.worker_id = self._channel.worker_id()
        return option

    # -- TPU-era fast path -------------------------------------------------
    def get_device(self) -> jax.Array:
        return self._server_table.data

    def get_state_device(self, name: str) -> jax.Array:
        """An updater state's device array as the server holds it (for a
        row-state updater ``(rows,)``, the table's rows and its scratch
        rows): what a checkpoint stores, read without one."""
        return self._server_table.states[name]
