"""The row plan of a server table: which device program serves its row Add
and its row Get, what that program needs of its ids and its delta, and what
its launch record says.

ONE function, :func:`row_plan`, chooses, once, when the table is created.
The table's op methods parse the request and ask the plan
(:class:`RowPlan`), which sends the ids up, fits a device delta, launches,
and fills the TABLE_ROW_LAUNCH record and the always-on launch counters:
one piece of code for the matrix table, the table group and the FTRL table.

PERF.md section 3 tabulates the programs: who each serves, its name in a
device trace, the ``path`` its record and the ``ROW_LAUNCH_<PATH>_<OP>``
counters say, the slots it launches.

This module reaches ``ops/pallas_rows`` inside functions only (`_row_kernel`).
"""

from __future__ import annotations

import collections
from concurrent.futures import ThreadPoolExecutor
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.dashboard import Dashboard, current_span, span
from multiverso_tpu.parallel import mesh as mesh_lib
from multiverso_tpu.tables.array_table import _make_whole_update
from multiverso_tpu.tables.device_ids import (IDS_FROM, KeptIds, LaunchIds,
                                              live_slots, state_of_slots)
from multiverso_tpu.updaters import SGDUpdater, Updater
from multiverso_tpu.utils import async_upload


# ``(id of its TABLE_ROW_LAUNCH record, distinct rows of 128 its lane kernel
# walked)`` of the keyed FTRL Adds launched while the op trace recorded: the
# count is the program's third result, an int32 LEFT ON THE DEVICE. Nothing
# here fetches it (a launch only appends the pair: no copy is started and
# the dispatcher waits for nothing, traced or not); whoever reads the trace
# joins the pairs to the records by id and fetches the counts in one go
# (``benchmark/layers/ftrl_rows_share.py``). The newest 4,096 launches.
ROWS_WALKED: collections.deque = collections.deque(maxlen=1 << 12)


def _row_kernel():
    """``ops/pallas_rows``, loaded on first call: a second of module code
    (``jax.experimental.pallas``) that only a table the kernels serve pays."""
    from multiverso_tpu.ops import pallas_rows
    return pallas_rows


# -- the matrix table's device programs --------------------------------------
@functools.partial(jax.jit, static_argnames=("bucket", "cols"))
def _device_pad(values: jax.Array, bucket: int, cols: int) -> jax.Array:
    """(n, c) → (bucket, cols) zero-padded, entirely on device."""
    out = jnp.zeros((bucket, cols), values.dtype)
    return out.at[: values.shape[0], : values.shape[1]].set(values)


def _xla_scatter_add(data: jax.Array, ids: jax.Array, deltas: jax.Array,
                     *, sign: float = 1.0,
                     tail_count: bool = False) -> jax.Array:
    """XLA's scatter-add in the call shape of
    ``pallas_rows.scatter_add_rows``: ``ids`` may be longer than ``deltas``
    (a bucket; its tail is sliced off here) and the updater's sign is
    applied inside the program. ``tail_count``: ``ids[-1]`` is the number
    of leading slots that name rows; the rest add nothing, past the table's
    end, where XLA drops an update."""
    if sign != 1.0:
        deltas = sign * deltas
    slots = ids[: deltas.shape[0]]
    if not tail_count:
        return data.at[slots].add(deltas)
    live = jnp.arange(slots.shape[0]) < ids[-1]
    return data.at[jnp.where(live, slots, data.shape[0])].add(
        jnp.where(live[:, None], deltas, 0), mode="drop")


def _row_gather(data: jax.Array, ids: jax.Array,
                bucket: Optional[int] = None, sentinel: int = 0,
                live: Optional[int] = None) -> jax.Array:
    """The table's row Get: ``(bucket, lanes)`` whose first ``live`` slots
    are the rows ``ids[:live]`` names and whose every later slot is a copy
    of row ``sentinel``, read once and broadcast (the gather follows the
    ids named, not the bucket). ``ids`` come as an Add's do, ``bucket`` of
    them (``DeviceIdsServer.launch_ids``: one uploaded form, so that either
    op can launch on the other's), and the slots gathered are a static
    slice of them that XLA folds into the pass it makes over the ids
    anyway; ``live`` None gathers every id given. Ids that fill the bucket
    (or no bucket given) leave the gather alone. Named, like its table
    parameter, so that the compiled module is ``jit__row_gather`` in a
    trace and the gather a fusion over ``%data``."""
    if live is not None:
        ids = ids[:live]
    rows = data[ids]
    tail = (bucket or ids.shape[0]) - ids.shape[0]
    if not tail:
        return rows
    return jnp.concatenate(
        [rows, jnp.broadcast_to(data[sentinel], (tail, data.shape[1]))])


# one jit for every table: the programs are keyed by shapes, bucket, live
# slots and sentinel, and tables of one shape share them
_row_gather_jit = jax.jit(_row_gather,
                          static_argnames=("bucket", "sentinel", "live"))


def _make_row_state_add(updater: Updater, scatter, cols: int,
                        jit: bool = True):
    """A row Add under an updater with one value of state a row
    (``Updater.row_state``), ``(data, states, ids, delta, worker, scalars)
    -> (data, states)``: the named rows' states are read, stepped from the
    delta alone and written back, and the scaled delta goes to ``scatter``,
    the scatter-add the table's linear Adds use (the Pallas row kernel
    where it serves the table): one device program. ``ids`` may be a bucket
    longer than ``delta`` (the kernel's contract); a sentinel slot's zero
    delta leaves its state as it was and adds zero. ``cols`` is the table's
    column count, the length of a gradient row whatever the delta's width
    or the table's lanes."""

    def _row_state_add(data, states, ids, delta, worker, scalars):
        del worker  # the state is shared
        live = ids[: delta.shape[0]]
        step, new = updater.row_step(
            {k: state_of_slots(v, live) for k, v in states.items()}, delta,
            scalars, cols)
        # sentinel slots may repeat: each writes back the value it read
        states = {k: states[k].at[live].set(new[k]) for k in states}
        return scatter(data, ids, step), states

    # named so that the compiled module is ``jit__row_state_add`` in a
    # trace: what runs in it beside the kernel is the state step
    return jax.jit(_row_state_add, donate_argnums=(0, 1)) if jit \
        else _row_state_add


def _make_state_update(updater: Updater, jit: bool = True):
    """A row Add under a state shaped like the table: XLA gathers the rows
    and their states, applies the rule and scatters both back."""

    def f(data, states, ids, delta, worker, scalars):
        # a shared state has one plane
        at = worker if updater.per_worker_state else 0
        new_rows, stepped = updater.apply(
            data[ids], {k: v[at, ids] for k, v in states.items()}, delta,
            scalars)
        return data.at[ids].set(new_rows), {
            k: states[k].at[at, ids].set(stepped[k]) for k in states}

    return jax.jit(f, donate_argnums=(0, 1)) if jit else f


def _make_whole_row_state_update(updater: Updater, cols: int, rows: int):
    """The whole-table Add under a row-state updater: every row is named,
    the delta's lanes past the table's columns are zeros."""

    def f(data, states, delta, worker, scalars):
        del worker  # the state is shared
        step, new = updater.row_step(
            {k: v[:rows] for k, v in states.items()}, delta, scalars, cols)
        return data + step, {k: states[k].at[:rows].set(new[k])
                             for k in states}

    return jax.jit(f, donate_argnums=(0, 1))


# -- the plan ------------------------------------------------------------------
class RowPlan:
    """What :func:`row_plan` chose for one table.

    As data. ``path``: what an Add's launch record says, ``pallas`` or
    ``xla`` (a Get's says ``xla``; so does an Add's of a bucket past
    ``largest_bucket``, the largest the kernel takes).
    ``kernel``, ``interpret``: whether the gate chose the Pallas row
    kernels, and whether they run interpreted (None: no kernel).
    ``unique_ids``: a host Add's ids must be distinct before the launch
    (the kernel's in-place row DMA, a stateful rule's one apply a row;
    XLA's scatter-add sums repeats itself). ``routed``: the ops (``add``,
    ``get``) whose ids, and delta, go to the mesh's first chip with the
    host's counts by shard. ``longer_delta``: a device delta of more rows
    than its ids is served. ``merge``: queued host Adds may be fused (a
    linear rule). ``slot_bytes``, ``state_slot_bytes``: bytes of table
    rows, and of state, a launch reads for one id slot (an Add writes them
    again); ``itemsize``: of one value. ``group``, ``arrays``: the slots of
    the kernel's row group and the arrays it walks, which count its
    descriptors and waits (``ROW_GROUP``, the table; ``LANE_GROUP``, ``z``
    and ``n``). ``updater``, ``state_ops``: the rule's name, on the records
    of the ops that touch its state. ``stateful_adds``: a matrix table's
    Adds under a stateful updater, counted by path; their records say
    ``state_rows`` too (None: the FTRL table, whose rows are its state).
    ``why``: the plan in the words of the creation log line.

    As callables, the programs: ``add(state, took, delta, slots, path,
    *rule) -> state`` and ``get(state, took, live) -> rows`` behind
    ``launch_add`` / ``launch_get`` (``state``: ``(data, states)`` or ``(z,
    n)``); ``whole_update``, the whole-table Add; ``row_apply``, the row
    update, traceable, for a caller's fused jit; ``scatter_add``, a linear
    rule's as launched; ``device_delta(values, bucket)``, a device delta
    as the Add's program takes it."""

    path = "xla"
    kernel = False
    interpret: Optional[bool] = None
    unique_ids = True
    routed: Tuple[str, ...] = ()
    longer_delta = merge = False
    slot_bytes = state_slot_bytes = 0
    itemsize = 4
    group, arrays = 0, 1
    largest_bucket = float("inf")
    updater, state_ops = "", ()
    stateful_adds = None
    why = "XLA scatter"
    add = get = whole_update = row_apply = scatter_add = None
    device_delta = staticmethod(lambda values, bucket: values)
    # a device Get's result is committed to ONE device, the mesh's first:
    # it feeds WORKER-thread jits (the word2vec fast path's compact
    # training space), which must be single-device programs
    # (`ArrayServer._leaf_codec` has the reason)
    _out_device = None
    # the routed programs of a table sharded over chips (`ops/sharded_rows`)
    _shards = None
    # the last routed op's ids as they went up, with an Add's counts and
    # capacity (`launch_ids`)
    _kept: Optional[KeptIds] = None

    def __init__(self) -> None:
        # always on: which program served each row launch, by op
        self._launches = {
            ("add", "pallas"): Dashboard.counter("ROW_LAUNCH_PALLAS_ADD"),
            ("add", "xla"): Dashboard.counter("ROW_LAUNCH_XLA_ADD"),
            ("get", "pallas"): Dashboard.counter("ROW_LAUNCH_PALLAS_GET"),
            ("get", "xla"): Dashboard.counter("ROW_LAUNCH_XLA_GET")}
        # and whose thread had uploaded the launch's ids
        self._ids_from = {
            "caller": Dashboard.counter("ROW_IDS_FROM_CALLER"),
            "dispatcher": Dashboard.counter("ROW_IDS_FROM_DISPATCHER")}

    def launched(self, rows: int) -> int:
        """Id slots the Add's program walks for ``rows`` delta rows, whole
        groups: a device Add's rows named, a host Add's uploaded bucket."""
        return -(-rows // self.group) * self.group

    def launch_ids(self, table, row_ids: np.ndarray, op: str, prep,
                   **form) -> LaunchIds:
        """``row_ids`` (int32) as ``op``'s program takes them, their upload
        begun, inside the op's TABLE_ROW_PREP (``prep``):
        ``table.launch_ids`` (the bucket's one form), or, where the op is
        routed, on the mesh's first chip with the host's count of them by
        shard and a segment's capacity. A routed Add's and a routed Get's
        go up in ONE form, the Get's: the ``live_slots`` it gathers alone,
        the ids named, then ids past the table, which no shard owns, and
        the sentinel last (the result's tail is its row, wherever it
        lives); the Add's program walks the slots its delta has rows for.

        The plan keeps what its last routed op sent up (``KeptIds``, depth
        one; only the dispatcher routes, so no lock), and a routed op that
        names the same rows launches on it: a trainer's Get names the rows
        of its Add. Nothing is filled, counted (no TABLE_ROW_ROUTE), put or
        assembled; ``ROW_IDS_KEPT`` counts it, and a routed op's ``prep``
        says the ``bytes`` of ids that went up: 0. The same rows:
        ``row_ids`` equal, element for element, to the plan's own copy of
        what the last op named (never the caller's array, which it may
        write again once its op has returned), in an array of the same
        form (``KeptIds.serves``). Anything else is a miss and replaces
        what is kept. Hit or miss, the op's own ``counts`` (a Get's: the
        named ids' and one at the sentinel's owner) and ``capacity`` are
        worked out from the named ids' counts alike, so a hit launches the
        program a miss would have."""
        if op not in self.routed:
            return table.launch_ids(row_ids, op, **form)
        from multiverso_tpu.ops import sharded_rows
        n, shards = len(row_ids), self._shards.shards
        form = table.launch_form(n, op, **form)
        slots = live_slots(n, form[0])
        block = table.padded_rows // shards
        kept = self._kept
        if kept is not None and kept.serves(row_ids, op, form) \
                and np.array_equal(row_ids, kept.named):
            table.ids_kept.add()
            took = kept.took
        else:
            ids = table._padded_ids(row_ids, slots, None, table.padded_rows,
                                    table.sentinel_row)
            with span("TABLE_ROW_ROUTE") as routing:
                routing.n = n
                counts = sharded_rows.shard_counts(ids[:n], block, shards)
            # as an Add takes them. What goes up is a fresh array nobody
            # writes again: its first slots are the plan's own copy of the
            # ids named
            took = LaunchIds(
                self._shards.on_first(ids), form[0], counts,
                sharded_rows.shard_capacity(int(counts.max()), n, shards),
                ids.nbytes, ids[:n])
            self._kept = KeptIds(took, took.host)
            prep.bytes = took.nbytes
        if op == "get":
            counts = took.counts
            if slots > n:
                # the sentinel, the last slot, is its owner's to gather
                counts = counts.copy()
                counts[table.sentinel_row // block] += 1
            took = took._replace(
                counts=counts, capacity=sharded_rows.shard_capacity(
                    int(counts.max()), slots, shards))
        return took

    def took_ids(self, table, row_ids: np.ndarray, op: str,
                 took: Optional[LaunchIds], **form):
        """TABLE_ROW_PREP of a Get or a device Add: ``(the ids on their way
        up, who sent them)``: ``took``, the caller's at submit, or here."""
        ids_from = IDS_FROM[took is not None]
        with span("TABLE_ROW_PREP") as prep:
            prep.n = len(row_ids)
            if took is None:
                took = self.launch_ids(table, row_ids, op, prep, **form)
        return took, ids_from

    def host_operands(self, table, prep, ids: np.ndarray, vals: np.ndarray,
                      n: int, bucket: int) -> Tuple[LaunchIds, jax.Array]:
        """A host Add's operands on their way up, inside its TABLE_ROW_PREP
        (``prep``), from the table's staging arrays (``n`` distinct ids
        then sentinel slots, their summed rows then zeros): ``bucket``
        slots of both in ONE call (a call costs the host a quarter of a
        millisecond); a routed Add's ``n`` ids, in the routed ops' one form
        (``launch_ids``: the Get of them launches on that array), and rows
        at the table's columns go to the first chip, as a device delta's."""
        if "add" in self.routed:
            return (self.launch_ids(table, ids[:n], "add", prep),
                    self._shards.on_first(vals[:n, : table.num_col]))
        ids_up, vals_up = async_upload((ids[:bucket], vals[:bucket]))
        return LaunchIds(ids_up, bucket, None, 0, ids_up.nbytes,
                         ids[:n]), vals_up

    def launch_add(self, state, took: LaunchIds, delta: jax.Array,
                   slots: int, ids_from: str, *rule):
        """TABLE_ROW_LAUNCH of an Add: its program on ``took`` and
        ``delta``, and its record; the table's new state. ``slots``: the id
        slots launched (``launched``, or a keyed Add's ``live_slots``);
        ``rule``: a matrix table's worker and option scalars."""
        path = self.path if took.bucket <= self.largest_bucket else "xla"
        with span("TABLE_ROW_LAUNCH") as launch:
            self._note(launch, "add", path, slots, took, ids_from,
                       delta.shape[-1] * self.itemsize)
            return self.add(state, took, delta, slots, path, *rule)

    def launch_get(self, table, state, row_ids: np.ndarray,
                   took: Optional[LaunchIds],
                   device_out: bool = False) -> jax.Array:
        """TABLE_ROW_PREP and TABLE_ROW_LAUNCH of a Get: the rows (weights)
        ``row_ids`` names at the op's bucket, on the mesh's first device
        where ``device_out``. The slots gathered are the ids named rounded
        up (``live_slots``), not the bucket the result (or the ids) fills."""
        took, ids_from = self.took_ids(table, row_ids, "get", took,
                                       ensure_pad=device_out)
        with span("TABLE_ROW_LAUNCH") as launch:
            live = live_slots(len(took.host), took.bucket)
            self._note(launch, "get", "xla", live, took, ids_from,
                       self.slot_bytes)
            rows = self.get(state, took, live)
            return jax.device_put(rows, self._out_device) if device_out \
                else rows

    def _note(self, launch, op: str, path: str, slots: int, took: LaunchIds,
              ids_from: str, exchanged: int) -> None:
        """What a row launch did, on its TABLE_ROW_LAUNCH record
        (``dashboard._Section`` has the fields) and the always-on counters:
        ``n`` id slots (an Add's row groups, the slots a Get gathers: not
        the bucket); the kernel's ``descriptors`` (a read, and for an Add a
        write, a slot of its whole groups and array; XLA's: not counted)
        and ``waits`` (two a group and array; a shard's last, partial group
        a slot); ``bytes`` of rows moved; the rule and bytes of state the
        op touches. A routed op's ``n`` sums the shards; every shard's
        segment but the first crossed chips, ``exchanged`` bytes a row."""
        self._launches[op, path].add()
        self._ids_from[ids_from].add()
        if launch.id:
            launch.ids_from = ids_from
            launch.ids_ready = int(took.ids.is_ready())
        launch.path = path
        moves = 2 if op == "add" else 1
        walked, waits = slots, 0
        if took.counts is not None:
            from multiverso_tpu.ops import sharded_rows
            by_shard = np.full(len(took.counts), took.capacity)
            if op == "add":
                by_shard = sharded_rows.launched_slots(took.counts)
                waits = sharded_rows.launch_waits(took.counts)
            slots = walked = int(by_shard.sum())
            launch.shards = len(by_shard)
            launch.max_shard_n = int(by_shard.max())
            launch.exchange_bytes = ((len(by_shard) - 1) * took.capacity
                                     * exchanged)
        elif path == "pallas":
            walked = self.launched(slots)
            waits = 2 * self.arrays * (walked // self.group)
        launch.n = slots
        if path == "pallas":
            launch.descriptors = moves * self.arrays * walked
            launch.waits = waits
        launch.bytes = moves * slots * self.slot_bytes
        if op in self.state_ops:
            launch.updater = self.updater
            launch.state_bytes = moves * slots * self.state_slot_bytes
            if self.stateful_adds is not None:
                self.stateful_adds[path].add()
                launch.state_rows = slots


def row_plan(mesh, spans_processes: bool, *, dtype: Any = np.float32,
             lanes: int = 128, updater: Optional[Updater] = None,
             cols: int = 0, padded_rows: int = 0, sentinel: int = 0,
             keyed: Optional[Tuple[Callable, Callable]] = None,
             fill: Optional[Callable[[], None]] = None,
             platform: Optional[str] = None) -> RowPlan:
    """The plan of a table on ``mesh`` (``spans_processes``: under a
    multi-process runtime; ``platform``: the mesh's devices' unless given),
    the one place that chooses. A matrix table gives its ``dtype``,
    ``lanes`` (the padded columns), ``updater``, ``cols``, ``padded_rows``
    and ``sentinel`` row. The keyed FTRL table gives ``keyed``, its two
    jitted programs (``ftrl_table._make_programs``), and ``fill``, which
    makes its state: the plan answers in two steps around it, so that the
    lane kernel's module, where this table will launch it, loads on a
    thread under the fill (which waits on the device, holding no
    interpreter lock) and nowhere earlier."""
    from jax.sharding import SingleDeviceSharding
    plan = RowPlan()
    shards = int(mesh.devices.size)
    platform = platform or mesh.devices.flat[0].platform
    dtype = np.dtype(dtype)
    plan.itemsize = dtype.itemsize
    plan._out_device = SingleDeviceSharding(mesh.devices.flat[0])

    if keyed is not None:
        # ONE device of a platform the row kernels run on (`interpret_for`
        # says how); a table anywhere else never loads their module
        plan.kernel = shards == 1 and platform in ("tpu", "cpu")
        with ThreadPoolExecutor(1, "ftrl-row-kernel-import") as loading:
            if plan.kernel:
                loading.submit(_row_kernel)
            fill()
        if plan.kernel:
            # here, not on the loading thread, a failed import raises
            pallas_rows = _row_kernel()
            plan.interpret = pallas_rows.interpret_for(platform)
            plan.path, plan.group = "pallas", pallas_rows.LANE_GROUP
            plan.largest_bucket = pallas_rows.PREFETCH_SLOTS
            plan.why = ("an Add's rows of 128 read, stepped in VMEM and "
                        "written back by the Pallas row kernel%s (XLA's "
                        "gathers, step and scatters past a bucket of %d "
                        "keys)" % (", interpreted" if plan.interpret else "",
                                   plan.largest_bucket))
        else:
            plan.why = "an Add's step on XLA's gathers, written by XLA scatter"
        get, add = keyed
        # an FTRL step is not linear and sums a repeated key's gradients on
        # the device; the table holds `z` and `n` and nothing else
        plan.unique_ids, plan.longer_delta = False, True
        plan.slot_bytes = plan.state_slot_bytes = 8
        plan.arrays = 2
        plan.updater, plan.state_ops = "ftrl", ("add", "get")

        def keyed_add(state, took, grad, slots, path):
            *state, walked = add(
                *state, took.ids, grad, live=slots,
                rows=plan.interpret if path == "pallas" else None)
            launch = current_span()
            if launch and walked is not None:
                ROWS_WALKED.append((launch, walked))
            return tuple(state)

        plan.add = keyed_add
        plan.get = lambda state, took, live: get(*state, took.ids, live=live)
        if shards > 1:
            # a worker's gradient is committed to one device
            everywhere = mesh_lib.replicated(mesh, ndim=1)
            plan.device_delta = lambda grad, bucket: jax.device_put(
                grad, everywhere)
        return plan

    # the gate keeps its home (tests replace it there). A mesh over several
    # processes keeps XLA's partitioned programs: the routed ones assemble
    # their operands from this process's devices alone
    from multiverso_tpu.tables import matrix_table
    plan.kernel = matrix_table._use_pallas_scatter(
        platform, shards, lanes, dtype.itemsize
    ) and (shards == 1 or not spans_processes)
    linear = type(updater) in (Updater, SGDUpdater)
    sign = -1.0 if isinstance(updater, SGDUpdater) else 1.0
    pallas_rows = _row_kernel()     # the gate has loaded it
    plan.group = pallas_rows.ROW_GROUP
    plan.slot_bytes = lanes * dtype.itemsize
    scatter = functools.partial(_xla_scatter_add, sign=sign)
    if not plan.kernel:
        plan.why = "XLA scatter (%s)" % (
            "the kernel compiles for tpu only" if platform != "tpu"
            else "the mesh spans processes" if shards > 1 and spans_processes
            else "a row group of %d lanes is past the kernel's VMEM" % lanes)
    else:
        plan.interpret = pallas_rows.interpret_for(platform)
        plan.why = "pallas row-DMA kernel, %s" % (
            "interpreted" if plan.interpret else "compiled")
        if shards == 1:
            scatter = plan.scatter_add = functools.partial(
                pallas_rows.scatter_add_rows, interpret=plan.interpret,
                sign=sign)
        else:
            from multiverso_tpu.ops import sharded_rows
            plan._shards = sharded_rows.programs(mesh, plan.interpret, sign)
            plan.routed = ("get", "add") if linear else ("get",)
            plan.why += (", on every shard's block of %d rows, ids routed "
                         "to their owners" % (padded_rows // shards))
            if not linear:
                plan.why += ("; this table's %s updater takes XLA's "
                             "partitioned row update" % updater.name)
        # the kernel takes a linear updater's delta, or a row-state
        # updater's scaled delta (over chips the first is routed, the
        # second takes XLA's partitioned programs)
        if linear or (updater.row_state and shards == 1):
            plan.path = "pallas"
    if plan.scatter_add is None:
        # where no kernel serves the rows (`row_apply` embeds `scatter`)
        plan.scatter_add = jax.jit(scatter, donate_argnums=(0,),
                                   static_argnames=("tail_count",))

    plan.whole_update = (
        _make_whole_row_state_update(updater, cols, padded_rows)
        if updater.row_state else _make_whole_update(updater))
    if linear:
        plan.merge = True
        plan.unique_ids = plan.kernel

        def apply_linear(data, states, ids, delta, worker, scalars):
            return scatter(data, ids, delta), states

        plan.row_apply = apply_linear
        if "add" in plan.routed:
            # one program: the first chip puts the delta's rows in shard
            # order, each shard is sent its own and its kernel walks them
            plan.add = lambda state, took, delta, *_: (
                plan._shards.add(state[0], took.ids, delta,
                                 capacity=took.capacity), state[1])
            plan.device_delta = lambda values, bucket: \
                plan._shards.on_first(values)
        else:
            plan.longer_delta = True
            plan.add = lambda state, took, delta, *_: (plan.scatter_add(
                state[0], took.ids, delta, tail_count=took.counted), state[1])
    else:
        make = (functools.partial(_make_row_state_add, updater, scatter, cols)
                if updater.row_state
                else functools.partial(_make_state_update, updater))
        update, plan.row_apply = make(), make(jit=False)
        plan.add = lambda state, took, delta, slots, path, *rule: update(
            *state, took.ids, delta, *rule)
        spec = updater.state_spec((padded_rows, lanes), dtype)
        if spec:
            plan.updater, plan.state_ops = updater.name, ("add",)
            plan.state_slot_bytes = sum(
                np.dtype(sdtype).itemsize for _, sdtype in spec.values()
            ) * (1 if updater.row_state else lanes)
            plan.stateful_adds = {
                "pallas": Dashboard.counter("ROW_LAUNCH_PALLAS_STATEFUL_ADD"),
                "xla": Dashboard.counter("ROW_LAUNCH_XLA_STATEFUL_ADD")}
            if shards == 1:
                plan.why += "; %s updater: %s" % (updater.name, (
                    "state step, then that scatter-add of the scaled delta"
                    if updater.row_state else "XLA's row update"))

    if plan.path == "xla":
        # XLA's programs take a device delta zero-padded to the id bucket
        # and the table's lanes, on the table's devices: a worker's is
        # committed to ONE device (a device Get's contract) and is
        # re-sharded here, on the dispatcher thread, where cross-shard
        # collectives are legal (the jit would reject mixed device sets)
        by_rows = mesh_lib.table_sharding(mesh, ndim=2, shard_dim=0)
        plan.device_delta = lambda values, bucket: jax.device_put(
            _device_pad(values.astype(dtype), bucket, lanes), by_rows)

    if "get" in plan.routed:
        # every shard's gather of its rows, put in order on the first chip
        plan.get = lambda state, took, live: plan._shards.get(
            state[0], took.ids, took.capacity, took.bucket)
    else:
        gather = functools.partial(_row_gather_jit, sentinel=sentinel)
        plan.get = lambda state, took, live: gather(
            state[0], took.ids, bucket=took.bucket, live=live)
    return plan
