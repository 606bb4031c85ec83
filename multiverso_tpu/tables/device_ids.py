"""The ids of an in-process device-path op on their way to the device: what
every table kind whose device ops launch on a padded id array shares.

A table on one device has its caller send an op's ids up itself, at submit,
so that the upload rides under the queue wait (PR 36), and the proxy keeps
what its last op sent up, so that the next op that names the same ids
launches on the array that is already there (PR 39). On a table sharded
over the chips of one process the dispatcher sends the ids up, and there
the table's row plan keeps what its last routed op sent up, by the same
rule (``KeptIds``; ``RowPlan.launch_ids``, PR 53). The matrix and the
keyed FTRL table share both, as two mixins:

* ``DeviceIdsServer`` (beside ``ServerTable``): the form of the array an op's
  ids go up in (``launch_form``) and the array itself (``launch_ids``); the
  table's row plan (``tables/row_plan.py``) launches on it and counts who
  sent it up.
* ``DeviceIdsWorker`` (beside ``WorkerTable``): ``_ids_at_submit``, the
  caller's half, with the kept ids.

A kind says what it needs through a few attributes and hooks named in the
mixins' docstrings; nothing here knows a row, a key or a column.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.dashboard import Dashboard, span
from multiverso_tpu.utils import async_upload


def live_slots(n: int, bucket: int) -> int:
    """Slots of a ``bucket`` that a Get of ``n`` ids gathers: ``n`` rounded
    up to a step of a thirty-second of the bucket (at least 8), so that a
    bucket size compiles at most 16 gather programs, not one an id count,
    and at most 3% of it is left aimed at the sentinel (such a slot costs
    the device three named rows'). Then one group of 8 more: XLA's TPU
    gather takes its ids in tiles of 1,024 and tiles its rows by 128 where
    the ids fill their last tile, by 256 where they leave most of it empty,
    and the second form moves a row in 4.3 ns against 10.3 (PERF.md,
    Findings, PR 27); a whole number of steps is a whole number of tiles
    from a bucket of 32,768 up. A count within a step of the bucket gathers
    the bucket."""
    step = max(bucket // 32, 8)
    return min(-(-n // step) * step + 8, bucket)


def state_of_slots(state: jax.Array, live: jax.Array) -> jax.Array:
    """``state[live]`` of a lane-dense ``(rows,)`` state whose length is
    whole lane tiles (the table pads it so): read as rows of 128 values (a
    bitcast) and the one lane a slot wants picked under a mask (the sum has
    one term that is not zero, so it is exact). XLA's TPU gather moves
    100,000 rows of 512 bytes in 0.137 ms and the select takes 0.080; the
    same gather of single floats took 1.195 ms (PERF.md, Findings,
    PR 33)."""
    rows = state.reshape(-1, 128)[live >> 7]
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    return jnp.sum(jnp.where(lane == (live & 127)[:, None], rows, 0), axis=1)


# who sent a launch's ids up, by whether its request carried them
# (``SentIds.took``): the dispatcher in the op's TABLE_ROW_PREP, or the
# caller's own thread at submit
IDS_FROM = ("dispatcher", "caller")


class LaunchIds(NamedTuple):
    """The ids of one row op as its launch takes them, on their way to the
    device (``DeviceIdsServer.launch_ids``). ``ids``: on a table one program
    serves, the ids padded to ``bucket`` with sentinel-aimed slots, an
    Add's and a Get's alike; a routed op's on a table sharded over chips
    (``RowPlan.launch_ids``), an Add's and a Get's alike, the slots a Get
    gathers (the ids, ids past the table, the sentinel last) as the first
    shard's piece of the array the routed program takes
    (``ShardedRows.on_first``). ``bucket``: the op's power of two (the
    shape of a Get's result, and of a delta XLA's programs take).
    ``counts`` and ``capacity``: the host's part of routing an op over the
    chips, the op's own, None and 0 where nothing is routed.
    ``nbytes`` went up. ``host``: the ids named as they went up, a view of
    the uploaded host array (to read, never to write). ``counted``: the
    bucket's last slot holds the count of ids and not the sentinel (an Add
    whose delta outnumbers its ids). ``host``, ``bucket`` and ``counted``
    decide the array: an op whose own would have the same three can launch
    on this one (``DeviceIdsWorker._ids_at_submit`` keeps the last; on a
    mesh ``RowPlan.launch_ids`` does)."""

    ids: jax.Array
    bucket: int
    counts: Optional[np.ndarray]
    capacity: int
    nbytes: int
    host: np.ndarray
    counted: bool = False


class SentIds(np.ndarray):
    """The int32 ids of an in-process device-path op on a table on one
    device as its request holds them: an ndarray to everything that reads
    ids on the host, that also carries what the caller sent up at submit
    (``took``). The request keeps its shape, ``(ids, values, option)`` or ``(ids, option, True)``,
    for whatever stands between the proxy and the table."""

    took: Optional[LaunchIds] = None


class KeptIds(NamedTuple):
    """What a proxy keeps of the last device-path op it sent up
    (``DeviceIdsWorker._ids_at_submit``), and a row plan of the last routed
    op it sent up (``RowPlan.launch_ids``: ``took`` with the counts by shard
    of the ids named, an Add's): ``took``, the array on the device,
    and ``named``, a private host copy of the ids as the caller named them
    (before a group's bases; ``took.host`` itself where nothing is
    added)."""

    took: LaunchIds
    named: np.ndarray

    def serves(self, ids: np.ndarray, op: str,
               form: Tuple[int, bool]) -> bool:
        """Whether the kept array could be the one ``op`` of ``ids`` in
        ``form`` (``DeviceIdsServer.launch_form``) would send up, by what is
        cheap to see: the count, the bucket, the last slot, the first and
        the last id. A Get reads the last slot only where it gathers the
        whole bucket, and then not one that holds a count."""
        named, took = self.named, self.took
        n, (bucket, counted) = len(ids), form
        if n != len(named) or bucket != took.bucket:
            return False
        if op == "add":
            if counted != took.counted:
                return False
        elif took.counted and live_slots(n, bucket) == bucket:
            return False
        return not n or (ids[0] == named[0] and ids[-1] == named[-1])


class DeviceIdsServer:
    """A server table's half. The kind provides ``_get_bucket(n,
    ensure_pad)``, the power of two an op of ``n`` ids is padded to (or its
    own ``launch_form``), and calls ``_init_device_ids`` once it knows its sentinel (the id of the
    scratch slot padding aims at) and whether it is on one device."""

    @property
    def orders_adds(self) -> bool:
        """``ServerTable.orders_adds`` as the table's row plan says it: a
        rule that is not linear (``RowPlan.merge`` declined) makes an Add
        an optimizer step, the dispatcher never merges them and the async
        server stamps their order on the replies (``Server._stamp``)."""
        return not self.plan.merge

    def _init_device_ids(self, sentinel: int, one_device: bool) -> None:
        self._pad_id = int(sentinel)
        # of the launches on ids their caller sent up, those on the ids the
        # proxy had kept from its last op (`DeviceIdsWorker._ids_at_submit`)
        self.ids_kept = Dashboard.counter("ROW_IDS_KEPT")
        # an in-process device-path caller sends its ids up itself, at
        # submit (`launch_ids`), where the launch would otherwise wait for
        # them to land: a table on one device. On a mesh it does not wait
        # (the routed ids' `on_first` and a launch call on several devices
        # outlast the landing: `launch_to_device_ms` 0.37 either way in
        # `emb128x4.bulk-rows`, where the move cost 0.14-0.23 ms an op,
        # PERF.md, PR 36), so the dispatcher sends them up, and the row plan
        # keeps the routed ones there (`RowPlan.launch_ids`, PR 53)
        self.ids_at_submit = bool(one_device)

    def launch_form(self, n: int, op: str, ensure_pad: bool = False,
                    rows: Optional[int] = None) -> Tuple[int, bool]:
        """``(bucket, counted)`` of the array ``launch_ids`` makes for ``n``
        ids under the same arguments: what decides it beside the ids."""
        counted = op == "add" and rows is not None and rows > n
        return self._get_bucket(rows if counted else n, ensure_pad), counted

    def _padded_ids(self, ids: np.ndarray, slots: int, offsets, pad: int,
                    last: int) -> np.ndarray:
        """A fresh int32 array of ``slots``: ``ids`` (plus ``offsets``),
        then slots aimed at ``pad``, the last of them holding ``last``."""
        n = len(ids)
        out = np.empty(slots, np.int32)
        if offsets is None:
            out[:n] = ids
        else:
            np.add(ids, offsets, out=out[:n])
        out[n:] = pad
        if slots > n:
            out[-1] = last
        return out

    def launch_ids(self, row_ids: np.ndarray, op: str,
                   ensure_pad: bool = False, offsets=None,
                   rows: Optional[int] = None) -> LaunchIds:
        """The int32 ``row_ids`` of a Get or of a device Add (``op``:
        ``get`` or ``add``) as the launch takes them, their upload begun:
        on the thread that calls, which is the dispatcher in the op's
        ``TABLE_ROW_PREP`` or, for an in-process device-path op on a table
        on one device (``ids_at_submit``), the caller at submit
        (``DeviceIdsWorker._ids_at_submit``), so that the upload rides under
        the queue wait. What goes up is a fresh array that nobody writes
        again (``async_upload``'s rule): ``row_ids`` may change as soon as
        this returns.

        The bucket is the next power of two, so jit traces are
        shape-stable. The ids go up padded to it with sentinel-aimed
        slots, a Get's as an Add's: ONE form a bucket, so that the proxy
        that keeps the last array it sent up can hand a Get the ids of the
        Add before it and an Add the ids of its Get (a put costs by the
        call, not the byte). The Get's program takes the slots it gathers,
        ``live_slots`` of them, as a static slice: the rest of the bucket
        is filled on the device, not fetched.

        ``offsets`` (an int32 or an int32 array an id) are added to the
        ids as they are written into the array that goes up: a table
        group's bases (``tables/group_table.py``), in the one pass.

        ``rows``: the rows of a device Add's delta. Where they outnumber
        the ids (``MatrixServer._process_add_device``) the bucket holds the
        delta's row groups, and its last slot, which then names no row,
        holds the count of ids (``pallas_rows.scatter_add_rows``,
        ``tail_count``; ``LaunchIds.counted``). A Get that gathers less
        than the bucket never reads that slot."""
        n = len(row_ids)
        bucket, counted = self.launch_form(n, op, ensure_pad, rows)
        ids = self._padded_ids(row_ids, bucket, offsets, self._pad_id,
                               n if counted else self._pad_id)
        return LaunchIds(async_upload(ids), bucket, None, 0, ids.nbytes,
                         ids[:n], counted)


class DeviceIdsWorker:
    """A worker proxy's half. ``self._server_table`` is the table it
    holds (a ``DeviceIdsServer``)."""

    # the last device-path op's ids as they went up (`_ids_at_submit`)
    _kept: Optional[KeptIds] = None

    def _ids_at_submit(self, ids: np.ndarray, op: str,
                       rows: Optional[int] = None) -> np.ndarray:
        """A device-path op's ids for its request. Where the table says so
        (``DeviceIdsServer.ids_at_submit``: a table on one device) they
        carry themselves as the launch takes them (``SentIds``): made by the
        table this proxy holds and sent up from the caller's thread,
        inside the op's WORKER_SUBMIT, so that the upload is in flight
        while the message waits for the dispatcher, which launches on ids
        already on their way (``DeviceIdsServer.launch_ids``). Elsewhere the
        ids go as they came and the dispatcher sends them up. ``rows``: of
        an Add, its delta's.

        The proxy keeps what its last such op sent up (``KeptIds``: depth
        one), and an op that names the same rows launches on it: a
        trainer's push names the rows of its pull. Nothing is filled, put
        or waited for; the op's WORKER_ROW_IDS record has ``bytes`` 0 and
        ``ROW_IDS_KEPT`` counts it. The same rows: ``ids`` equal, element
        for element, to the proxy's private copy of what the last op named
        (never the caller's array: that may have been overwritten in
        place), in an array of the same form (``launch_form``). Any other
        op is a miss and replaces what is kept. Two threads on one proxy
        read and write the one attribute: whichever array a thread takes
        holds the ids it compared, and a miss is always right."""
        server = self._server_table
        if not server.ids_at_submit:
            return self._table_ids(ids)
        with span("WORKER_ROW_IDS") as up:
            up.n = len(ids)
            kept = self._kept
            form = server.launch_form(len(ids), op, op == "get", rows)
            if kept is not None and kept.serves(ids, op, form) \
                    and self._names_kept(ids, kept):
                took = kept.took
                server.ids_kept.add()
            else:
                offsets = self._ids_offsets(ids)
                took = server.launch_ids(ids, op, ensure_pad=op == "get",
                                         offsets=offsets, rows=rows)
                up.bytes = took.nbytes
                # what went up is what was named where no base was added
                self._kept = KeptIds(took, took.host if offsets is None
                                     else self._named(ids))
            # the ids as they went up, not the caller's array
            sent = took.host.view(SentIds)
            sent.took = took
            return sent

    def _named(self, ids: np.ndarray) -> np.ndarray:
        """``ids`` as the caller named them, for ``KeptIds``: a copy
        nobody else holds."""
        return np.array(ids)

    def _names_kept(self, ids: np.ndarray, kept: "KeptIds") -> bool:
        """Whether ``ids`` name what the kept op named, element for
        element (0.03 ms for 100,000 ids)."""
        return np.array_equal(ids, kept.named)

    def _ids_offsets(self, ids: np.ndarray):
        """What turns this proxy's ``ids`` into its server table's, for
        ``launch_ids``: nothing for a table's own proxy; a table group's
        proxies check their ids against their members' ends here and give
        their bases (``tables/group_table.py``)."""
        return None

    def _table_ids(self, ids: np.ndarray) -> np.ndarray:
        """``ids`` (int32, checked) as a request carries them where the
        dispatcher sends them up: the server table's own ids."""
        offsets = self._ids_offsets(ids)
        return ids if offsets is None else ids + offsets
