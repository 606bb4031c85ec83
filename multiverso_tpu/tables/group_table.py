"""Table group: N matrix tables of one width, dtype and updater held as ONE
``MatrixServer`` slab, and one Get and one Add for the rows of all of them.

The deployment it is for holds many embedding tables of one width (a
recommendation model has one a categorical feature: the MLPerf DLRM 26, of 3
to 40,000,000 rows) and a training step names rows in all of them. As N
matrix tables that is 2N ops a step, each paying the host and runtime path
around its device work; the path is per op, not per row. Production
libraries hold such tables in one buffer with a base offset a table and take
a step's ids for all tables in one jagged call (FBGEMM's table-batched
embedding; torchrec's ``KeyedJaggedTensor``: ids concatenated, one length a
table). That is the form here.

Layout. Member ``i`` has ``num_rows[i]`` rows and lives at rows
``[bases[i], bases[i + 1])`` of the slab, members in order, nothing between
them; the slab's own scratch rows (the sentinel) come after the last member.
The slab is a matrix table to everything below the proxies: a group op is
ONE message, one ``TABLE_PROCESS_*``, one ``TABLE_ROW_LAUNCH`` of the row
scatter-add or gather every matrix table shares. With bases, ids of
different members never collide, so the kernel's distinct-id contract holds
across the op wherever it holds within each segment.

The group op (``MatrixGroupWorker``). Ids are each member's OWN ids,
concatenated in member order, with one length a member (``(ids, lengths)``,
or a list of one id array a member). A Get returns the rows in that order
and the segments' offsets; an Add takes one delta in the same order, of
``(n, cols)`` or, as a trainer holds it, of more rows than ids: every step
names another count of rows, a member has no sentinel for the caller to aim
pad ids at, and a device Add's program is keyed by its delta's shape. So
the delta keeps ONE shape (the ``(bucket, lanes)`` of the device Get whose
gradient it is), the count of ids rides up in the last slot of the id array
and the kernel walks the rows named: one program for every count, nothing
applied from the delta's tail. On the caller's thread, inside the op's ``WORKER_GROUP_IDS``, every
segment is checked against ITS member's ``num_row`` (an id past a member's
end is refused, it never lands in the next member's rows), and the bases are
added as the ids are written into the array that goes up
(``MatrixServer.launch_ids``): one pass, one upload. An op names fewer than
``GROUP_OP_ROWS`` rows (the ids of one launch are a scalar prefetch).

A member (``group.tables[i]``, ``GroupMember``) stays a table: its own Get
and Add by its own ids on the host and on the device path, a whole-table Get
(a row range of the slab), ``num_row``. What a member may not do: name a row
outside ``[0, num_row)`` on any path (a matrix table's device path lets pad
slots aim at ``num_row``, its sentinel; a member's ``num_row`` is the next
member's first row, so pads are refused; a member's device delta may be
longer than its ids, like the group's), a whole-table Add, a fused
transaction. Every member op is an op on the slab's table id: the servers'
clocks see one table.

Not served, refused by name at ``create_table``: a stateful updater (the
slab's state would be one accumulator across members: not wrong for a
row-state rule, not tested, ROADMAP Queue 2 item 10), ``is_sparse``, a mesh
over several processes. A table-serving process lists the group in its
directory as kind ``matrix_group``, which a remote client refuses by name.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import numpy as np

from multiverso_tpu import log
from multiverso_tpu.dashboard import Dashboard, span
from multiverso_tpu.runtime.message import MsgType
from multiverso_tpu.runtime.zoo import Zoo
from multiverso_tpu.tables.matrix_table import MatrixServer, MatrixWorker
from multiverso_tpu.updaters import (AddOption, GetOption, SGDUpdater,
                                     Updater, get_updater)

# a group op names fewer rows than this: its ids are one launch's scalar
# prefetch, and an op of this many takes a 262,144-slot bucket, which the
# row kernel's SMEM does not hold (ROADMAP Queue 2 item 2)
GROUP_OP_ROWS = 131072


class RowRange:
    """Rows ``[lo, hi)`` of the slab where a request carries ids: a
    member's whole-table Get."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo: int, hi: int) -> None:
        self.lo, self.hi = lo, hi

    def __len__(self) -> int:
        return self.hi - self.lo


class _Segments(np.ndarray):
    """The int32 ids of a group op, each member's own, with the length of
    every member's segment and the segments' offsets: what
    ``MatrixGroupWorker._ids_offsets`` reads."""

    lengths: Optional[np.ndarray] = None
    offsets: Optional[np.ndarray] = None


class MatrixGroupServer(MatrixServer):
    """The slab: a matrix table of ``sum(num_rows)`` rows that knows where
    its members lie."""

    def __init__(self, num_rows: Sequence[int], num_col: int,
                 dtype: Any = np.float32, updater_type: str = "",
                 init_values: Optional[Sequence[Any]] = None) -> None:
        self.member_rows = np.asarray(num_rows, np.int64).reshape(-1)
        if not len(self.member_rows) or (self.member_rows < 1).any():
            log.fatal("matrix_group: every member needs a row, got "
                      "num_rows %s", list(num_rows))
        self.bases = np.concatenate([[0], np.cumsum(self.member_rows)])
        if self.bases[-1] >= 2 ** 31 - 1:
            log.fatal("matrix_group: %d rows in all; row ids are int32",
                      self.bases[-1])
        if Zoo.instance().multihost is not None:
            log.fatal("matrix_group is not served on a mesh over several "
                      "processes (ROADMAP Queue 2 item 10)")
        updater = get_updater(np.dtype(dtype), updater_type)
        if type(updater) not in (Updater, SGDUpdater):
            log.fatal("matrix_group is not served under the stateful "
                      "updater %r: default and sgd only (ROADMAP Queue 2 "
                      "item 10)", updater.name)
        super().__init__(int(self.bases[-1]), num_col, dtype, updater_type,
                         init_value=self._slab_source(
                             init_values, num_col, np.dtype(dtype)))

    def _slab_source(self, init_values, num_col: int, dtype: np.dtype):
        """The slab's block source, ``(lo, n) -> rows [lo, lo + n)``, from
        one source a member: an array of the member's rows, a block source
        over the member's own rows, or None for zeros. A block that lies in
        one member is that member's own array or view."""
        if init_values is None:
            return None
        sources = list(init_values)
        if len(sources) != len(self.member_rows):
            log.fatal("matrix_group: %d init_values for %d members",
                      len(sources), len(self.member_rows))
        for i, source in enumerate(sources):
            if source is not None and not callable(source):
                sources[i] = np.asarray(source)
                if sources[i].shape != (self.member_rows[i], num_col):
                    log.fatal("matrix_group: init_values[%d] is %s, member "
                              "%d is (%d, %d)", i, sources[i].shape, i,
                              self.member_rows[i], num_col)

        def of_member(i: int, lo: int, n: int) -> np.ndarray:
            source = sources[i]
            if source is None:
                return np.zeros((n, num_col), dtype)
            return source(lo, n) if callable(source) else source[lo:lo + n]

        def rows(lo: int, n: int) -> np.ndarray:
            i = int(np.searchsorted(self.bases, lo, side="right")) - 1
            parts = []
            while n:
                take = min(n, int(self.bases[i + 1]) - lo)
                parts.append(of_member(i, lo - int(self.bases[i]), take))
                lo, n, i = lo + take, n - take, i + 1
            return parts[0] if len(parts) == 1 else np.concatenate(parts)

        return rows

    def _process_get(self, request):
        if isinstance(request[0], RowRange):
            whole = request[0]
            return self._host_read(self.updater.access(
                self.data[whole.lo:whole.hi]))[:, : self.num_col]
        return super()._process_get(request)

    def remote_spec(self):
        return {"kind": "matrix_group", "num_rows": self.member_rows.tolist(),
                "num_col": self.num_col, "dtype": self.dtype.str}

    # -- checkpoint: the members' row counts, then the slab's file ----------
    def store(self, stream) -> None:
        from multiverso_tpu.checkpoint import write_array
        write_array(stream, self.member_rows)
        super().store(stream)

    def load(self, stream) -> None:
        from multiverso_tpu.checkpoint import read_array
        stored = read_array(stream)
        if stored.shape != self.member_rows.shape \
                or (stored != self.member_rows).any():
            log.fatal("matrix_group: the checkpoint holds members of %s "
                      "rows, this group's have %s: a slab loads under the "
                      "layout it was stored under", stored.tolist(),
                      self.member_rows.tolist())
        super().load(stream)


class GroupMember(MatrixWorker):
    """Member ``index`` of a group: a matrix table's proxy over the slab's
    rows ``[base, base + num_row)``, by its own ids."""

    def __init__(self, group: "MatrixGroupWorker", index: int) -> None:
        server = group._server_table
        self.index = index
        self.base = np.int32(server.bases[index])
        self._member_ops = Dashboard.counter("GROUP_MEMBER_OPS")
        super().__init__(int(server.member_rows[index]), server.num_col,
                         server.dtype, server=server)

    def _register(self, server_table) -> None:
        # an op of a member is an op on the slab's table
        self.table_id = server_table.table_id

    def _enqueue(self, msg_type, request, submit):
        self._member_ops.add()
        return super()._enqueue(msg_type, request, submit)

    def _check_range(self, ids: np.ndarray) -> None:
        if ids.size and (ids.min() < 0 or ids.max() >= self.num_row):
            log.fatal("matrix_group: member %d row id out of range [0, %d): "
                      "a member's op names its own rows only", self.index,
                      self.num_row)

    def _ids_offsets(self, ids: np.ndarray):
        # on every path, the device path's included: ``num_row`` is no
        # sentinel here, it is the next member's first row
        self._check_range(ids)
        return self.base

    def _norm_ids(self, row_ids):
        if row_ids is None:
            return RowRange(int(self.base), int(self.base) + self.num_row)
        return super()._norm_ids(row_ids)

    def add(self, values, row_ids=None, option=None) -> None:
        self._refuse_whole_add(row_ids)
        super().add(values, row_ids, option)

    def add_async(self, values, row_ids=None, option=None) -> int:
        self._refuse_whole_add(row_ids)
        return super().add_async(values, row_ids, option)

    def _refuse_whole_add(self, row_ids) -> None:
        if row_ids is None:
            log.fatal("matrix_group: a member's whole-table Add is not "
                      "served: name the rows (member %d)", self.index)

    def transact_device_async(self, *args, **kwargs):
        log.fatal("matrix_group: a fused transaction over a member is not "
                  "served (its buffer is the whole slab)")

    @property
    def sentinel_row(self) -> int:
        log.fatal("matrix_group: a member has no sentinel row (member %d's "
                  "num_row is the next member's first row)", self.index)


class MatrixGroupWorker(MatrixWorker):
    """The group's proxy: ``tables[i]`` is member ``i``; ``get`` / ``add``
    (numpy) and ``get_device_async`` + ``wait_device`` /
    ``add_device_async`` + ``wait`` (device arrays, in-process) take the
    rows of all members in one op. See the module's docstring."""

    def __init__(self, num_rows: Sequence[int], num_col: int,
                 dtype: Any = np.float32, updater_type: str = "",
                 init_values: Optional[Sequence[Any]] = None) -> None:
        server = MatrixGroupServer(num_rows, num_col, dtype, updater_type,
                                   init_values)
        super().__init__(server.num_row, num_col, dtype, server=server)
        self.num_rows: List[int] = server.member_rows.tolist()
        self._bases = server.bases[:-1].astype(np.int32)
        self._ends = server.member_rows.astype(np.uint32)
        self._group_ops = {"get": Dashboard.counter("GROUP_OPS_GET"),
                           "add": Dashboard.counter("GROUP_OPS_ADD")}
        self._offsets_of = {}      # msg_id -> a Get's segment offsets
        self.tables = [GroupMember(self, i) for i in range(len(self._ends))]

    # -- the op's ids --------------------------------------------------------
    def _segments(self, ids, lengths) -> _Segments:
        """``(ids, lengths)``, or a list of one id array a member, as the
        op's flat int32 ids carrying their segments' lengths and offsets
        (``len(members) + 1``)."""
        if lengths is None:
            lengths = [len(part) for part in ids]
            ids = np.concatenate([np.asarray(part, np.int32).reshape(-1)
                                  for part in ids]) if len(ids) else ids
        flat = np.asarray(ids, np.int32).reshape(-1).view(_Segments)
        flat.lengths = lengths = np.asarray(lengths, np.int64).reshape(-1)
        if len(lengths) != len(self._ends) or (lengths < 0).any() \
                or int(lengths.sum()) != len(flat):
            log.fatal("matrix_group: %d ids under lengths %s; a group op "
                      "takes one length a member (%d) that add up to its "
                      "ids", len(flat), lengths.tolist(), len(self._ends))
        if len(flat) >= GROUP_OP_ROWS:
            log.fatal("matrix_group: an op of %d rows; a group op names "
                      "fewer than %d (ROADMAP Queue 2 item 2)", len(flat),
                      GROUP_OP_ROWS)
        flat.offsets = np.concatenate([[0], np.cumsum(lengths)])
        return flat

    def _ids_offsets(self, ids: _Segments):
        """Every segment against its member's end, then the base of every
        id: one ``reduceat`` over the ids (as unsigned, so that a negative
        id reads past any end) and one ``repeat`` of the bases."""
        with span("WORKER_GROUP_IDS") as checked:
            checked.n = len(ids)
            named = np.flatnonzero(ids.lengths)
            if len(named):
                top = np.maximum.reduceat(np.asarray(ids).view(np.uint32),
                                          ids.offsets[named])
                past = top >= self._ends[named]
                if past.any():
                    member = int(named[np.argmax(past)])
                    log.fatal("matrix_group: member %d row id out of range "
                              "[0, %d): an id past a member's end is "
                              "refused, it never names the next member's "
                              "rows", member, self.num_rows[member])
            return np.repeat(self._bases, ids.lengths)

    def _named(self, ids: _Segments) -> _Segments:
        """What the group keeps of an op (``KeptIds.named``): the members'
        own ids and their lengths, copies both (``lengths`` may be the
        caller's array)."""
        named = super()._named(ids).view(_Segments)
        named.lengths = ids.lengths.copy()
        return named

    def _names_kept(self, ids: _Segments, kept) -> bool:
        # the same ids under other lengths are other rows of the slab
        return np.array_equal(ids.lengths, kept.named.lengths) \
            and super()._names_kept(ids, kept)

    def _send(self, op: str, ids, lengths, option, device: bool,
              values=None) -> int:
        """One group op as one message: ``op`` is ``get`` or ``add``; the
        device path's ids go up from this thread where the slab says so
        (``_ids_at_submit``), the host path's go as the slab's ids. A
        device-path op that names what the group's last one named, the
        same ids under the same lengths, launches on the array that one
        sent up and skips ``WORKER_GROUP_IDS`` with the upload: segments
        that were checked against their members' ends and given their
        bases once are the same segments."""
        with span("WORKER_SUBMIT") as submit:
            segments = self._segments(ids, lengths)
            submit.n = len(segments)
            self._group_ops[op].add()
            # a device delta's rows (they may outnumber the ids)
            rows = values.shape[0] if device and op == "add" else None
            if rows is not None and rows > GROUP_OP_ROWS:
                log.fatal("matrix_group: a delta of %d rows; a group op's "
                          "has at most %d (ROADMAP Queue 2 item 2)", rows,
                          GROUP_OP_ROWS)
            sent = (self._ids_at_submit(segments, op, rows) if device
                    else self._table_ids(segments))
            if op == "add":
                return self._submit(
                    MsgType.Request_Add,
                    (sent, values, self._default_add_option(option)), submit)
            option, _ = self._prep_get_option(option, segments)
            msg_id = self._submit(
                MsgType.Request_Get,
                (sent, option, True) if device else (sent, option), submit)
        self._offsets_of[msg_id] = segments.offsets
        return msg_id

    # -- device path (in-process workers) -----------------------------------
    def get_device_async(self, ids, lengths=None,
                         option: Optional[GetOption] = None) -> int:
        """The rows ``ids`` name in every member, in one op that stays in
        HBM: ``wait_device`` gives ``(rows, offsets)``, ``rows`` the
        ``(bucket, padded_cols)`` array a matrix table's device Get gives
        (member ``i``'s rows at ``[offsets[i], offsets[i + 1])``, the slots
        past ``offsets[-1]`` copies of the slab's sentinel row)."""
        self._require_device_io()
        return self._send("get", ids, lengths, option, device=True)

    def wait_device(self, msg_id: int, row_ids=None):
        offsets = self._offsets_of.pop(msg_id)
        rows = self.wait(msg_id)
        self.rows_pulled += int(offsets[-1])
        return rows, offsets

    def add_device_async(self, values, ids, lengths=None,
                         option: Optional[AddOption] = None) -> int:
        """One Add for every member: ``values`` is a ``(rows, <= num_col)``
        jax.Array in the ids' order, ``len(ids) <= rows <= GROUP_OP_ROWS``;
        ids distinct within a member's segment. ``wait`` as for a matrix
        table. A step's count of rows differs from the last step's: hold
        the delta at ONE shape, the ``(bucket, lanes)`` of the device Get's
        result whose gradient it is. Its rows past the ids are not applied,
        whatever they hold, and one device program serves every count (a
        delta of exactly ``len(ids)`` rows compiles a program a count:
        ``MatrixWorker.add_device_async``)."""
        self._require_device_io()
        return self._send("add", ids, lengths, option, device=True,
                          values=values)

    # -- host path (numpy) ---------------------------------------------------
    def get_async(self, ids, lengths=None,
                  option: Optional[GetOption] = None) -> int:
        return self._send("get", ids, lengths, option, device=False)

    wait_get = wait_device

    def get(self, ids, lengths=None, option: Optional[GetOption] = None):
        """``(rows, offsets)``: the ``(len(ids), num_col)`` rows in the
        ids' order, member ``i``'s at ``[offsets[i], offsets[i + 1])``."""
        return self.wait_get(self.get_async(ids, lengths, option))

    def add_async(self, values, ids, lengths=None,
                  option: Optional[AddOption] = None) -> int:
        return self._send("add", ids, lengths, option, device=False,
                          values=values)

    def add(self, values, ids, lengths=None,
            option: Optional[AddOption] = None) -> None:
        self.wait(self.add_async(values, ids, lengths, option))

    def transact_device_async(self, *args, **kwargs):
        log.fatal("matrix_group: a fused transaction over the group is not "
                  "served: the group op is the one Get and the one Add")
