"""Table layer contracts: WorkerTable (client proxy) and ServerTable (state).

Reference capability (not copied): ``WorkerTable`` client bookkeeping —
per-request waiter with expected-reply count, msg-id allocation, sync
wrappers ``Get/Add = Wait(XxxAsync(...))`` — and the abstract
``ServerTable::ProcessAdd/ProcessGet`` + ``Serializable::Store/Load``
checkpoint hooks (``include/multiverso/table_interface.h:24-75``,
``src/table.cpp``), with ``table_factory::CreateTable`` wiring the pair
(``include/multiverso/table_factory.h:16-26``).

TPU-native re-design: there is no Partition step on the client — sharding is
the server state's ``NamedSharding`` and XLA owns the partitioning. The async
handle (msg_id → Completion) and the sync-wrapper shape are preserved so
callers written against the reference's API port 1:1.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional

import numpy as np

from multiverso_tpu import log
from multiverso_tpu.dashboard import Dashboard, monitor, span
from multiverso_tpu.runtime.message import (Message, MsgType,
                                            PendingHostRead, next_msg_id)
from multiverso_tpu.runtime.zoo import Zoo
from multiverso_tpu.utils import Waiter


class RowOccurrences:
    """Which entries of ``ids`` name a row an earlier entry named, from one
    stable sort: ``order`` sorts the ids and keeps arrival order within an
    id, ``new`` marks (in sorted order) the first entry of each distinct
    id, ``n`` counts them."""

    __slots__ = ("order", "new", "n")

    def __init__(self, ids: np.ndarray) -> None:
        self.order = np.argsort(ids, kind="stable")
        sorted_ids = ids[self.order]
        self.new = np.empty(len(ids), dtype=bool)
        self.new[:1] = True
        np.not_equal(sorted_ids[1:], sorted_ids[:-1], out=self.new[1:])
        self.n = int(np.count_nonzero(self.new))


# occurrence ranks that `sum_duplicate_rows` adds one indexed add a rank; a
# fused group is 16 requests of distinct ids at most (`apply_batch_rows`
# over a remote op's 1,024 rows), so its ranks end below this
_VECTOR_RANKS = 16


def sum_duplicate_rows(ids: np.ndarray, pieces,
                       found: Optional[RowOccurrences],
                       out: np.ndarray) -> np.ndarray:
    """The rows of ``pieces`` (arrays of value rows whose lengths add up
    to ``len(ids)``, ``ids`` naming them in the same order) with every
    row an earlier one's id names summed into it, written once into
    ``out[:found.n]``; returns the distinct ids, each at its first
    entry's place in arrival order. Where nothing is to be summed (the
    ids are distinct, or ``found`` is None: the caller sums elsewhere)
    the pieces are copied one after the other and ``ids`` come back.

    One pass and no loop over rows: a piece's first-named rows are
    compressed straight into their place in ``out`` (a piece that brings
    no repeat is one slice copy); the repeats (a tenth of a fused Add's
    rows) are set aside in arrival order and added occurrence rank by
    occurrence rank, the second entry of every id in one indexed add, then
    the third: a row's sum is ``((first + second) + third)...`` in arrival
    order, the bits of ``values[entries].sum(axis=0)``. An id that recurs
    more than ``_VECTOR_RANKS`` times in one Add (a block's commonest
    word) has the rest of its entries summed onto that in one ``sum``, id
    by id: one such id in eighteen rows at most, the same bits. On the chip's
    host, three requests of 1,024 Zipf rows (277 repeats) with their
    uploads: 1.2-1.3 ms, against 3.8-4.0 for what this replaced, a
    concatenation, a Python loop over the repeated rows and a fresh
    zero-padded bucket (my chip run, PR 31; PERF.md)."""
    total = len(ids)
    if found is None or found.n == total:
        row = 0
        for piece in pieces:
            out[row:row + len(piece)] = piece
            row += len(piece)
        return ids
    order, new = found.order, found.new
    first_at = order[new]            # entry of each id's first naming
    keep = np.zeros(total, dtype=bool)
    keep[first_at] = True
    out_row = np.cumsum(keep) - 1    # an entry that is kept -> its row
    later = np.empty((total - found.n,) + out.shape[1:], out.dtype)
    lo = row = set_aside = 0
    for piece in pieces:
        hi = lo + len(piece)
        kept = int(np.count_nonzero(keep[lo:hi]))
        if kept == len(piece):
            out[row:row + kept] = piece
        else:
            # "clip": under the default ("raise") `take` fills a copy of
            # `out` and copies it back; these indices cannot be out of range
            np.take(piece, np.flatnonzero(keep[lo:hi]), axis=0,
                    out=out[row:row + kept], mode="clip")
            np.take(piece, np.flatnonzero(~keep[lo:hi]), axis=0,
                    out=later[set_aside:set_aside + len(piece) - kept],
                    mode="clip")
            set_aside += len(piece) - kept
        lo, row = hi, row + kept
    repeat = np.flatnonzero(~new)    # sorted places of the repeats
    group = (np.cumsum(new) - 1)[repeat]
    rank = repeat - np.flatnonzero(new)[group]
    entry = order[repeat]
    target = out_row[first_at][group]
    source = entry - out_row[entry] - 1   # its place among the set aside
    ranks = int(rank.max())
    for r in range(1, min(ranks, _VECTOR_RANKS) + 1):
        sel = rank == r
        out[target[sel]] += later[source[sel]]
    if ranks > _VECTOR_RANKS:
        # what is left lies id by id, each id's entries in arrival order
        rest = np.flatnonzero(rank > _VECTOR_RANKS)
        cuts = np.flatnonzero(np.diff(group[rest])) + 1
        for lo, hi in zip([0, *cuts], [*cuts, len(rest)]):
            row = target[rest[lo]]
            rows = np.empty((hi - lo + 1,) + out.shape[1:], out.dtype)
            rows[0] = out[row]
            np.take(later, source[rest[lo:hi]], axis=0, out=rows[1:],
                    mode="clip")
            out[row] = rows.sum(axis=0)
    return ids[keep]


def merge_duplicate_rows(ids: np.ndarray, values: np.ndarray):
    """Pre-aggregate duplicate row ids so every touched row's error-
    feedback residual is read and written exactly once — duplicates would
    otherwise share one residual read and last-write the update,
    permanently losing part of the feedback. Shared by the per-proxy EF
    path and the shard router's per-shard EF path; a table's own Adds take
    the same pass (``sum_duplicate_rows``) into the array they upload.
    Ids that are already distinct come back as they were given, with
    their values; otherwise the distinct ids in the order of their first
    naming, and a fresh array of their summed rows."""
    id_arr = np.asarray(ids)
    found = RowOccurrences(id_arr)
    if found.n == len(id_arr):
        return ids, values
    values = np.asarray(values)
    merged = np.empty((found.n,) + values.shape[1:], values.dtype)
    return sum_duplicate_rows(id_arr, [values], found, merged), merged


class Completion:
    """One outstanding request: a waiter plus its result slot.
    ``done_ns`` is ``time.perf_counter_ns()`` at ``done`` while the op
    trace is on (else 0), and ``wake_ns`` how long after it the thread
    that slept in ``wait`` ran again: ``WorkerTable.wait`` reports it.

    A keyed host Get is done with its ``PendingHostRead``
    (``takes_pending``): the waiter's own thread fetches the rows in
    ``wait``, not the dispatcher, which has gone on to the next
    message.

    ``ordinal``: the table's Add ordinal the op was stamped with, where
    the table orders its Adds (``ServerTable.orders_adds``; the async
    dispatcher writes it before ``done``, a remote client's pump from the
    reply), else None.

    ``client_op``: the record of the op's client half, where a remote
    client keeps one (``RemoteChannel.submit`` sets it), else None:
    ``wait`` stamps the waiter's ``woken`` on it."""

    __slots__ = ("_waiter", "result", "error", "done_ns", "wake_ns",
                 "ordinal", "client_op")
    takes_pending = takes_ordinal = True

    def __init__(self) -> None:
        self._waiter = Waiter(1)
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self.done_ns = self.wake_ns = 0
        self.ordinal: Optional[int] = None
        self.client_op: Any = None

    def done(self, result: Any) -> None:
        self.result = result
        if Dashboard.profile_annotations:
            self.done_ns = time.perf_counter_ns()
        self._waiter.notify()

    def fail(self, error: BaseException) -> None:
        self.error = error
        self._waiter.notify()

    def wait(self, timeout: Optional[float] = None) -> Any:
        if not self._waiter.wait(timeout):
            raise TimeoutError("table request timed out")
        if self.done_ns:
            self.wake_ns = time.perf_counter_ns() - self.done_ns
        if self.error is not None:
            raise self.error  # a failed op's record keeps ``woken`` 0
        if self.client_op is not None:
            self.client_op.woken = time.perf_counter_ns()
        # kept: a second wait finds the rows
        self.result = PendingHostRead.fetched(self.result)
        return self.result


class LocalChannel:
    """Default request channel: the in-process dispatcher queue (workers and
    server shards share the mesh — no wire). The remote equivalent lives in
    :mod:`multiverso_tpu.runtime.remote`."""

    def __init__(self) -> None:
        self._zoo = Zoo.instance()

    def worker_id(self) -> int:
        return self._zoo.current_worker_id()

    def submit(self, table_id: int, msg_type: MsgType, request: Any,
               msg_id: int, completion: "Completion") -> None:
        msg = Message(src=self.worker_id(), dst=-1, type=msg_type,
                      table_id=table_id, msg_id=msg_id,
                      data=[request, completion])
        self._zoo.server.send(msg)

    def post(self, table_id: int, msg_type: MsgType) -> None:
        """Fire-and-forget control message (Server_Finish_Train)."""
        msg = Message(src=self.worker_id(), dst=-1, type=msg_type,
                      table_id=table_id, msg_id=next_msg_id())
        self._zoo.server.send(msg)


# what ``WorkerTable._public_op`` hands out where no record is kept
_NOT_RECORDED = contextlib.nullcontext()


class WorkerTable:
    """Client proxy: issues Get/Add messages, tracks outstanding replies."""

    def __init__(self, channel: Optional[Any] = None) -> None:
        self.table_id: int = -1
        self._channel = channel if channel is not None else LocalChannel()
        self._zoo = Zoo.instance() if channel is None else None
        self._pending: Dict[int, Completion] = {}
        self._pending_request: Dict[int, Any] = {}
        self._lock = threading.Lock()
        # a remote client's channel records the client's half of an op
        # while its server, or its own process, traces (runtime/remote.py)
        self._begin_op = getattr(self._channel, "begin_op", None)

    def _public_op(self):
        """What a public op runs inside, from its first line to its
        return: the record of the op's client half (its ``call`` stamped
        here, its ``ret`` where the ``with`` ends), where the channel keeps
        one and no op that encloses this one on the calling thread has
        begun it; else a context that does nothing. An async op's record
        stays open past the ``with`` and ends where its ``wait`` returns."""
        op = self._begin_op() if self._begin_op is not None else None
        return _NOT_RECORDED if op is None else op

    # -- wiring ------------------------------------------------------------
    def _register(self, server_table: "ServerTable") -> None:
        self.table_id = self._zoo.register_table(self, server_table)
        server_table.table_id = self.table_id

    # -- async machinery ---------------------------------------------------
    def _submit(self, msg_type: MsgType, request: Any,
                submit: Any = None) -> int:
        """Hand one request to the channel, inside a ``WORKER_SUBMIT``
        section: the caller's own (``submit``) where a public call opened
        one around its argument work, else one opened here. It ends when
        the channel has the message (in process: when ``Server.send`` has
        returned), under the ``msg_id`` given here."""
        if submit is not None:
            return self._enqueue(msg_type, request, submit)
        with span("WORKER_SUBMIT") as submit:
            return self._enqueue(msg_type, request, submit)

    def _enqueue(self, msg_type: MsgType, request: Any, submit: Any) -> int:
        msg_id = submit.op = next_msg_id()
        completion = Completion()
        with self._lock:
            self._pending[msg_id] = completion
            self._pending_request[msg_id] = request
        self._channel.submit(self.table_id, msg_type, request, msg_id,
                             completion)
        return msg_id

    def get_async(self, request: Any) -> int:
        with self._public_op():
            return self._submit(MsgType.Request_Get, request)

    def add_async(self, request: Any) -> int:
        with self._public_op():
            return self._submit(MsgType.Request_Add, request)

    def wait(self, msg_id: int) -> Any:
        with self._lock:
            completion = self._pending.pop(msg_id, None)
            request = self._pending_request.pop(msg_id, None)
        if completion is None:
            log.fatal("wait: unknown msg_id %d on table %d", msg_id, self.table_id)
        try:
            with span("WORKER_WAIT", op=msg_id) as waited:
                raw = completion.wait()
                if waited.id and completion.done_ns > waited.start_ns:
                    # n: how long after done() the thread that slept here
                    # ran again (0 where the result was there before the
                    # wait); a host Get's fetch, made in the wait, is not
                    # in it
                    waited.n = completion.wake_ns
            if raw is None:
                return None
            return self.process_reply_get(raw, request)
        finally:
            op = completion.client_op
            if op is not None and not op.open:
                op.close()  # an async op: it ends where its wait returns

    def process_reply_get(self, raw: Any, request: Any) -> Any:
        """Post-process a Get reply (reference: ``ProcessReplyGet`` writes
        into user buffers). Default: identity."""
        return raw

    def _require_device_io(self) -> None:
        """Guard for device-array-exchanging entry points: in-process
        proxies only — multihost lockstep descriptors and remote wire
        requests must be host-serializable."""
        if not getattr(self, "supports_device_io", False):
            log.fatal("device IO is in-process only (multihost/remote "
                      "proxies take the host paths)")

    # -- sync wrappers (Get/Add = Wait(Async)) ------------------------------
    # NOTE: these call _submit directly (not self.get_async) so subclasses can
    # override the async methods with their own signatures safely.
    def get(self, request: Any) -> Any:
        with self._public_op(), monitor("WORKER_TABLE_SYNC_GET"):
            return self.wait(self._submit(MsgType.Request_Get, request))

    def add(self, request: Any) -> Any:
        with self._public_op(), monitor("WORKER_TABLE_SYNC_ADD"):
            return self.wait(self._submit(MsgType.Request_Add, request))

    def query(self, vecs: Any, k: int, metric: str = "dot") -> Any:
        """Server-side top-k retrieval pushdown: score every row of the
        table against ``vecs`` ((n_q, dim) float32) under ``metric``
        (``dot`` | ``cosine``) and return ``(ids, scores)`` — each
        (n_q, k') with k' = min(k, rows), ranked score-descending with
        ties broken toward the lower global id. Slot-free on the server
        (never clocked, never WAL'd) and replica-servable, so results
        may trail the primary by the read tier's staleness budget.

        Bypasses wait()/process_reply_get: the reply is already the
        final (ids, scores) pair — per-kind Get post-processing (e.g.
        MatrixWorker's buffer fill) must not touch it."""
        from multiverso_tpu.query.engine import check_request
        with self._public_op():
            request = check_request((vecs, k, metric))
            with monitor("WORKER_TABLE_SYNC_QUERY"):
                completion = Completion()
                self._channel.submit(self.table_id, MsgType.Request_Query,
                                     request, next_msg_id(), completion)
                return completion.wait()

    def finish_train(self) -> None:
        """Signal end-of-training so BSP clocks release peers
        (reference: ``Server_Finish_Train``)."""
        self._channel.post(self.table_id, MsgType.Server_Finish_Train)


class ServerTable:
    """Device-resident table shard set + checkpoint hooks."""

    def _unwrapped(self):
        """This server table with any lockstep wrapper peeled off (a
        named transaction's secondary tables are state holders, not
        dispatch points — the PRIMARY table's descriptor already covers
        the op; see MatrixServer._resolve_named). On any real table this
        is the identity; the multihost LockstepTable forwards it to its
        inner table via __getattr__."""
        return self

    def __init__(self) -> None:
        self.table_id: int = -1
        # Global position of this table's first row/element/key when it is
        # one shard of a range-partitioned table (shard/partition.py): the
        # member serves SHARD-LOCAL ids in [0, local size) — the router
        # translates — and advertises the offset in its remote directory
        # so clients and operators can see which span this member owns.
        # 0 = unsharded (or the first shard).
        self.row_offset: int = 0
        self._replicate = None  # lazy replicate-jit for multihost host reads
        # (scalars tuple, worker) -> device constants, LRU-bounded. A
        # repeated AddOption envelope (fixed-lr hot paths) hits the cache
        # and skips two host->device transfers per add; a churning
        # envelope (per-block lr decay) misses but cannot pin more than
        # _OPT_CACHE_MAX dead device buffers. Locked: the dispatcher
        # thread (process_add) and worker threads (the word2vec txn path)
        # both call _option_consts, and a concurrent move_to_end on a key
        # being popitem'd can raise KeyError.
        self._opt_cache: "OrderedDict" = OrderedDict()
        self._opt_cache_lock = threading.Lock()

    _OPT_CACHE_MAX = 256

    # True on a table whose Adds do not commute (each reads the state the
    # one before it left: an optimizer step), so that their order is part
    # of the result: its dispatcher never merges them, and the async
    # server stamps every reply to an op on it with the table's Add
    # ordinal (``Server._stamp``). A table with a row plan says what its
    # plan says (``DeviceIdsServer.orders_adds``).
    orders_adds = False

    def _option_consts(self, option):
        """Device constants (worker index, scalars envelope) for an
        AddOption, cached so identical envelopes upload once. Requires
        ``self.num_workers``."""
        import jax.numpy as jnp
        key = (option.scalars(), int(option.worker_id))
        with self._opt_cache_lock:
            cached = self._opt_cache.get(key)
            if cached is not None:
                self._opt_cache.move_to_end(key)
                return cached
        # build device constants OUTSIDE the lock (host->device upload);
        # a racing duplicate insert is harmless — last writer wins
        scalars = jnp.asarray(option.scalars(), dtype=jnp.float32)
        worker = jnp.int32(max(option.worker_id, 0)
                           % max(1, self.num_workers))
        cached = (worker, scalars)
        with self._opt_cache_lock:
            self._opt_cache[key] = cached
            if len(self._opt_cache) > self._OPT_CACHE_MAX:
                self._opt_cache.popitem(last=False)
        return cached

    def remote_spec(self) -> Optional[Dict[str, Any]]:
        """Metadata a remote client needs to build a matching worker proxy
        (kind + shape + dtype); None = not servable over the wire."""
        return None

    def _host_read(self, arr) -> Any:
        """Device->host read of table state. Under a multi-process mesh the
        array is globally sharded and not fully addressable from one
        controller, so route through a replicating jit first (an XLA
        allgather — collective, which is safe there because under
        ``multihost`` every host-read site runs on the lockstep
        dispatcher/replay thread: ``_host_read_behind`` hands nothing
        over). Single-process meshes skip straight to ``device_get``, on
        the dispatcher for a whole-table or sparse read and a checkpoint,
        and for a keyed Get on whichever thread resolves its
        ``PendingHostRead``: the reply's finishing thread or the
        in-process waiter's own."""
        import jax
        import numpy as np
        from multiverso_tpu.runtime.zoo import Zoo
        if Zoo.instance().multihost is not None:
            if self._replicate is None:
                from jax.sharding import NamedSharding, PartitionSpec
                self._replicate = jax.jit(
                    lambda x: x,
                    out_shardings=NamedSharding(self.mesh,
                                                PartitionSpec()))
            arr = self._replicate(arr)
        with span("TABLE_HOST_READ", cpu=True) as read:
            out = np.asarray(jax.device_get(arr))
            read.n = out.nbytes
        return out

    def _host_read_behind(self, arr, index) -> Any:
        """A keyed Get's ``_host_read(arr)[index]``, left for whoever
        finishes the Get: the copy to the host is started here, on the
        dispatcher at launch, and a ``PendingHostRead`` returned. Under a
        multi-process mesh the read is a collective and is made here."""
        if Zoo.instance().multihost is not None:
            return self._host_read(arr)[index]
        arr.copy_to_host_async()
        return PendingHostRead(self._host_read, arr, index)

    def merge_add_requests(self, requests):
        """Fuse a PREFIX of a drained group of Add requests into ONE
        request (the dispatcher's micro-batch path, runtime/server.py):
        return ``(merged_request, rows, consumed)`` — where
        ``process_add(merged)`` is equivalent to applying the first
        ``consumed`` requests in turn (up to the commutative-Add
        reordering Downpour tolerates) and ``rows`` feeds the
        APPLY_BATCH_ROWS histogram — or None when even the first request
        cannot merge (the dispatcher then applies per message, exactly as
        before). Consuming a prefix lets a table bound the fused-apply
        size (e.g. the matrix row cap) without giving up batching for
        the remainder.

        Contract: MUST NOT mutate table state; the eventual
        ``process_add(merged)`` must validate before it mutates, so a
        raised error means nothing applied (the dispatcher retries the
        group per message). Default: no batching."""
        return None

    def process_add(self, request: Any) -> None:
        raise NotImplementedError

    def process_get(self, request: Any) -> Any:
        raise NotImplementedError

    def launch_get(self, request: Any) -> Any:
        """``process_get`` as a dispatcher calls it: the same result,
        except that a table kind whose keyed host Get can be fetched
        behind the dispatcher returns a ``PendingHostRead`` for one (and
        resolves it in its own ``process_get``)."""
        return self.process_get(request)

    # Serializable (checkpoint) hooks
    def store(self, stream) -> None:
        raise NotImplementedError

    def load(self, stream) -> None:
        raise NotImplementedError

    # -- live migration hooks (shard/reshard.py) ----------------------------
    # Raw-value slice transfer for key-range migration: extract hands the
    # coordinator the CURRENT values of a shard-local id range (no updater
    # involvement, mirrors store()); absorb installs values at a range on
    # the recipient, bypassing updaters entirely — a migrated value is
    # state, not a gradient. Only range-partitionable kinds implement
    # these; the migration planner refuses the rest before ever calling.
    def extract_range(self, lo: int, hi: int) -> Any:
        log.fatal("live migration is unsupported for %s (no extract_range)",
                  type(self).__name__)

    def absorb_range(self, start: int, values: Any) -> None:
        log.fatal("live migration is unsupported for %s (no absorb_range)",
                  type(self).__name__)
