"""Server runtime: the dispatcher that owns table state and applies requests.

Reference capability (not copied): the ``Server`` actor owns the
``ServerTable`` store, applies Adds and answers Gets; the ``SyncServer``
subclass implements BSP via per-worker vector clocks and deferred-message
caches (``src/server.cpp:36-222``). Routing ran worker actor → communicator →
network → server actor.

TPU-native re-design: table state is a sharded ``jax.Array`` in HBM; "apply
an Add" is a jitted donated updater call; "answer a Get" is a device gather,
launched here in order, + a host fetch that whoever finishes the Get makes
(``complete_get``). The actor zoo collapses to ONE dispatcher thread per
process pulling typed messages from an in-process queue — the network hop no
longer exists because workers and server shards share the mesh. The BSP
contract is
preserved exactly (and tested like ``Test/unittests/test_sync.cpp``):
*every worker's i-th Get observes exactly i rounds of every worker's Adds*,
implemented with the same two-sided clock: round-(i+1) Adds are deferred
until all round-i Gets are served, round-i Gets are deferred until all
round-i Adds are applied.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Callable, Dict, List, Optional

from multiverso_tpu import config, log
from multiverso_tpu.dashboard import (RING, Dashboard, count, current_span,
                                      gauge_set, monitor, observe, span)
from multiverso_tpu.obs.profiler import clear_wait, mark_wait
from multiverso_tpu.obs.trace import flight_dump, hop
from multiverso_tpu.runtime.admission import (AdmissionGate, DeadlineExceeded,
                                              ShedError, lane_order)
from multiverso_tpu.runtime.contracts import dispatcher_only
from multiverso_tpu.runtime.message import (Message, MsgType,
                                            PendingHostRead)
from multiverso_tpu.utils import MtQueue

_apply_metrics_cache = None


def _apply_metrics():
    """Apply-path metric objects resolved once — the registry lock must
    not sit inside the dispatcher drain loop (Dashboard.reset zeroes
    objects in place, so cached references stay live). APPLY_BATCH_ROWS
    is count-valued: unit-based geometric bounds (1..2^27 rows), not the
    1µs latency default whose top edge it would overflow."""
    global _apply_metrics_cache
    if _apply_metrics_cache is None:
        from multiverso_tpu.obs.metrics import log_bounds
        _apply_metrics_cache = (
            Dashboard.counter("APPLY_FUSED_CALLS"),
            Dashboard.counter("APPLY_BATCHED_MSGS"),
            Dashboard.histogram("APPLY_BATCH_ROWS",
                                bounds=log_bounds(lowest=1.0)),
            Dashboard.gauge("SERVER_QUEUE_DEPTH"),
            Dashboard.histogram("SERVER_QUEUE_WAIT_SECONDS"),
        )
    return _apply_metrics_cache


# the interpreter probe's sleep (``Server._probe_interpreter``): fifty wakes a
# second, while the op trace is on
_PROBE_PERIOD_NS = 20_000_000


def complete_get(completion, result) -> None:
    """Complete a Get with what its table's ``launch_get`` returned, served
    at once or released from a round gate later. A keyed host Get comes
    back launched and not fetched (``PendingHostRead``), and the
    completion decides by its kind who fetches: one that says
    ``takes_pending`` (an in-process waiter, a reply framed over the wire)
    is done with the pending result and the dispatcher goes on; any other
    gets the rows, fetched here as before."""
    if not getattr(completion, "takes_pending", False):
        result = PendingHostRead.fetched(result)
    completion.done(result)


class _NullCompletion:
    """Fire-and-forget completion for internally-generated dispatcher work
    (watchdog-triggered evictions): errors are logged by the dispatcher's
    own guard, nobody waits."""

    __slots__ = ()

    def done(self, result) -> None:
        pass

    def fail(self, error: BaseException) -> None:
        pass


class _ExecWaiter:
    """Minimal completion for :meth:`Server.run_serialized` (tables.base's
    Completion would be an import cycle from here)."""

    __slots__ = ("_event", "result", "error")

    def __init__(self) -> None:
        self._event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None

    def done(self, result) -> None:
        self.result = result
        self._event.set()

    def fail(self, error: BaseException) -> None:
        self.error = error
        self._event.set()

    def wait(self, timeout: Optional[float]):
        if not self._event.wait(timeout):
            raise TimeoutError("dispatcher execution timed out (server "
                               "stopped?)")
        if self.error is not None:
            raise self.error
        return self.result


class Server:
    """Async parameter server dispatcher (reference: async ``Server``).

    One background thread applies requests in arrival order. Asynchrony is
    real: ``add_async`` returns once the message is queued; the device update
    happens on the dispatcher thread, overlapping the caller's compute.
    """

    # True on servers that defer Gets behind round clocks (BSP): fused
    # add+get replies sample the table AT APPLY TIME, which cannot honor a
    # round-gated Get contract — clients (PytreeWorkerSync) check this and
    # re-issue a properly gated Get instead of trusting the fused reply.
    gates_gets = False
    # True on servers that complete Adds at enqueue and apply later
    # (deterministic ordering): fused add+get replies are None — clients
    # should send reply-free pushes and pull separately.
    defers_adds = False
    # True on servers whose dispatcher may micro-batch queued Adds into
    # one fused table apply (the Downpour-tolerated reordering). The
    # round-gated and deterministic servers keep it False: their
    # (round, worker) ordering admits no compatible multi-message group,
    # so they apply per message exactly as before.
    fuses_adds = True
    # True on servers whose drain may stably sort a drained batch into
    # priority lanes (serving reads > control > training writes). The
    # deterministic server keeps it False: its WAL is appended in ARRIVAL
    # order across workers and lane sorting would reorder that tape.
    # Sync/SSP keep it True — their round clocks defer, not order, so a
    # lane-sorted drain reaches the same gated state.
    reorders_lanes = True

    @property
    def plain_async(self) -> bool:
        """True iff fused add+get replies are trustworthy and cross-table
        device transactions are admissible — the single capability check
        clients use (derived, so a subclass setting either gating attr
        cannot forget to flip it)."""
        return not (self.gates_gets or self.defers_adds)

    @property
    def supports_named_transact(self) -> bool:
        """Named (registry-resolved) transactions are admissible exactly
        when raw ones are; FollowerServer overrides — named transactions
        are the ONE device-transaction form that crosses processes."""
        return self.plain_async

    def __init__(self, num_workers: int) -> None:
        self.num_workers = num_workers
        self._tables: Dict[int, "object"] = {}  # table_id -> ServerTable
        self._queue: MtQueue[Message] = MtQueue()
        self._thread: Optional[threading.Thread] = None
        # the interpreter probe: a thread only while the op trace is on
        # (``_main`` starts it, the switch going off ends it)
        self._probe: Optional[threading.Thread] = None
        self._probe_stop = threading.Event()
        self._started = threading.Event()
        # Heartbeat/lease tracker for remote workers, attached by the
        # RemoteServer when it starts serving (fault/detector.py); None
        # when no off-mesh clients exist. Only the sync watchdog acts on
        # it — async servers have no round gates a dead worker could hold.
        self.liveness = None
        # Write-ahead log (durable/wal.py), attached by mv.serve() when
        # the wal_dir flag is set; None = no durability. Wire Adds carry
        # their raw blobs in msg._wal and are appended via _wal_append on
        # this dispatcher thread before the add is applied/ACKed.
        self.wal = None
        # Shard identity (shard/_child.py): a shard group runs N
        # identical-looking serving processes, so operator-facing logs
        # (stalls, lease evictions) carry which shard spoke; -1 = not a
        # shard-group member.
        self.shard_id = -1
        # micro-batch cap: how many queued Adds one drain may fuse into a
        # single table apply (0 = legacy per-message dispatch); cached for
        # the drain loop but LIVE through the config watch seam — the
        # autotuner (and operators) can step it on a running server
        self._apply_batch_cap = max(0, int(
            config.get_flag("apply_batch_msgs")))
        self._flag_unsub = config.FLAGS.on_change(
            "apply_batch_msgs", self._on_batch_cap_change)
        # overload survival (runtime/admission.py): drain-time admission
        # gate (backlog shedding, tenant write quotas, optional SLO burn
        # signal attachable via gate.burn_signal) + lane sorting. Flags
        # read once at construction; defaults admit everything.
        self.admission = AdmissionGate.from_flags()
        self._lane_sort = (self.reorders_lanes
                           and bool(config.get_flag("priority_lanes")))
        # table_id -> Adds applied, of the tables whose Adds are ordered
        # (``ServerTable.orders_adds``): the Add ordinal (``_stamp``).
        # Written on the dispatcher thread alone.
        self._adds_applied: Dict[int, int] = {}

    def _on_batch_cap_change(self, _name: str, value) -> None:
        self._apply_batch_cap = max(0, int(value))

    def _ident(self) -> str:
        """Log prefix naming this dispatcher when it is one of many."""
        return f"shard {self.shard_id}: " if self.shard_id >= 0 else ""

    @dispatcher_only
    def _wal_append(self, msg: Message) -> None:
        """Append a wire Add's WAL entry (attached by the RemoteServer)
        immediately before it is applied, so WAL order equals apply order
        and recovery replay reproduces the table bit-for-bit. The entry is
        popped so a deferred message re-dispatched by a drain loop appends
        exactly once. Runs on the dispatcher thread — appends serialize
        with applies for free."""
        entry = getattr(msg, "_wal", None)
        if entry is not None and self.wal is not None:
            msg._wal = None
            self.wal.append(*entry)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        self._probe_stop.clear()  # before the thread that starts the probe
        self._thread = threading.Thread(target=self._main, name="mv-server", daemon=True)
        self._thread.start()
        self._started.wait()

    def stop(self) -> None:
        if getattr(self, "_flag_unsub", None) is not None:
            self._flag_unsub()
            self._flag_unsub = None
        self._queue.exit()
        self._probe_stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        probe = self._probe
        if probe is not None:
            probe.join(timeout=10)

    def _probe_interpreter(self) -> None:
        """How long after its timer a sleeping thread of THIS process runs
        Python again, fifty times a second while the op trace is on: the
        kernel's timer and scheduler, then the wait for the interpreter
        lock, which the dispatcher, the serve thread, the finishing thread
        and every receive thread of a serving process share (a taker waits
        at most ``sys.getswitchinterval()`` a holder). An idle process
        reads the first alone. One ring record a wake, start the instant
        the sleep was due to end, ``dur_ns`` how late it ended; no section
        (nothing runs inside it) and no monitor: with the switch off there
        is no thread, because a thread that wakes fifty times a second
        beside a saturated dispatcher cost it 1.3-2.6% of its rate
        (PERF.md, PR 52). The dispatcher starts it with the first drain
        it makes under the switch (``_main``); it ends when it
        wakes to the switch off, or with the dispatcher (the sleep is a
        timed wait for the stop, so ``stop`` does not wait a period
        out)."""
        while Dashboard.profile_annotations:
            due = time.perf_counter_ns() + _PROBE_PERIOD_NS
            if self._probe_stop.wait(_PROBE_PERIOD_NS * 1e-9):
                break
            late = max(0, time.perf_counter_ns() - due)
            RING.append(0, 0, "INTERP_WAKE_DELAY", due, late, 0, 0, 0)
        self._probe = None

    def run_serialized(self, fn: Callable,
                       timeout: Optional[float] = 300.0):
        """Execute ``fn`` on the dispatcher thread, serialized with table
        traffic, and return its result — the checkpoint and multihost
        layers' shared 'quiesced execution' primitive. Re-entrant (runs
        inline when already on the dispatcher thread). ``timeout=None``
        waits as long as the dispatcher LIVES — callers whose fn
        legitimately runs long (multi-GB checkpoint streams) are not cut
        off mid-write, but a stopped/dead dispatcher raises instead of
        hanging the caller forever."""
        thread = self._thread
        if threading.current_thread() is thread:
            return fn()
        waiter = _ExecWaiter()
        self.send(Message(src=-1, dst=-1, type=MsgType.Server_Execute,
                          data=[fn, waiter]))
        if timeout is not None:
            return waiter.wait(timeout)
        while not waiter._event.wait(10.0):
            if thread is None or not thread.is_alive():
                raise TimeoutError(
                    "dispatcher exited with the serialized execution "
                    "still pending (server stopped?)")
        return waiter.wait(0)

    def register_table(self, server_table) -> int:
        table_id = len(self._tables)
        # stamp the id BEFORE the table becomes dispatchable: a forwarded
        # multihost request can hit process_add the instant the dict entry
        # exists, and the lockstep wrapper broadcasts server_table.table_id
        # (WorkerTable._register re-stamps the same value later)
        server_table.table_id = table_id
        self._tables[table_id] = server_table
        return table_id

    def table(self, table_id: int):
        return self._tables[table_id]

    # -- client side -------------------------------------------------------
    def send(self, msg: Message) -> None:
        msg.enq_ns = time.perf_counter_ns()
        if Dashboard.profile_annotations:
            msg.enq_span = current_span()
        self._queue.push(msg)

    # -- dispatcher --------------------------------------------------------
    def _main(self) -> None:
        self._started.set()
        queue_gauge = _apply_metrics()[3]
        while True:
            # recomputed per drain: the cap is a live knob (watch seam)
            fuse = self.fuses_adds and self._apply_batch_cap > 0
            # profiler wait site: an idle dispatcher parks here; time in
            # the drain is "no work", everything after is dispatch cost
            _prev_wait = mark_wait("dispatcher_drain")
            try:
                with span("DISPATCHER_PARKED"):
                    msgs = self._queue.pop_all()
            finally:
                clear_wait(_prev_wait)
            if msgs is None:
                return
            if Dashboard.profile_annotations and self._probe is None:
                # this thread alone starts the probe
                self._probe = threading.Thread(
                    target=self._probe_interpreter, name="mv-interp-probe",
                    daemon=True)
                self._probe.start()
            with span("DISPATCHER_DRAIN", n=len(msgs), cpu=True):
                # depth AFTER the drain = requests that arrived behind
                # this wakeup's batch; sampled once per drain, not once
                # per message (per-message sampling was pure hot-loop
                # overhead)
                queue_gauge.set(self._queue.size())
                if self._lane_sort and len(msgs) > 1:
                    msgs = lane_order(msgs)
                msgs = self._admit(msgs)
                if fuse and len(msgs) > 1:
                    self._dispatch_batch(msgs)
                else:
                    for msg in msgs:
                        self._dispatch_guarded(msg)

    def _admit(self, msgs: List[Message]) -> List[Message]:
        """Drain-time overload filter: drop expired-deadline work (its
        caller stopped waiting — an apply would be pure heat) and ask the
        admission gate about the rest. Both failure paths answer the
        completion truthfully (deadline_exceeded / "shed: ...") so the
        client can distinguish 'degrade gracefully' from 'broken'. Depth
        = this batch + what queued behind it, the backlog a new arrival
        actually waits behind."""
        depth = len(msgs) + self._queue.size()
        now = time.monotonic()
        admitted: List[Message] = []
        for msg in msgs:
            if 0.0 < msg.deadline < now and msg.type in (
                    MsgType.Request_Get, MsgType.Request_Add):
                count("DEADLINE_EXPIRED_DROPS")
                hop(msg.req_id, "deadline_drop")
                if msg.data and hasattr(msg.data[-1], "fail"):
                    msg.data[-1].fail(DeadlineExceeded(
                        f"deadline_exceeded: {msg.type.name} expired "
                        f"{now - msg.deadline:.3f}s before apply "
                        f"(backlog {depth})"))
                continue
            text = self.admission.refusal(msg, depth)
            if text is not None:
                if msg.data and hasattr(msg.data[-1], "fail"):
                    msg.data[-1].fail(ShedError(text))
                continue
            admitted.append(msg)
        return admitted

    @staticmethod
    def _queue_waited(msg: Message, until_ns: int = 0) -> None:
        """A Get's or Add's wait for the dispatcher, from ``send`` to the
        moment its service begins (``until_ns``, else now), so the time
        behind earlier messages of its own drain counts. Observed once:
        a message a clock gate re-dispatches later waited at the gate
        (the SYNC_GATE_WAIT_SECONDS histogram and, in the op trace, its
        SYNC_GATE_WAIT record), not in the queue."""
        enq_ns = msg.enq_ns
        if not enq_ns or msg.type not in (MsgType.Request_Get,
                                          MsgType.Request_Add):
            return
        msg.enq_ns = 0
        waited = (until_ns or time.perf_counter_ns()) - enq_ns
        _apply_metrics()[4].observe(waited * 1e-9)
        if Dashboard.profile_annotations:
            RING.append(0, msg.enq_span, "SERVER_QUEUE_WAIT", enq_ns, waited,
                        0, msg.req_id or msg.msg_id, 0)

    def _dispatch_guarded(self, msg: Message) -> None:
        self._queue_waited(msg)
        try:
            with monitor("SERVER_DISPATCH_MSG", op=msg.req_id or msg.msg_id):
                self._dispatch(msg)
        except Exception as exc:  # keep the dispatcher alive; fail the waiter
            log.error("server dispatcher error on %s: %r", msg.type, exc)
            if msg.data and hasattr(msg.data[-1], "fail"):
                msg.data[-1].fail(exc)

    @staticmethod
    def _fusable_add(msg: Message) -> bool:
        """Adds the drain loop may hold back and group: plain table Adds.
        Device transactions (request[0] is a tag string) read/write
        MULTIPLE tables — they are full barriers, like any non-Add."""
        if msg.type != MsgType.Request_Add or not msg.data:
            return False
        request = msg.data[0]
        return not (isinstance(request, tuple) and request
                    and isinstance(request[0], str))

    def _dispatch_batch(self, msgs: List[Message]) -> None:
        """Micro-batched drain (the receive-side mirror of the PR-5 send
        coalescing): walk the drained backlog in arrival order, holding
        plain Adds back in per-table groups; a Get flushes ITS table's
        group first (per-worker FIFO — a worker's own earlier Adds are
        always visible to its Get), any other message is a full barrier.
        Within one flushed group, Adds from different workers reorder
        into a single fused apply — the commutative-Add reordering
        Downpour SGD (Dean et al., NIPS 2012) explicitly tolerates."""
        pending: Dict[int, List[Message]] = {}

        def flush(table_id: Optional[int] = None) -> None:
            if table_id is None:
                for tid in list(pending):
                    flush(tid)
                return
            batch = pending.pop(table_id, None)
            if batch:
                self._apply_add_batch(table_id, batch)

        for msg in msgs:
            if self._fusable_add(msg):
                pending.setdefault(msg.table_id, []).append(msg)
                continue
            if msg.type == MsgType.Request_Get:
                flush(msg.table_id)
            else:
                flush()
            self._dispatch_guarded(msg)
        flush()

    @dispatcher_only
    def _apply_add_batch(self, table_id: int, msgs: List[Message]) -> None:
        cap = self._apply_batch_cap
        while msgs:
            consumed = self._apply_add_chunk(table_id, msgs[:cap])
            msgs = msgs[consumed:]

    @dispatcher_only
    def _apply_add_chunk(self, table_id: int, msgs: List[Message]) -> int:
        """Fuse-and-apply a prefix of ``msgs``; returns how many messages
        were handled (the table's merge may consume fewer than offered to
        bound the fused-apply size)."""
        if len(msgs) == 1:
            self._dispatch_guarded(msgs[0])
            return 1
        began_ns = time.perf_counter_ns()  # the group's service: the merge too
        table = self._tables.get(table_id)
        merged = None
        if table is not None:
            try:
                with span("TABLE_MERGE_ADDS", n=len(msgs),
                          op=msgs[0].req_id or msgs[0].msg_id):
                    merged = table.merge_add_requests(
                        [m.data[0] for m in msgs])
            except Exception as exc:  # merge must never sink the batch
                log.error("server: merge_add_requests failed on table %d "
                          "(%r); applying per message", table_id, exc)
                merged = None
        if merged is None:
            # the FIRST request cannot merge: dispatch it alone and offer
            # the rest again — a lone incompatible request must not
            # degrade its whole group to per-message dispatch. (Tables
            # that never merge return None without scanning, so the extra
            # calls cost an attribute lookup each.)
            self._dispatch_guarded(msgs[0])
            return 1
        request, rows, consumed = merged
        consumed = max(1, min(int(consumed), len(msgs)))
        if consumed == 1:
            self._dispatch_guarded(msgs[0])
            return 1
        msgs = msgs[:consumed]
        # WAL entries per Add, in arrival order, BEFORE the fused apply
        # (the PR-2 invariant: an ACKed Add is always recoverable);
        # recovery replays the records individually, which sums to the
        # same state for the commutative Adds that merged at all
        for msg in msgs:
            self._queue_waited(msg, began_ns)
            self._wal_append(msg)
            hop(msg.req_id, "apply_add")
        fused_c, batched_c, rows_h = _apply_metrics()[:3]
        try:
            with monitor("SERVER_PROCESS_ADD_MSG",
                         op=msgs[0].req_id or msgs[0].msg_id,
                         n=len(msgs)) as fused:
                self._apply_fused(table, request)
        except Exception as exc:
            # merge validated shapes, so this is rare; the contract that
            # makes the retry safe: process_add validates before it
            # mutates, so a raised error means nothing applied
            log.error("server: fused apply of %d adds on table %d failed "
                      "(%r); retrying per message", len(msgs), table_id,
                      exc)
            for msg in msgs:
                try:
                    with monitor("SERVER_PROCESS_ADD_MSG"):
                        msg.data[-1].done(table.process_add(msg.data[0]))
                except Exception as per_exc:
                    msg.data[-1].fail(per_exc)
            return consumed
        fused_c.add(1)
        batched_c.add(len(msgs))
        rows_h.observe(rows)
        if fused.id:
            # each request the group answers carries the group's service
            # span: its service time is the group's
            for msg in msgs:
                RING.append(0, fused.id, "APPLY_FUSED_ADD", fused.start_ns,
                            fused.dur_ns, 0, msg.req_id or msg.msg_id,
                            len(msgs))
        for msg in msgs:
            msg.data[-1].done(None)
        return consumed

    @dispatcher_only
    def _apply_fused(self, table, request) -> None:
        """The fused apply — a named seam so crash-point tests can kill
        the process between a batch's WAL appends and its apply."""
        table.process_add(request)

    def _dispatch(self, msg: Message) -> None:
        if msg.type == MsgType.Request_Add:
            self._process_add(msg)
        elif msg.type == MsgType.Request_Get:
            self._process_get(msg)
        elif msg.type == MsgType.Request_Query:
            self._process_query(msg)
        elif msg.type == MsgType.Server_Execute:
            # administrative callable, serialized with table traffic (used
            # by the multihost lockstep checkpoint path): never clocked,
            # identical on every server flavor
            fn, completion = msg.data
            completion.done(fn())
        elif msg.type == MsgType.Server_Finish_Train:
            self._process_finish_train(msg)
        else:
            log.error("server: unhandled message type %s", msg.type)

    @dispatcher_only
    def _stamp(self, table_id: int, completion, served, add: bool) -> None:
        """The Add ordinal of an op on a table whose Adds are ordered, on
        its completion (which replies with it) and on its service record
        (``served``): an Add that has just been applied takes the table's
        next place, 1, 2, ...; a Get about to launch takes the number of
        Adds applied so far, so that it returns the state after exactly
        that many. One count a table, on this thread, which applies and
        launches in one order: the ordinals are that order."""
        k = self._adds_applied.get(table_id, 0)
        if add:
            k = self._adds_applied[table_id] = k + 1
        served.ordinal = k
        if getattr(completion, "takes_ordinal", False):
            completion.ordinal = k

    @dispatcher_only
    def _process_add(self, msg: Message) -> None:
        with monitor("SERVER_PROCESS_ADD_MSG", n=1) as served:
            request, completion = msg.data
            self._wal_append(msg)
            hop(msg.req_id, "apply_add")
            table = self._tables[msg.table_id]
            # process_add may return a fused-get payload (ArrayTable's
            # add+get sync path); plain adds return None as before
            result = table.process_add(request)
            if table.orders_adds:
                self._stamp(msg.table_id, completion, served, add=True)
            completion.done(result)

    @dispatcher_only
    def _process_get(self, msg: Message) -> None:
        with monitor("SERVER_PROCESS_GET_MSG") as served:
            request, completion = msg.data
            hop(msg.req_id, "serve_get")
            table = self._tables[msg.table_id]
            if table.orders_adds:
                self._stamp(msg.table_id, completion, served, add=False)
            complete_get(completion, table.launch_get(request))

    @dispatcher_only
    def _process_query(self, msg: Message) -> None:
        """Request_Query: top-k retrieval pushdown (multiverso_tpu/
        query/). Serialized with applies like a Get — a query observes a
        consistent table state — but never clocked: it is slot-free
        administrative traffic on every server flavor (src=-1 bypasses
        the round gates on the sync server the same way read-tier
        forwards do)."""
        from multiverso_tpu.query import query_table
        with monitor("SERVER_PROCESS_QUERY_MSG"):
            request, completion = msg.data
            hop(msg.req_id, "serve_query")
            completion.done(query_table(self._tables[msg.table_id],
                                        request))

    def _process_finish_train(self, msg: Message) -> None:
        pass  # async server has no clocks to drain


class DeterministicServer(Server):
    """Async server with a deterministic apply order (the ``deterministic``
    flag). Adds are buffered per (table, worker) and applied in
    (round, worker_id) order: round-r deltas apply only once every unfinished
    worker's round-r delta has arrived, then in ascending worker id. The final
    table state is therefore bitwise reproducible run-to-run regardless of
    thread scheduling (float addition is not associative; plain async applies
    in arrival order). Gets are served immediately — reads stay async.

    Contract: workers must issue the same number of adds per table between
    ``finish_train`` calls (the lockstep-rounds shape BSP already imposes);
    ``finish_train`` releases a finished worker's hold on later rounds.
    Add completions fire at ENQUEUE, not apply (``add`` means "accepted;
    will apply in deterministic order" — the same contract as
    ``add_async``): completing at apply time would deadlock two workers
    adding to two tables in opposite orders, each blocked waiting for the
    round-mate add the other is about to send. Apply-time errors therefore
    surface in the log, not in the caller (again like ``add_async``).
    """

    defers_adds = True
    # (round, worker) apply order admits no multi-message fused group:
    # the drain loop dispatches per message, exactly as before
    fuses_adds = False
    # WAL/ACK happen at enqueue in ARRIVAL order — lane sorting would
    # reorder that tape, so the deterministic drain keeps FIFO
    reorders_lanes = False

    def __init__(self, num_workers: int) -> None:
        super().__init__(num_workers)
        self._add_queues: Dict[int, List[List[Message]]] = {}
        self._det_finished: List[bool] = [False] * num_workers

    def register_table(self, server_table) -> int:
        table_id = super().register_table(server_table)
        self._add_queues[table_id] = [[] for _ in range(self.num_workers)]
        return table_id

    @dispatcher_only
    def _process_add(self, msg: Message) -> None:
        if not 0 <= msg.src < self.num_workers:
            super()._process_add(msg)  # administrative: apply immediately
            return
        # WAL entry at ENQUEUE (arrival order), matching the ACK-at-enqueue
        # contract: recovery replays in arrival order, so exactly-once
        # holds across a crash, but the (round, worker) apply order — and
        # with it bitwise run-to-run reproducibility — does not survive a
        # mid-training restart (docs/fault_tolerance.md §7).
        self._wal_append(msg)
        self._add_queues[msg.table_id][msg.src].append(msg)
        msg.data[-1].done(None)  # accepted; applies in round order below
        self._drain_adds(msg.table_id)

    @dispatcher_only
    def _drain_adds(self, table_id: int) -> None:
        queues = self._add_queues[table_id]
        while any(queues) and all(
                q or self._det_finished[w] for w, q in enumerate(queues)):
            for w, q in enumerate(queues):
                if q:
                    request, _ = q.pop(0).data
                    try:
                        with monitor("SERVER_PROCESS_ADD_MSG"):
                            self._tables[table_id].process_add(request)
                    except Exception as exc:  # keep the round draining
                        log.error("deterministic add from worker %d on table"
                                  " %d failed at apply time: %r", w,
                                  table_id, exc)

    def _process_finish_train(self, msg: Message) -> None:
        if 0 <= msg.src < self.num_workers:
            self._det_finished[msg.src] = True
        for tid in list(self._tables):
            self._drain_adds(tid)


class SyncServer(Server):
    """BSP dispatcher preserving the reference SyncServer's observable
    contract with per-worker vector clocks and deferred request caches."""

    gates_gets = True
    # the two-sided clock defers/releases every Add itself — per-message
    # dispatch is the gate (SSPServer inherits: its Adds bump per-worker
    # clocks that a fused apply could not account)
    fuses_adds = False

    def __init__(self, num_workers: int) -> None:
        super().__init__(num_workers)
        # per-table clocks: table_id -> [adds applied per worker], [gets served per worker]
        self._add_clock: Dict[int, List[int]] = {}
        self._get_clock: Dict[int, List[int]] = {}
        self._finished: List[bool] = [False] * num_workers
        self._pending_add: Dict[int, List[Message]] = {}
        self._pending_get: Dict[int, List[Message]] = {}
        # rounds as the op trace and the counters see them: per table, the
        # rounds every gated worker's Add has reached, and when the first
        # Add of each round still open began
        self._rounds_done: Dict[int, int] = {}
        self._round_began: Dict[int, Dict[int, int]] = {}
        self._rounds = Dashboard.counter("SYNC_ROUNDS")
        self._served = {
            MsgType.Request_Add: Dashboard.counter("SYNC_SERVED_ADD"),
            MsgType.Request_Get: Dashboard.counter("SYNC_SERVED_GET")}
        self._deferred = {
            MsgType.Request_Add: Dashboard.counter("SYNC_DEFERRED_ADD"),
            MsgType.Request_Get: Dashboard.counter("SYNC_DEFERRED_GET")}
        # Straggler tolerance: the reference defined `backup_worker_ratio`
        # but never read it (src/server.cpp:21); here it is real — the
        # slowest floor(ratio * num_workers) workers' clocks are ignored by
        # the round gates, so backups can lag without stalling the ring.
        self._backup_count = int(
            config.get_flag("backup_worker_ratio") * num_workers)
        # Stall watchdog (reference gap: peers hung silently on a crashed
        # worker). Every `sync_stall_seconds` with no clock progress while
        # requests sit deferred, log WHICH worker ids are holding the round.
        self.last_stall: Optional[str] = None
        self._watch_stop = threading.Event()
        self._watch_thread: Optional[threading.Thread] = None
        # guards dict INSERTS (register_table, user thread) against the
        # watchdog's iteration; in-place clock list mutation never resizes
        self._register_lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        super().start()
        period = float(config.get_flag("sync_stall_seconds"))
        if period > 0:
            self._watch_thread = threading.Thread(
                target=self._watch_stalls, args=(period,),
                name="mv-sync-watchdog", daemon=True)
            self._watch_thread.start()

    def stop(self) -> None:
        self._watch_stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=10)
            self._watch_thread = None
        super().stop()

    def _watch_stalls(self, period: float) -> None:
        last_snap = None
        while not self._watch_stop.wait(period):
            self._reap_leases()
            with self._register_lock:
                tids = list(self._add_clock)
                snap_add = {t: list(self._add_clock[t]) for t in tids}
                snap_get = {t: list(self._get_clock[t]) for t in tids}
            pending = {tid: (len(self._pending_add[tid]),
                             len(self._pending_get[tid]))
                       for tid in tids}
            snap = (snap_add, snap_get, pending)
            if last_snap == snap and any(a or g for a, g in pending.values()):
                for tid, (n_add, n_get) in pending.items():
                    if not (n_add or n_get):
                        continue
                    adds, gets = self._add_clock[tid], self._get_clock[tid]
                    # Blockers = unfinished workers at the minimum clock that
                    # have NO deferred request of their own (a worker whose
                    # request sits in the pending queue is waiting, not
                    # holding the round).
                    waiting = ({m.src for m in self._pending_add[tid]}
                               | {m.src for m in self._pending_get[tid]})
                    unfin = [w for w in range(self.num_workers)
                             if not self._finished[w]]
                    if not unfin:
                        continue
                    min_add = min(adds[w] for w in unfin)
                    min_get = min(gets[w] for w in unfin)
                    at_min = [w for w in unfin
                              if adds[w] == min_add or gets[w] == min_get]
                    lag = sorted(w for w in at_min if w not in waiting) \
                        or sorted(at_min)
                    report = (
                        f"{self._ident()}"
                        f"sync stall: table {tid} has {n_add} deferred adds /"
                        f" {n_get} deferred gets with no progress for "
                        f"{period:.1f}s; waiting on worker(s) {lag} "
                        f"(add clocks {adds}, get clocks {gets})")
                    self.last_stall = report
                    log.error("%s", report)
            last_snap = snap

    def _reap_leases(self) -> None:
        """Watchdog escalation (reference gap: the stall detector could
        only log): evict every remote worker whose lease expired. The
        detector reports each expiry exactly once; the eviction itself
        mutates clocks, so it runs on the dispatcher thread serialized
        with table traffic."""
        liveness = self.liveness
        if liveness is None:
            return
        for worker in liveness.reap():
            if not 0 <= worker < self.num_workers:
                continue
            log.error("%ssync: lease expired for worker %d — evicting it "
                      "from the round gates", self._ident(), worker)
            self.send(Message(
                src=-1, dst=-1, type=MsgType.Server_Execute,
                data=[lambda w=worker: self._evict_worker(w),
                      _NullCompletion()]))

    # -- gate-wait telemetry (obs/): a deferred request's queue time is the
    # tail the BSP/SSP contract creates — stamped at defer, observed at
    # release: the SYNC_GATE_WAIT_SECONDS histogram and, while the op trace
    # is on, one SYNC_GATE_WAIT record (written as SERVER_QUEUE_WAIT is: it
    # starts at the deferral, its parent is the span the sender was in, its
    # `op` the request's own id, its `n` the round the request waited for)
    @staticmethod
    def _gate_defer(msg: Message, round_: int = 0) -> None:
        msg._gated_at = time.perf_counter_ns()
        msg._gated_for = round_
        hop(msg.req_id, "gate_deferred")

    @staticmethod
    def _gate_release(msg: Message) -> None:
        gated_at = getattr(msg, "_gated_at", None)
        if gated_at is not None:
            waited = time.perf_counter_ns() - gated_at
            observe("SYNC_GATE_WAIT_SECONDS", waited * 1e-9)
            if Dashboard.profile_annotations:
                RING.append(0, msg.enq_span, "SYNC_GATE_WAIT", gated_at,
                            waited, 0, msg.req_id or msg.msg_id,
                            msg._gated_for)
        hop(msg.req_id, "gate_released")

    @dispatcher_only
    def _evict_worker(self, worker: int) -> None:
        """Remove a dead worker from every clock gate (dispatcher thread):
        mark it finished so ``_min_adds``/``_min_gets`` stop waiting on its
        clocks, fail-and-release its own deferred requests (their replies
        have nowhere to go — the completions log, nobody hangs), and drain
        so survivors' gated rounds proceed. BSP and SSP both recover
        through this path; an evicted worker's slot stays retired (its
        clock history is positional, like the deregister contract)."""
        if self._finished[worker]:
            return
        self._finished[worker] = True
        count("WORKER_EVICTIONS")
        exc = ConnectionError(
            f"worker {worker} evicted: lease expired (crashed or "
            "partitioned beyond lease_seconds)")
        for tid in list(self._tables):
            for pending in (self._pending_add, self._pending_get):
                mine = [m for m in pending[tid] if m.src == worker]
                if mine:
                    pending[tid] = [m for m in pending[tid]
                                    if m.src != worker]
                    for msg in mine:
                        hop(msg.req_id, "gate_failed_eviction")
                        msg.data[-1].fail(exc)
            self._note_rounds(tid)
            self._drain(tid)
        # post-mortem: the last N request traces (including the corpse's
        # deferred ones, hop by hop) + a dashboard snapshot
        flight_dump("worker_evicted", worker=worker)

    def register_table(self, server_table) -> int:
        table_id = super().register_table(server_table)
        with self._register_lock:
            self._add_clock[table_id] = [0] * self.num_workers
            self._get_clock[table_id] = [0] * self.num_workers
            self._pending_add[table_id] = []
            self._pending_get[table_id] = []
            self._rounds_done[table_id] = 0
            self._round_began[table_id] = {}
        return table_id

    # clock helpers: finished workers never hold anyone back, and the
    # slowest `_backup_count` unfinished workers are ignored (backup workers)
    def _gate(self, vals: List[int]) -> int:
        if not vals:
            return 1 << 60
        k = min(self._backup_count, len(vals) - 1)
        return sorted(vals)[k]

    def _min_gets(self, table_id: int) -> int:
        return self._gate([g for g, f in zip(self._get_clock[table_id],
                                             self._finished) if not f])

    def _min_adds(self, table_id: int) -> int:
        return self._gate([a for a, f in zip(self._add_clock[table_id],
                                             self._finished) if not f])

    def _is_admin(self, worker: int) -> bool:
        """Administrative access (no worker context — e.g. checkpoint reads
        on a server-only node, worker id -1) bypasses the clocks."""
        return not 0 <= worker < self.num_workers

    @dispatcher_only
    def _process_add(self, msg: Message) -> None:
        tid = msg.table_id
        worker = msg.src
        if self._is_admin(worker):
            super()._process_add(msg)
            return
        round_ = self._add_clock[tid][worker] + 1
        # round-r Adds wait until every worker has finished its round-(r-1) Gets
        if self._min_gets(tid) >= round_ - 1:
            self._serve(msg)
            self._drain(tid)
        else:
            self._defer(msg, round_)

    @dispatcher_only
    def _process_get(self, msg: Message) -> None:
        tid = msg.table_id
        worker = msg.src
        if self._is_admin(worker):
            super()._process_get(msg)
            return
        round_ = self._get_clock[tid][worker] + 1
        # round-i Gets wait until every worker's round-i Add is applied
        if self._min_adds(tid) >= round_:
            self._serve(msg)
            self._drain(tid)
        else:
            self._defer(msg, round_)

    @dispatcher_only
    def _serve(self, msg: Message) -> None:
        """Serve one worker's Add or Get whose clock condition holds, on
        arrival or released from the gate, and step the worker's clock:
        what the async server's ``_process_add`` / ``_process_get`` do and
        record, under the request's OWN id. A released request is served
        inside the dispatch of the message whose arrival released it, so
        the id is given to the section, and every record of the service
        (the table's, the launch's, the reply's hand-over) inherits it."""
        tid, worker = msg.table_id, msg.src
        op = msg.req_id or msg.msg_id
        request, completion = msg.data
        if msg.type == MsgType.Request_Add:
            began_ns = time.perf_counter_ns()
            with monitor("SERVER_PROCESS_ADD_MSG", op=op, n=1):
                self._wal_append(msg)
                hop(msg.req_id, "apply_add")
                # forward the fused-sync reply (ArrayTable leaf mode)
                # rather than discarding it — the client would otherwise
                # re-run the whole merged-value split in a fallback get
                completion.done(self._tables[tid].process_add(request))
            self._add_clock[tid][worker] += 1
            self._round_began[tid].setdefault(self._add_clock[tid][worker],
                                              began_ns)
            self._note_rounds(tid)
        else:
            with monitor("SERVER_PROCESS_GET_MSG", op=op):
                hop(msg.req_id, "serve_get")
                result = self._tables[tid].launch_get(request)
                self._get_clock[tid][worker] += 1
                complete_get(completion, result)
        self._served[msg.type].add(1)

    @dispatcher_only
    def _defer(self, msg: Message, round_: int) -> None:
        """Keep a request behind its clock gate; ``round_`` is the round
        of every worker's Adds (a Get) or Gets (an Add) it waits for."""
        self._gate_defer(msg, round_)
        self._deferred[msg.type].add(1)
        pending = (self._pending_add if msg.type == MsgType.Request_Add
                   else self._pending_get)
        pending[msg.table_id].append(msg)

    @dispatcher_only
    def _note_rounds(self, table_id: int) -> None:
        """Count the rounds that every gated worker's Adds have now reached
        (``_min_adds``, so a finished or evicted worker holds none open)
        and give each one SYNC_ROUND record: from the start of the first
        Add applied in the round to now, the last's end; ``op`` the table,
        ``n`` the round."""
        reached = min(self._min_adds(table_id),
                      max(self._add_clock[table_id], default=0))
        done = self._rounds_done[table_id]
        if reached <= done:
            return
        self._rounds_done[table_id] = reached
        self._rounds.add(reached - done)
        now_ns = time.perf_counter_ns()
        began = self._round_began[table_id]
        for round_ in range(done + 1, reached + 1):
            began_ns = began.pop(round_, now_ns)
            if Dashboard.profile_annotations:
                RING.append(0, 0, "SYNC_ROUND", began_ns, now_ns - began_ns,
                            0, table_id, round_)

    def _process_finish_train(self, msg: Message) -> None:
        if self._is_admin(msg.src):
            return
        self._finished[msg.src] = True
        for tid in list(self._tables):
            self._note_rounds(tid)
            self._drain(tid)

    @dispatcher_only
    def _drain(self, table_id: int) -> None:
        """Release the deferred messages whose clock condition now holds,
        each served as on arrival (``_serve``). A pass that releases any is
        one SYNC_RELEASE section, ``n`` the messages it released; a pass
        that releases none records nothing. A released request that fails
        fails its own waiter, as one served on arrival does."""
        releasable = self._releasable(table_id)
        first = next(releasable, None)
        if first is None:
            return
        with monitor("SYNC_RELEASE") as release:
            for msg in itertools.chain((first,), releasable):
                release.n += 1
                self._gate_release(msg)
                try:
                    self._serve(msg)
                except Exception as exc:  # the waiter's, not the releaser's
                    log.error("server dispatcher error on released %s: %r",
                              msg.type.name, exc)
                    msg.data[-1].fail(exc)

    @staticmethod
    def _take(pending: List[Message], ready: Callable[[Message], bool]):
        """Yield the messages of ``pending`` that ``ready`` admits, in
        order, each taken off the list first: the list is at every moment
        what still waits."""
        i = 0
        while i < len(pending):
            if ready(pending[i]):
                yield pending.pop(i)
            else:
                i += 1

    def _releasable(self, table_id: int):
        """The deferred messages whose clock condition holds. The caller
        serves each before it asks for the next, so every test reads the
        clocks as that service left them. Passes of Gets then Adds, until
        one finds none."""
        def get_ready(msg: Message) -> bool:
            return (self._min_adds(table_id)
                    >= self._get_clock[table_id][msg.src] + 1)

        def add_ready(msg: Message) -> bool:
            return (self._min_gets(table_id)
                    >= self._add_clock[table_id][msg.src])

        progressed = True
        while progressed:
            progressed = False
            # gets first (they unblock next-round adds)
            for msg in self._take(self._pending_get[table_id], get_ready):
                progressed = True
                yield msg
            for msg in self._take(self._pending_add[table_id], add_ready):
                progressed = True
                yield msg


class SSPServer(SyncServer):
    """Stale-Synchronous-Parallel dispatcher — BEYOND the reference
    (SURVEY §2.2 notes bounded staleness was absent upstream; SSP was the
    Petuum-era consistency point between async and BSP).

    Contract: a worker that has completed ``r`` Adds on a table may Get
    that table only once EVERY unfinished worker has completed at least
    ``r - staleness`` Adds — the fastest worker runs at most ``staleness``
    rounds ahead of the slowest. ``staleness=0`` degenerates to a
    BSP-like read gate; large staleness approaches pure async. Adds are
    never deferred (unlike BSP's two-sided clock): applying a straggler's
    delta cannot violate anyone's staleness bound, it only advances the
    gate. ``backup_worker_ratio`` composes — backups are excluded from
    the minimum like in BSP."""

    gates_gets = True

    def __init__(self, num_workers: int, staleness: int) -> None:
        super().__init__(num_workers)
        self.staleness = int(staleness)

    @dispatcher_only
    def _process_add(self, msg: Message) -> None:
        tid = msg.table_id
        worker = msg.src
        if self._is_admin(worker):
            super(SyncServer, self)._process_add(msg)
            return
        self._serve(msg)
        # observed staleness: how many add-rounds this worker now leads
        # the slowest unfinished worker by (0 = in lockstep; bounded by
        # the staleness flag for its Gets to be served)
        gauge_set(f"SSP_STALENESS_W{worker}",
                  self._add_clock[tid][worker] - self._min_adds(tid))
        self._drain(tid)

    def _gate_round(self, tid: int, worker: int) -> int:
        """The add-round this worker's next Get requires every unfinished
        (non-backup) worker to have reached."""
        return self._add_clock[tid][worker] - self.staleness

    @dispatcher_only
    def _process_get(self, msg: Message) -> None:
        tid = msg.table_id
        worker = msg.src
        if self._is_admin(worker):
            super(SyncServer, self)._process_get(msg)
            return
        if self._min_adds(tid) >= self._gate_round(tid, worker):
            self._serve(msg)
        else:
            self._defer(msg, self._gate_round(tid, worker))

    def _releasable(self, table_id: int):
        def get_ready(msg: Message) -> bool:
            return (self._min_adds(table_id)
                    >= self._gate_round(table_id, msg.src))

        return self._take(self._pending_get[table_id], get_ready)


def make_server(num_workers: int) -> Server:
    """Factory keyed on the consistency flags (reference:
    ``Server::GetServer``): ``sync`` → BSP, ``ssp_staleness >= 0`` →
    bounded staleness, ``deterministic`` → reproducible-apply-order async
    (sync mode is already deterministic through its clocks)."""
    if config.get_flag("sync"):
        return SyncServer(num_workers)
    ssp = int(config.get_flag("ssp_staleness"))
    if ssp >= 0:
        return SSPServer(num_workers, ssp)
    if config.get_flag("deterministic"):
        return DeterministicServer(num_workers)
    return Server(num_workers)
