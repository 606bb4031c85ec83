"""The op trace: the ring that `monitor`, `span` and `hop` write while
`Dashboard.profile_annotations` is on, the stages an op leaves in it on its
way through the dispatcher, the table and the wire's server half, and the
benchmark's readers of it (`benchmark/op_trace.py`, `benchmark/layers/`).

Times here come from a CPU run: they check that spans nest, tile and carry
the right counts, never how fast anything is."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu import dashboard
from multiverso_tpu.dashboard import Dashboard, OpRing, monitor, span
from multiverso_tpu.obs.trace import TRACES, hop
from multiverso_tpu.runtime.message import Message, MsgType
from multiverso_tpu.runtime.server import _ExecWaiter
from multiverso_tpu.runtime.zoo import Zoo

from benchmark import common, op_trace

ROWS, COLS = 256, 128
IDS = np.arange(32, dtype=np.int32)
ONES = np.ones((len(IDS), COLS), np.float32)


@pytest.fixture
def tracing():
    """The one switch, on for the test's body; `mv.init` sets it from the
    flag of its name."""
    mv.set_flag("profile_annotations", True)
    Dashboard.profile_annotations = True
    yield
    Dashboard.profile_annotations = False


def _table():
    return mv.create_table("matrix", ROWS, COLS,
                           init_value=np.zeros((ROWS, COLS), np.float32))


def _run(t0, t1):
    """What a per-layer reader is handed, as far as these readers look."""
    return SimpleNamespace(window=(t0, t1))


def _served_run(t0):
    """The window from t0 to now, closed behind the dispatcher and the
    finishing thread: a waiter has its result before the thread that
    completed it has left the spans around `done` or the send; a no-op
    queued behind the dispatcher's returns after they have ended, and a
    reply leaves the finishing thread's queue after its spans have."""
    zoo = Zoo.instance()
    zoo.server.run_serialized(lambda: None)
    limit = time.monotonic() + 10
    while zoo.remote_server is not None and zoo.remote_server._unfinished:
        assert time.monotonic() < limit, "a reply was never finished"
        time.sleep(0.001)
    return _run(t0, time.perf_counter())


def _metric(name, run):
    return common.load_module("layers", name).read(run)


def _hold_dispatcher(server):
    """Block the dispatcher inside a Server_Execute until the returned
    event is set: everything queued behind it lands in one drain."""
    gate = threading.Event()
    server.send(Message(src=-1, dst=-1, type=MsgType.Server_Execute,
                        data=[lambda: gate.wait(30), _ExecWaiter()]))
    time.sleep(0.05)  # let the dispatcher enter the gate
    return gate


def _inside(child, parent):
    return (parent.start_ns <= child.start_ns
            and child.start_ns + child.dur_ns
            <= parent.start_ns + parent.dur_ns)


def _chain(trace, op, stages):
    """The op's records of `stages`, each the child of the one before."""
    found = []
    for stage in stages:
        mine = [r for r in trace.spans(stage) if r.op == op]
        assert len(mine) == 1, (stage, op, mine)
        if found:
            assert mine[0].parent == found[-1].id, stage
            assert _inside(mine[0], found[-1]), stage
        found.append(mine[0])
    return found


# -- (a) switch off ------------------------------------------------------------

def test_switch_off_appends_nothing_and_monitors_keep_their_shape():
    mv.init()
    table = _table()
    t0 = time.perf_counter()
    table.add(ONES, row_ids=IDS)
    table.get(IDS)
    with span("NEVER_RECORDED", n=3) as off:
        off.n = 4
    assert off.id == 0
    records, overwrote = dashboard.RING.window(t0, time.perf_counter())
    assert records == [] and not overwrote
    assert dashboard.RING.overwritten == 0
    monitors = Dashboard.snapshot()["monitors"]
    for name in ("SERVER_DISPATCH_MSG", "SERVER_PROCESS_ADD_MSG",
                 "SERVER_PROCESS_GET_MSG", "WORKER_TABLE_SYNC_ADD",
                 "WORKER_TABLE_SYNC_GET"):
        assert set(monitors[name]) == {"count", "elapse_ms", "average_ms"}
        assert monitors[name]["count"] >= 1
    # the sections that exist only in the op trace register nothing
    ring_only = ("TABLE_", "DISPATCHER_", "WORKER_WAIT", "NET_", "SERVE_",
                 "WIRE_REPLY", "NEVER_")
    assert not [m for m in monitors if m.startswith(ring_only)]
    # the always-on addition: one queue wait a Get or Add
    assert Dashboard.histogram("SERVER_QUEUE_WAIT_SECONDS").count == 2
    mv.shutdown()


def test_monitor_resolves_its_units_once_and_survives_reset():
    with monitor("RESOLVED_ONCE"):
        pass
    feeds = dashboard._monitor_feeds["RESOLVED_ONCE"]
    assert feeds == (Dashboard.get("RESOLVED_ONCE"),
                     Dashboard.histogram("RESOLVED_ONCE"))
    Dashboard.reset()  # zeroes in place: the resolved references stay live
    with monitor("RESOLVED_ONCE"):
        pass
    assert dashboard._monitor_feeds["RESOLVED_ONCE"] is feeds
    assert Dashboard.get("RESOLVED_ONCE").count == 1
    assert Dashboard.histogram("RESOLVED_ONCE").count == 1


# -- (b) the stages an op leaves, nested -----------------------------------------

def test_in_process_pair_leaves_its_stages_nested(tracing):
    mv.init()
    table = _table()
    table.add(ONES, row_ids=IDS)  # compile outside the window
    table.get(IDS)
    t0 = time.perf_counter()
    add = table.add_async(ONES, row_ids=IDS)
    table.wait(add)
    # the Get's result comes only after its waiter has gone to sleep
    gate = _hold_dispatcher(Zoo.instance().server)
    get = table.get_async(IDS)
    threading.Timer(0.05, gate.set).start()
    table.wait_get(get, IDS)
    run = _served_run(t0)
    trace = op_trace.of(run)

    chain = _chain(trace, add, ("SERVER_DISPATCH_MSG",
                                "SERVER_PROCESS_ADD_MSG",
                                "TABLE_PROCESS_ADD", "TABLE_ROW_PREP"))
    assert chain[-1].n == len(IDS)
    _chain(trace, add, ("TABLE_PROCESS_ADD", "TABLE_ROW_LAUNCH"))
    chain = _chain(trace, get, ("SERVER_DISPATCH_MSG",
                                "SERVER_PROCESS_GET_MSG",
                                "TABLE_PROCESS_GET", "TABLE_ROW_LAUNCH"))
    _chain(trace, get, ("TABLE_PROCESS_GET", "TABLE_ROW_PREP"))
    # the dispatcher launched the gather and went on: the fetch is the
    # waiter's own, made in its wait after the Get's service has ended
    read = _chain(trace, get, ("WORKER_WAIT", "TABLE_HOST_READ"))[-1]
    assert read.n >= len(IDS) * COLS * 4  # bytes of the padded bucket
    assert read.start_ns >= chain[1].start_ns + chain[1].dur_ns
    for op in (add, get):
        wait, = [r for r in trace.spans("SERVER_QUEUE_WAIT") if r.op == op]
        service, = [r for r in trace.spans("SERVER_DISPATCH_MSG")
                    if r.op == op]
        assert wait.start_ns + wait.dur_ns <= service.start_ns
        drain, = [r for r in trace.spans("DISPATCHER_DRAIN")
                  if r.id == service.parent]
        assert drain.n == 1 and _inside(service, drain)
        waited, = [r for r in trace.spans("WORKER_WAIT") if r.op == op]
        assert 0 <= waited.n < waited.dur_ns
    assert waited.n > 0  # the Get's: woken after done, before the span's end
    assert waited.n < read.start_ns - waited.start_ns  # the fetch not in it
    assert trace.spans("DISPATCHER_PARKED")

    # the readers of the cell without a wire see what is theirs
    assert _metric("dispatch_queue_wait_ms", run) > 0
    assert _metric("completion_wake_ms", run) > 0
    assert 0 < _metric("dispatcher_busy_share", run) <= 100
    assert 0 < _metric("dispatcher_cpu_share", run) <= 100
    ops = trace.spans("TABLE_PROCESS_ADD") + trace.spans("TABLE_PROCESS_GET")
    assert 0 < _metric("table_op_self_ms", run) \
        < sum(r.dur_ns for r in ops) / len(ops) / 1e6
    for wire_metric in ("wire_ingress_ms", "wire_reply_ms",
                        "server_residence_ms"):
        assert _metric(wire_metric, run) is None
    mv.shutdown()


def test_served_pair_tiles_its_residence(tracing):
    mv.init(remote_workers=1)
    table = _table()
    client = mv.remote_connect(mv.serve("127.0.0.1:0"))
    remote = client.table(table.table_id)
    remote.add(ONES, row_ids=IDS)  # compile outside the window
    remote.get(IDS)
    t0 = time.perf_counter()
    remote.add(ONES, row_ids=IDS)
    remote.get(IDS)
    run = _served_run(t0)
    trace = op_trace.of(run)

    requests = trace.requests()
    assert len(requests) == 2
    for q in requests:
        for part in (q.ingress, q.queue_wait, q.service, q.reply):
            assert part > 0
        parts = q.ingress + q.queue_wait + q.service + q.reply
        assert parts == pytest.approx(q.residence, rel=0.05)
        _chain(trace, q.op, ("SERVE_HANDLE", "WIRE_DECODE"))
    add, get = sorted(requests, key=lambda q: q.op)
    _chain(trace, add.op, ("SERVER_DISPATCH_MSG", "SERVER_PROCESS_ADD_MSG",
                           "WIRE_REPLY", "NET_SEND"))
    # a Get leaves the dispatcher launched: its reply is finished behind
    # it, by the finishing thread, in a span that is nobody's child
    service = _chain(trace, get.op, (
        "SERVER_DISPATCH_MSG", "SERVER_PROCESS_GET_MSG", "TABLE_PROCESS_GET",
        "TABLE_ROW_LAUNCH"))[1]
    chain = _chain(trace, get.op, ("REPLY_FINISH", "WIRE_REPLY",
                                   "WIRE_ENCODE"))
    assert chain[-1].n >= len(IDS) * COLS * 4  # the rows, encoded
    finish = chain[0]
    assert finish.parent == 0
    _chain(trace, get.op, ("REPLY_FINISH", "WIRE_REPLY", "NET_SEND"))
    read = _chain(trace, get.op, ("REPLY_FINISH", "TABLE_HOST_READ"))[-1]
    assert read.n >= len(IDS) * COLS * 4
    assert read.start_ns + read.dur_ns <= chain[1].start_ns
    for stage in ("TABLE_HOST_READ", "WIRE_REPLY"):  # the dispatcher has none
        assert not [r for r in trace.spans(stage) if r.op == get.op
                    and _inside(r, service)]
    # the new queue: from the hand-over inside the Get's service to the
    # start of the finish
    handed, = [r for r in trace.spans("REPLY_FINISH_WAIT")
               if r.op == get.op]
    assert handed.parent == service.id
    assert service.start_ns <= handed.start_ns \
        <= service.start_ns + service.dur_ns
    assert handed.start_ns + handed.dur_ns <= finish.start_ns
    assert not [r for r in trace.spans("REPLY_FINISH_WAIT")
                + trace.spans("REPLY_FINISH") if r.op == add.op]
    for q in requests:  # the receive thread, before the request's arrival
        arrived = min(r.start_ns for r in trace.spans("net_recv")
                      if r.op == q.op)
        for stage in ("NET_FRAME_READ", "NET_FRAME_CRC", "NET_FRAME_COPY"):
            first = min((r for r in trace.spans(stage) if r.op == q.op),
                        key=lambda r: r.start_ns)
            assert first.n > 0 and first.start_ns + first.dur_ns <= arrived
    # points inherit the span they fell in
    handle, = [r for r in trace.spans("SERVE_HANDLE") if r.op == get.op]
    enqueue, = [r for r in trace.spans("dispatch_enqueue")
                if r.op == get.op]
    assert enqueue.parent == handle.id

    # the readers of the cell with a wire, and how they close
    ingress = _metric("wire_ingress_ms", run)
    residence = _metric("server_residence_ms", run)
    assert 0 < ingress < residence
    assert 0 < _metric("wire_reply_ms", run) < residence
    assert _metric("dispatch_queue_wait_ms", run) < residence
    assert 0 < _metric("reply_finish_ms", run) < residence
    assert 0 <= _metric("reply_finish_wait_ms", run) < residence
    assert _metric("replies_behind_share", run) == 100.0
    # no blocking fetch is left inside a served Get, and the op's self
    # time subtracts none
    assert _metric("table_host_read_ms", run) is None
    ops = trace.spans("TABLE_PROCESS_ADD") + trace.spans("TABLE_PROCESS_GET")
    assert 0 < _metric("table_op_self_ms", run) \
        < sum(r.dur_ns for r in ops) / len(ops) / 1e6
    client.close()
    mv.shutdown()


# -- (c) a fused apply ---------------------------------------------------------

def test_fused_apply_gives_each_request_the_groups_span(tracing):
    mv.init()
    table = _table()
    gate = _hold_dispatcher(Zoo.instance().server)
    t0 = time.perf_counter()
    handles = [table.add_async(ONES, row_ids=IDS) for _ in range(3)]
    gate.set()
    for h in handles:
        table.wait(h)
    trace = op_trace.of(_served_run(t0))

    members = trace.spans("APPLY_FUSED_ADD")
    assert sorted(m.op for m in members) == sorted(handles)
    group, = [r for r in trace.spans("SERVER_PROCESS_ADD_MSG")
              if r.id == members[0].parent]
    assert group.n == 3
    for m in members:
        assert (m.parent, m.start_ns, m.dur_ns, m.n) == (
            group.id, group.start_ns, group.dur_ns, 3)
    # one apply for the three, its rows the three requests' together
    apply, = [r for r in trace.spans("TABLE_PROCESS_ADD")
              if r.parent == group.id]
    prep, = [r for r in trace.children(apply.id)
             if r.stage == "TABLE_ROW_PREP"]
    assert prep.n == 3 * len(IDS)  # XLA's scatter takes duplicates as they are
    merge, = trace.spans("TABLE_MERGE_ADDS")  # the concatenation before it
    assert merge.n == 3 and merge.start_ns + merge.dur_ns <= group.start_ns
    # each waited in the queue until the group's service began
    for h in handles:
        wait, = [r for r in trace.spans("SERVER_QUEUE_WAIT") if r.op == h]
        assert wait.start_ns + wait.dur_ns <= merge.start_ns
    np.testing.assert_array_equal(table.get(IDS), 3 * ONES)
    mv.shutdown()


# -- (d) the window cut and an overwritten ring -----------------------------------

def test_window_returns_only_what_lies_inside_and_reports_overwrites():
    ring = OpRing(8)
    second = 1_000_000_000
    for i in range(6):  # spans [i, i + 0.5] s
        ring.append(i + 1, 0, "STAGE", i * second, second // 2, 0, i, 0)
    records, overwrote = ring.window(0.9, 4.6)
    assert [r.op for r in records] == [1, 2, 3, 4] and not overwrote
    assert ring.overwritten == 0
    for i in range(6, 12):
        ring.append(i + 1, 0, "STAGE", i * second, second // 2, 0, i, 0)
    assert ring.overwritten == 4  # spans 0-3 are gone; 4 is the oldest kept
    records, overwrote = ring.window(2.0, 8.0)
    assert [r.op for r in records] == [4, 5, 6, 7] and overwrote
    records, overwrote = ring.window(4.6, 8.0)  # after the oldest kept ended
    assert [r.op for r in records] == [5, 6, 7] and not overwrote
    with pytest.raises(ValueError):
        OpRing(12)


def test_reader_fails_on_a_ring_that_overwrote_the_window(tracing,
                                                          monkeypatch):
    monkeypatch.setattr(dashboard, "RING", OpRing(8))
    t0 = time.perf_counter()
    for _ in range(20):
        with span("TOO_MANY"):
            pass
    with pytest.raises(RuntimeError, match="overwrote"):
        op_trace.of(_run(t0, time.perf_counter()))


# -- (e) the queue wait the metric reads -------------------------------------------

def test_queue_wait_metric_reads_an_injected_wait(tracing):
    mv.init()
    table = _table()
    table.get(IDS)  # compile outside the window
    Dashboard.reset()
    gate = _hold_dispatcher(Zoo.instance().server)
    t0 = time.perf_counter()
    get = table.get_async(IDS)
    time.sleep(0.2)
    held_ms = (time.perf_counter() - t0) * 1e3
    gate.set()
    table.wait_get(get, IDS)
    waited_ms = _metric("dispatch_queue_wait_ms", _served_run(t0))
    assert held_ms - 1 <= waited_ms <= held_ms + 100
    # the operator's series holds the same wait with the switch on or off
    hist = Dashboard.histogram("SERVER_QUEUE_WAIT_SECONDS")
    assert hist.count == 1
    assert hist.max * 1e3 == pytest.approx(waited_ms, abs=1e-3)
    mv.shutdown()


# -- (f) CPU time tells a wait from work ---------------------------------------------

def test_thread_cpu_time_of_a_sleeping_span_is_far_under_its_wall_time(
        tracing):
    t0 = time.perf_counter()
    with span("SLEEPS", op=9, n=1, cpu=True):
        time.sleep(0.05)
    with span("SPINS", cpu=True):
        end = time.perf_counter() + 0.05
        while time.perf_counter() < end:
            pass
    with span("UNASKED"):  # the clock is read only where a section asks
        pass
    records, _ = dashboard.RING.window(t0, time.perf_counter())
    sleeps, spins, unasked = [r for r in records if r.stage in (
        "SLEEPS", "SPINS", "UNASKED")]
    assert unasked.cpu_ns == 0
    assert (sleeps.stage, sleeps.op, sleeps.n) == ("SLEEPS", 9, 1)
    assert sleeps.dur_ns >= 50e6 and sleeps.cpu_ns < sleeps.dur_ns / 10
    assert spins.cpu_ns > spins.dur_ns / 4  # a busy thread, even when shared


# -- (g) the readers of replies finished behind the dispatcher, by hand -------

MS = 1_000_000


def _made_up(rows):
    """A reader's run over records written by hand: (id, parent, stage,
    start_ns, dur_ns, op) each."""
    records = [dashboard.OpRecord._make((seq, i, parent, stage, start, dur,
                                         0, op))
               for seq, (i, parent, stage, start, dur, op) in enumerate(rows)]
    return SimpleNamespace(_op_trace=op_trace.Trace(records, 0, 100 * MS))


def _served_get(op, at, finish_ms, wait_ms=None):
    """A served Get's records: its table op, the hand-over's wait (None:
    the dispatcher finished the reply itself), the finish and the stamp."""
    rows = [(op * 10, 0, "TABLE_PROCESS_GET", at, MS, op)]
    begun = at + MS
    if wait_ms is not None:
        rows.append((0, op * 10, "REPLY_FINISH_WAIT", begun,
                     int(wait_ms * MS), op))
        begun += int(wait_ms * MS)
    if finish_ms is not None:
        rows.append((op * 10 + 1, 0, "REPLY_FINISH", begun,
                     int(finish_ms * MS), op))
    rows.append((0, op * 10 + 1, "reply_sent", begun + MS // 2, 0, op))
    return rows


REPLY_TRACES = {
    # two Gets handed over, one the dispatcher finished (the queue was
    # full), an Add's reply, and an in-process Get nobody replies to
    "behind": (_served_get(11, 0, 1.0, wait_ms=0.2)
               + _served_get(12, 10 * MS, 2.0, wait_ms=0.4)
               + _served_get(13, 20 * MS, 3.0)
               + [(0, 0, "reply_sent", 30 * MS, 0, 14),
                  (150, 0, "TABLE_PROCESS_GET", 40 * MS, MS, 15)]),
    # the parent's program: a served Get has neither span
    "parent": _served_get(11, 0, None) + _served_get(12, 10 * MS, None),
    # an in-process cell: nothing is served over the wire
    "in_process": [(150, 0, "TABLE_PROCESS_GET", 0, MS, 15)],
}


@pytest.mark.parametrize("name, expected", [
    ("reply_finish_ms", {"behind": 2.0, "parent": None, "in_process": None}),
    ("reply_finish_wait_ms", {"behind": 0.3, "parent": None,
                              "in_process": None}),
    ("replies_behind_share", {"behind": 200 / 3, "parent": 0.0,
                              "in_process": None}),
])
def test_reply_finish_readers_on_a_made_up_trace(name, expected):
    for which, want in expected.items():
        got = _metric(name, _made_up(REPLY_TRACES[which]))
        assert got == (want if want is None else pytest.approx(want)), which


# -- hops: a point in the ring, the TraceStore as it was --------------------------------

def test_hop_adds_a_ring_point_and_keeps_the_trace_store_format(tracing):
    TRACES.reset()
    t0 = time.perf_counter()
    before = time.time_ns()
    with span("AROUND", op=77) as around:
        hop(77, "client_send")
        hop(0, "never")  # in-process messages carry no req_id
    records, _ = dashboard.RING.window(t0, time.perf_counter())
    point, section = [r for r in records if r.op == 77]
    assert (point.stage, point.op, point.dur_ns, point.id) == (
        "client_send", 77, 0, 0)
    assert point.parent == around.id == section.id
    assert _inside(point, section)
    (stage, t_ns), = TRACES.export(8)[77]  # wall clock, [stage, t_ns] pairs
    assert stage == "client_send" and before <= t_ns <= time.time_ns()
    assert 0 not in TRACES.export(8)


# -- the row launch's path, descriptors and bytes, and their readers ----------

@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_readers_of_the_row_launch_fields(kernel, tracing, monkeypatch):
    """`pallas_row_share` and `row_descriptor_bytes` read the launch
    records of the window: all of the Adds the kernel's and 1,536 bytes a
    descriptor on a table of three lane tiles where the (interpreted)
    kernel serves; 0% and nothing to read where XLA's scatter does. A
    program whose records carry no path (the ring of the PR before) gives
    None."""
    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables import matrix_table

    if kernel == "pallas":
        monkeypatch.setattr(
            matrix_table, "_use_pallas_scatter",
            lambda platform, num_shards, *width: num_shards == 1)
        monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)
        mv.init(mesh_shape="1")
    else:
        mv.init()
    # 72 x 300 tables appear in no other test
    table = mv.create_table("matrix", 72, 300, np.float32)
    t0 = time.perf_counter()
    for _ in range(3):
        table.add(np.ones((len(IDS), 300), np.float32), row_ids=IDS)
        table.get(IDS)
    run = _served_run(t0)
    share = _metric("pallas_row_share", run)
    carried = _metric("row_descriptor_bytes", run)
    if kernel == "pallas":
        assert share == 100.0 and carried == 1536.0
    else:
        assert share == 0.0 and carried is None
    # the parent's records: the same window with the three fields gone
    trace = op_trace.of(run)
    bare = SimpleNamespace(
        window=run.window,
        _op_trace=op_trace.Trace(
            [SimpleNamespace(**{k: v for k, v in r._asdict().items()
                                if k not in ("path", "descriptors", "bytes")})
             for r in trace.records], trace.t0_ns, trace.t1_ns))
    assert _metric("pallas_row_share", bare) is None
    assert _metric("row_descriptor_bytes", bare) is None
    mv.shutdown()


def _reduction(raw_ops):
    """What `trace_reduce.Reduction` gives a reader, as far as the wide
    row readers look."""
    import re

    from benchmark import trace_reduce

    def ops_matching(pattern):
        rx = re.compile(pattern)
        return [(k, n, s) for k, (n, s) in raw_ops.items()
                if rx.search(trace_reduce.short_name(k))]
    return SimpleNamespace(raw_ops=raw_ops, ops_matching=ops_matching)


def test_wide_row_readers_count_the_rows_named_at_300_columns():
    """The scatter's and the gather's share of the roofline on a table of
    three lane tiles, by hand: 10 Adds and 10 Gets of 100,000 rows of 300
    float32 columns; event names as the v5e's trace gives them."""
    scatter = ("%_scatter_add_call.1 = f32[375001,3,8,128]{3,2,1,0:T(8,128)} "
               "custom-call(s32[131072]{0:T(1024)} %ids.1, "
               "f32[100000,300]{1,0:T(8,128)} %copy.1, "
               "f32[375001,3,8,128]{3,2,1,0:T(8,128)} %bitcast.1), "
               "custom_call_target=\"tpu_custom_call\"")
    gather = ("%fusion = f32[131072,384]{1,0:T(8,128)} fusion("
              "f32[3000008,384]{1,0:T(8,128)} %data.1, "
              "s32[131072]{0:T(1024)S(1)} %fusion.1), kind=kCustom")
    other = ("%copy.1 = f32[100000,300]{1,0:T(8,128)} copy("
             "f32[100000,300]{0,1:T(8,128)} %deltas.1)")
    run = SimpleNamespace(
        trace=_reduction({scatter: [10, 0.028], gather: [10, 0.020],
                          other: [10, 0.004]}),
        result={"add_rows": 1_000_000, "get_rows": 1_000_000,
                "row_cols": 300},
        peaks={"hbm_bytes_per_s": 819e9})
    assert _metric("wide_scatter_device_ms", run) == pytest.approx(2.8)
    assert _metric("wide_gather_device_ms", run) == pytest.approx(2.0)
    # 3 x 1,000,000 x 300 x 4 B = 3.6 GB in 28 ms = 128.6 GB/s of 819
    assert _metric("wide_scatter_roofline", run) == pytest.approx(
        100 * 3.6e9 / 0.028 / 819e9)
    # 2 x 1,000,000 x 300 x 4 B = 2.4 GB in 20 ms
    assert _metric("wide_gather_roofline", run) == pytest.approx(
        100 * 2.4e9 / 0.020 / 819e9)
    # fewer slots in the trace than rows named: part of the work is missing
    run.result["add_rows"] = run.result["get_rows"] = 1_400_000
    with pytest.raises(ValueError, match="part of the work"):
        _metric("wide_scatter_roofline", run)
    with pytest.raises(ValueError, match="part of the work"):
        _metric("wide_gather_roofline", run)
    # no traced run, or a trace without the programs: nothing to read
    run.trace = None
    assert _metric("wide_scatter_roofline", run) is None
    assert _metric("wide_gather_device_ms", run) is None
    run.trace = _reduction({other: [10, 0.004]})
    assert _metric("wide_gather_roofline", run) is None
    assert _metric("wide_scatter_device_ms", run) is None


# -- a table sharded over chips: routing, and what a launch record carries ----

def test_sharded_launch_records_and_their_readers(tracing, monkeypatch):
    """On a table sharded over four devices a row op that sends ids up
    counts them by shard in a TABLE_ROW_ROUTE inside its TABLE_ROW_PREP
    (`n` = the ids named; the chip does the rest of the routing): the
    dispatcher sends the ids up there, a device-path op's too (no
    WORKER_ROW_IDS on a mesh), padded to a Get's step of the bucket, the
    sentinel last, and the row plan keeps them: an op that names the rows
    of the op before it routes nothing and its TABLE_ROW_PREP says `bytes`
    0. Every TABLE_ROW_PREP keeps `n` = rows named; its TABLE_ROW_LAUNCH
    says who sent the ids (`ids_from`) and carries the shards, the slots
    launched over all of them, the fullest shard's and the bytes of rows
    that crossed chips, hit or miss
    (on one device the caller sends them up: WORKER_ROW_IDS, `caller`).
    `shard_slots_share`,
    `shard_exchange_bytes_share` and `shard_row_imbalance` read them; a
    program whose records carry no `shards` (the parent's ring) gives None,
    and so does a one-shard table."""
    import jax

    from multiverso_tpu.ops import pallas_rows
    from multiverso_tpu.tables import matrix_table
    from multiverso_tpu.tables.matrix_table import _live_slots

    monkeypatch.setattr(matrix_table, "_use_pallas_scatter",
                        lambda platform, num_shards, *width: True)
    monkeypatch.setattr(pallas_rows, "ROW_GROUP", 8)
    rows, cols, n, shards = 4096, 128, 1600, 4
    rng = np.random.default_rng(30)
    ids = rng.choice(rows, n, replace=False).astype(np.int32)
    delta = jax.device_put(np.ones((n, cols), np.float32))
    for mesh in (shards, 1):
        mv.init(mesh_shape=str(mesh))
        table = mv.create_table("matrix", rows, cols, np.float32)
        t0 = time.perf_counter()
        for _ in range(2):
            table.wait(table.add_device_async(delta, ids))
            table.wait_device(table.get_device_async(ids), ids)
        run = _served_run(t0)
        run.result = {"row_cols": cols}
        trace = op_trace.of(run)
        routes = trace.spans("TABLE_ROW_ROUTE")
        launches = trace.spans("TABLE_ROW_LAUNCH")
        if mesh == 1:
            assert not routes and not any(r.shards for r in launches)
            # on one device the caller sends a device-path op's ids up
            assert [r.ids_from for r in launches] == ["caller"] * 4
            # one bucket-long form for both ops; the same rows again launch
            # on the array the op before sent up: nothing more goes up
            assert [(r.n, r.bytes) for r in trace.spans("WORKER_ROW_IDS")
                    ] == [(n, 4 * 2048)] + [(n, 0)] * 3
            assert [r.n for r in launches] == [n, _live_slots(n, 2048)] * 2
            assert [r.n for r in trace.spans("TABLE_ROW_PREP")] == [n] * 4
            for name in ("shard_slots_share", "shard_exchange_bytes_share",
                         "shard_row_imbalance"):
                assert _metric(name, run) is None
            mv.shutdown()
            continue
        assert [r.n for r in routes] == [n]
        preps = {r.id: r for r in trace.spans("TABLE_ROW_PREP")}
        assert all(r.parent in preps for r in routes)
        assert not trace.spans("WORKER_ROW_IDS")
        assert [(r.n, r.bytes) for r in preps.values()] == [
            (n, 4 * _live_slots(n, 2048))] + [(n, 0)] * 3
        assert _metric("shard_ids_kept_share", run) == 75.0
        assert all(trace._by_id[r.parent].stage in (
            "TABLE_PROCESS_ADD", "TABLE_PROCESS_GET")
            for r in preps.values())
        assert [r.shards for r in launches] == [shards] * 4
        assert [r.ids_from for r in launches] == ["dispatcher"] * 4
        adds, gets = launches[0::2], launches[1::2]
        counts = np.bincount(ids // table._server_table._block_rows,
                             minlength=shards)
        for add in adds:
            assert add.path == "pallas"
            assert add.n == int((-(-counts // 8) * 8).sum())
            assert add.max_shard_n == -(-counts.max() // 8) * 8
            # two waits a whole group, two a slot of a shard's last one
            assert add.waits == int(2 * (counts // 8 + counts % 8).sum())
            assert add.descriptors == 2 * add.n
        segment = gets[0].max_shard_n
        assert gets[0].n == shards * segment and segment > counts.max()
        assert adds[0].exchange_bytes == gets[0].exchange_bytes == (
            3 * segment * cols * 4)
        share = _metric("shard_slots_share", run)
        assert share == pytest.approx(
            100.0 * (adds[0].n + gets[0].n) / (2 * n)) and share <= 115
        assert _metric("shard_exchange_bytes_share", run) == pytest.approx(
            100.0 * 3 * segment / n)
        assert 1.0 <= _metric("shard_row_imbalance", run) <= 1.2
        assert _metric("pallas_row_share.emb128x4", run) == 100.0
        assert _metric("table_op_self_ms.emb128x4", run) > 0
        # the parent's ring: the same records without the new fields
        bare = SimpleNamespace(
            window=run.window, result=run.result,
            _op_trace=op_trace.Trace(
                [SimpleNamespace(**{
                    k: v for k, v in r._asdict().items()
                    if k not in ("shards", "max_shard_n", "exchange_bytes")})
                 for r in trace.records], trace.t0_ns, trace.t1_ns))
        for name in ("shard_slots_share", "shard_exchange_bytes_share",
                     "shard_row_imbalance"):
            assert _metric(name, bare) is None
        mv.shutdown()


def test_sharded_trace_readers_by_chip():
    """The readers of the sharded table's device programs, on a trace in
    plain form built by hand: four chips, ten Adds and ten Gets of 100,000
    rows; event names as the program compiled for a described v5e 2x2 gives
    them. The scatter's and the gather's milliseconds are the slowest
    chip's; the roofline counts the rows named over that chip's time on all
    four chips; the exchange is the time the first chip's
    collective-permutes were in flight, an Add's and a Get's told apart by
    the program they lie in."""
    from benchmark import shard_trace

    scatter = ("%shard_scatter.1 = f32[10000001,128]{1,0:T(8,128)} "
               "custom-call(s32[25664]{0:T(1024)S(1)} %copy-done.1, "
               "s32[1]{0:T(128)S(6)} %copy.10, f32[25664,128]{1,0:T(8,128)} "
               "%select_select_fusion, f32[10000001,128]{1,0:T(8,128)} "
               "%param.5), custom_call_target=\"tpu_custom_call\"")
    gather = ("%fusion = f32[25664,128]{1,0:T(8,128)S(1)} fusion("
              "f32[10000001,128]{1,0:T(8,128)} %param.3, "
              "s32[26624]{0:T(1024)S(1)} %pad_clamp_fusion.1), kind=kCustom, "
              "calls=%fused_computation")
    unroute = ("%fusion.1 = f32[102408,128]{1,0:T(8,128)S(1)} fusion("
               "f32[102656,128]{1,0:T(8,128)S(1)} %dus_fusion, "
               "s32[103424]{0:T(1024)S(1)} %pad_clamp_fusion), kind=kCustom, "
               "calls=%fused_computation.1")
    flight = ("%collective-permute-start.4 = (f32[25664,128]{1,0:T(8,128)}, "
              "f32[25664,128]{1,0:T(8,128)}) collective-permute-start(...), "
              "channel_id=1, source_target_pairs={{0,2}}")
    wait = ("%collective-permute-done.4 = f32[25664,128]{1,0:T(8,128)} "
            "collective-permute-done(...)")

    def plane(chip, events, more=()):
        line, at = [], 1_000
        for name, count, each_ns in events:
            for _ in range(count):
                line.append([name, at, each_ns])
                at += each_ns + 1_000
        return {"name": f"/device:TPU:{chip}",
                "lines": [{"name": "XLA Ops", "events": line}, *more]}

    # the first chip's programs, 2 ms apart: ten Adds whose two transfers
    # overlap (0.3 ms in flight together), ten Gets with one of 0.5 ms
    modules, flights = [], []
    for i in range(10):
        at = 5_000_000 + i * 4_000_000
        modules += [["jit_sharded_row_add(1)", at, 1_500_000],
                    ["jit_sharded_row_get(2)", at + 2_000_000, 1_300_000]]
        flights += [[flight, at + 100_000, 200_000],
                    [flight, at + 200_000, 200_000],
                    [flight, at + 2_100_000, 500_000]]
    first = [{"name": "XLA Modules", "events": modules},
             {"name": "Async XLA Ops", "events": flights}]
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 0, 100_000_000]]}]}
    trace = {"planes": [host] + [
        plane(chip, [(scatter, 10, 600_000 + 50_000 * chip),
                     (gather, 10, 150_000 - 10_000 * chip),
                     (unroute, 10, 400_000), (wait, 20, 300_000)],
              first if chip == 0 else ()) for chip in range(4)]}
    chips = shard_trace.by_chip(trace, 4)
    assert [c.window_s for c in chips] == [pytest.approx(0.1)] * 4
    run = SimpleNamespace(
        trace=chips[0], _shard_trace=chips, _shard_raw=trace, chips=4,
        result={"add_rows": 1_000_000, "adds": 10, "gets": 10, "ops": 20,
                "row_cols": 128},
        peaks={"hbm_bytes_per_s": 819e9})
    assert _metric("shard_scatter_device_ms", run) == pytest.approx(0.75)
    assert _metric("shard_gather_device_ms", run) == pytest.approx(0.15)
    # 3 x 1,000,000 x 128 x 4 B over 7.5 ms on each of four chips
    assert _metric("shard_scatter_roofline", run) == pytest.approx(
        100 * 1.536e9 / (0.0075 * 4) / 819e9)
    assert shard_trace.exchange_in(trace) == {
        "add": [10, pytest.approx(10 * 0.3e-3)],
        "get": [10, pytest.approx(10 * 0.5e-3)]}
    assert _metric("shard_exchange_ms", run) == pytest.approx(
        (10 * 0.3 + 10 * 0.5) / 20)
    run.result["adds"] = 11
    with pytest.raises(ValueError, match="part of the work"):
        _metric("shard_scatter_roofline", run)
    # the parent's trace: XLA's partitioned programs, none of these events
    run._shard_raw = {"planes": [host] + [plane(chip, [(unroute, 3, 1000)])
                                          for chip in range(4)]}
    run._shard_trace = shard_trace.by_chip(run._shard_raw, 4)
    for name in ("shard_scatter_device_ms", "shard_scatter_roofline",
                 "shard_exchange_ms"):
        assert _metric(name, run) is None
    run.trace = None
    del run._shard_trace
    assert _metric("shard_gather_device_ms", run) is None
