"""A served op across two processes (docs/observability.md 2.3): the profile
bit on a tracing server's replies, the client's half recorded where it happens
and carried into the serving process's op trace, the ten tiles
`benchmark/remote_timeline.py` cuts an op into, its five readers, and the
interpreter probe a dispatcher's process runs while it traces."""

import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark import common, op_trace, remote_timeline
from multiverso_tpu import dashboard
from multiverso_tpu.dashboard import RING, Dashboard
from multiverso_tpu.runtime import net as net_mod
from multiverso_tpu.runtime import remote as remote_mod
from multiverso_tpu.runtime.message import Message, MsgType, next_msg_id
from multiverso_tpu.runtime.zoo import Zoo
from multiverso_tpu.tables.base import Completion

ROWS, COLS = 64, 4
IDS = np.arange(8, dtype=np.int32)
CLIENT_STAGES = remote_timeline.CLIENT_STAGES
MS = 1_000_000


@pytest.fixture
def tracing():
    mv.set_flag("profile_annotations", True)
    Dashboard.profile_annotations = True
    yield
    Dashboard.profile_annotations = False


def _serve(**flags):
    mv.init(remote_workers=2, **flags)
    table = mv.create_table("matrix", ROWS, COLS,
                            init_value=np.zeros((ROWS, COLS), np.float32))
    return table, mv.serve("127.0.0.1:0")


def _pairs(proxy, n):
    for _ in range(n):
        proxy.add(np.ones((len(IDS), COLS), np.float32), row_ids=IDS)
        proxy.get(IDS)


def _received(want, seconds=10.0):
    """Wait until the serving process has taken `want` ops' records."""
    limit = time.monotonic() + seconds
    while Dashboard.counter_value("CLIENT_SPANS_RECEIVED") < want:
        assert time.monotonic() < limit, (
            want, Dashboard.counter_value("CLIENT_SPANS_RECEIVED"))
        time.sleep(0.01)
    return Dashboard.counter_value("CLIENT_SPANS_RECEIVED")


def _window(t0):
    """The ring from t0 to now, behind the dispatcher and the finisher."""
    zoo = Zoo.instance()
    zoo.server.run_serialized(lambda: None)
    t1 = time.perf_counter()
    records, lost = RING.window(t0, t1)
    assert not lost
    return op_trace.Trace(records, int(t0 * 1e9), int(t1 * 1e9))


# -- two processes --------------------------------------------------------------

def test_a_child_clients_half_lands_in_the_servers_ring(tracing):
    """The client's switch is OFF and the server's on: every op the child
    sends (the reply to its registration told it so) leaves its six CLIENT_* records
    under the server's req_ids in the SERVER's ring, none in its own, and
    the ten tiles sum to CLIENT_OP to the nanosecond."""
    table, endpoint = _serve()
    child = os.path.join(os.path.dirname(__file__),
                         "remote_timeline_child.py")
    root = os.path.dirname(os.path.dirname(os.path.abspath(child)))
    env = {k: v for k, v in os.environ.items()
           if k != "MV_PROFILE_ANNOTATIONS"}
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    pairs = 20
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, child, endpoint, str(table.table_id), str(pairs)],
        capture_output=True, text=True, timeout=180, env=env)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    said = [line for line in proc.stdout.splitlines()
            if line.startswith("worker ")][0].split()
    worker, kept_by_child = int(said[1]), int(said[3])
    assert kept_by_child == 0
    # 32 posted after every 16th op, the last 8 at close()
    assert _received(2 * pairs) == 2 * pairs
    trace = _window(t0)
    served = {r.op for r in trace.spans("reply_sent")}
    for stage in CLIENT_STAGES:
        records = trace.spans(stage)
        assert len(records) == 2 * pairs, stage
        assert {r.op for r in records} <= served
        assert {r.worker for r in records} == {worker}
        assert all(r.id == 0 and r.parent == 0 for r in records)
    assert {r.path for r in trace.spans("CLIENT_OP")} == {"add", "get"}
    ops, n_served = remote_timeline.boundaries(trace)
    assert (len(ops), n_served) == (2 * pairs, 2 * pairs)
    by_op = {r.op: r for r in trace.spans("CLIENT_OP")}
    for op, (kind, who, at, contiguous) in ops.items():
        tiles = [b - a for a, b in zip(at, at[1:])]
        assert len(tiles) == len(remote_timeline.TILES)
        assert sum(tiles) == by_op[op].dur_ns and contiguous
        assert at[2] >= at[0] and at[6] >= at[4]   # causality, one clock
    found = remote_timeline.timeline(trace, ring_size=RING.size)
    assert found["joined_share"] == 100.0
    assert found["causality_breaks"] == found["tiles_unsummed"] == 0
    for kind in ("add", "get"):
        entry = found["ops"][kind]
        assert entry["sum_of_mean_tiles_ms"] == pytest.approx(
            entry["client_op_ms"]["mean"])
    assert found["workers"] == 1 and found["think_ms"] >= 0
    assert 0 < found["ring_occupancy"] < 100


# -- the bit ----------------------------------------------------------------------

def _channel_bytes(monkeypatch):
    """Every frame this process frames from now on: (type, channel byte)."""
    seen = []
    framed = net_mod.TcpNet._frame_segments

    def spy(self, msg, channel):
        segments, nbytes = framed(self, msg, channel)
        seen.append((msg.type, segments[0][5]))
        return segments, nbytes

    monkeypatch.setattr(net_mod.TcpNet, "_frame_segments", spy)
    return seen


@pytest.mark.parametrize("switch", ["off", "on", "late"])
def test_the_profile_bit_follows_the_servers_switch(switch, monkeypatch):
    """Off on both sides: no stamp, no post, bit 6 clear on every frame.
    On: bit 6 on the replies to correlated requests alone, and the client
    posts what it records from the reply that told it: to its
    registration, or, where the switch went on after that (the benchmark's
    traced run), to the first op of the window, which is not posted."""
    if switch == "on":
        mv.set_flag("profile_annotations", True)
    seen = _channel_bytes(monkeypatch)
    table, endpoint = _serve()
    client = mv.remote_connect(endpoint)
    proxy = client.table(table.table_id)
    if switch == "late":
        Dashboard.profile_annotations = True
    began = []
    monkeypatch.setattr(remote_mod._ClientOp, "__init__",
                        lambda self, c, f=remote_mod._ClientOp.__init__:
                        (began.append(1), f(self, c))[1])
    _pairs(proxy, 3)
    client.close()
    replies = [b for t, b in seen if t in (MsgType.Reply_Add,
                                           MsgType.Reply_Get)]
    requests = [b for t, b in seen if t in (MsgType.Request_Add,
                                            MsgType.Request_Get)]
    assert len(replies) == len(requests) == 6
    assert not any(b & 0x40 for b in requests)
    if switch == "off":
        assert not any(b & 0x40 for _, b in seen)
        assert not began and not client._spans
        assert not client._server_records
        assert Dashboard.counter_value("CLIENT_SPANS_RECEIVED") == 0
        assert MsgType.Control_Client_Spans not in [t for t, _ in seen]
    else:
        # one process here: "late" turned the client's OWN switch on too,
        # so its first op was recorded as well, and had the server's ask
        # (its own reply) by the time it ended
        assert all(b & 0x40 for b in replies)
        assert client._server_records and len(began) == 6
        assert _received(6) == 6
    Dashboard.profile_annotations = False


def test_an_unmarked_reply_says_nothing_of_the_servers_switch(tracing):
    """An error built outside a completion carries no bit while the server
    records: the client goes on recording. A marked reply without the bit
    ends it."""
    table, endpoint = _serve()
    client = mv.remote_connect(endpoint)
    proxy = client.table(table.table_id)
    assert client._server_records   # the registration's reply said so
    completion = Completion()
    client._send(0, MsgType.Control_Layout, None, next_msg_id(), completion)
    with pytest.raises(RuntimeError, match="no shard layout"):
        completion.wait(10)
    assert client._server_records
    Dashboard.profile_annotations = False
    proxy.get(IDS)
    assert not client._server_records
    client.close()


def test_the_profile_bit_survives_the_shm_transport(tracing):
    """The ring carries the TCP framing, so the bit with it; the batch
    posted after the 16th op rides the ring like any frame. (What is posted
    at close() may die with the ring, as a Control_Deregister may: the
    ring is closed under its reader.)"""
    table, endpoint = _serve(wire_shm=True, heartbeat_seconds=0,
                             request_retry_seconds=0)
    client = mv.remote_connect(endpoint)
    t0 = time.perf_counter()
    _pairs(client.table(table.table_id), 9)
    assert Dashboard.counter_value("SHM_RX_FRAMES") > 0
    assert client._server_records
    # behind the post in the ring: the 17th and 18th op were answered
    assert Dashboard.counter_value("CLIENT_SPANS_RECEIVED") == 16
    assert len({r.op for r in _window(t0).spans("CLIENT_OP")}) == 18
    client.close()


def test_a_reply_frame_carries_both_ride_along_bits():
    """Bit 6 beside the trace flag's bit 7, the channel under them."""
    net = net_mod.TcpNet()
    for trace, profile in ((False, False), (True, False), (False, True),
                           (True, True)):
        frame = net._frame(Message(src=0, dst=1, type=MsgType.Reply_Add,
                                   msg_id=7, trace=trace, profile=profile), 1)
        assert frame[5] == 1 | (0x80 if trace else 0) | (
            0x40 if profile else 0)
        chunks = [frame]
        msg = net._read_frame(
            lambda n: (chunks.append(chunks.pop()[n:]) or frame)[
                len(frame) - len(chunks[-1]) - n:len(frame) - len(chunks[-1])],
            set())
        assert (msg.trace, msg.profile, msg._wire_channel) == (
            trace, profile, 1)
        assert msg.recv_ns > 0
    net.finalize()


# -- the way to the server ---------------------------------------------------------

def test_a_batch_on_a_foreign_clock_is_counted_and_dropped(tracing):
    """A client on another host, or of another boot: its perf_counter is
    not this process's, and both wall clocks say so. A malformed batch (a
    row too short, an op kind that names none) is dropped too, and the
    serve thread lives."""
    table, endpoint = _serve(heartbeat_seconds=0, request_retry_seconds=0)
    client = mv.remote_connect(endpoint)
    t0 = time.perf_counter()
    now = time.perf_counter_ns()
    row = [12345, now, now + 1, now + 2, now + 3, now + 4, now + 5, now + 6,
           64, 0]
    away = np.array([now, time.time_ns() + 10 ** 10], np.int64)
    here = np.array([now, time.time_ns()], np.int64)

    def post(rows, clock):
        client._net.send(Message(
            src=client.worker_id, dst=0, type=MsgType.Control_Client_Spans,
            msg_id=next_msg_id(), data=[np.array(rows, np.int64), clock]))

    post([row], away)
    post([row[:5]], here)
    post([row, row[:-1] + [3]], here)
    post([row[:-1] + [-1]], here)
    proxy = client.table(table.table_id)
    proxy.get(IDS)   # behind both on the one connection
    assert Dashboard.counter_value("CLIENT_SPANS_FOREIGN_CLOCK") == 1
    assert Dashboard.counter_value("CLIENT_SPANS_RECEIVED") == 0
    post([row], here)
    assert _received(1) == 1
    ours = [r for r in _window(t0).records if r.op == 12345]
    assert sorted(r.stage for r in ours) == sorted(CLIENT_STAGES)
    assert {r.worker for r in ours} == {client.worker_id}
    client.close()


@pytest.mark.parametrize("by", ["maintenance", "close"])
def test_the_last_ops_of_a_burst_arrive(by, tracing):
    """Fewer ops than a post takes: the maintenance thread posts a batch
    older than its tick; a client without one posts at close()."""
    beats = 0.2 if by == "maintenance" else 0
    table, endpoint = _serve(heartbeat_seconds=beats,
                             request_retry_seconds=0)
    client = mv.remote_connect(endpoint)
    _pairs(client.table(table.table_id), 2)
    if by == "maintenance":
        assert _received(4, seconds=5.0) == 4
        assert not client._spans
    else:
        time.sleep(0.3)
        assert Dashboard.counter_value("CLIENT_SPANS_RECEIVED") == 0
        assert len(client._spans) == 4
    client.close()
    assert _received(4) == 4


def test_a_retransmitted_op_keeps_its_first_call_and_sent(tracing):
    mv.set_flag("fault_spec", "drop:type=Request_Add,first=1")
    mv.set_flag("fault_seed", 7)
    table, endpoint = _serve(request_retry_seconds=0.3)
    client = mv.remote_connect(endpoint)
    proxy = client.table(table.table_id)
    t0 = time.perf_counter()
    proxy.add(np.ones((len(IDS), COLS), np.float32), row_ids=IDS)
    client.close()
    assert Dashboard.counter_value("CLIENT_RETRIES") >= 1
    assert Dashboard.counter_value("CLIENT_SPANS_RETRIED") == 1
    trace = _window(t0)
    whole, submit = (trace.spans(s)[0] for s in ("CLIENT_OP",
                                                 "CLIENT_SUBMIT"))
    assert whole.start_ns == submit.start_ns
    assert whole.dur_ns > 0.25e9 > submit.dur_ns   # sent before the resend


def test_a_failed_op_leaves_client_op_alone(tracing):
    table, endpoint = _serve()
    client = mv.remote_connect(endpoint)
    proxy = client.table(table.table_id)
    proxy.get(IDS)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="server-side failure"):
        # past the table: the proxy's own check skipped, the server refuses
        with proxy._public_op():
            proxy.wait(proxy._submit(
                MsgType.Request_Get,
                (np.array([ROWS + 5], np.int32), None)))
    client.close()
    ours = [r.stage for r in _window(t0).records
            if r.stage.startswith("CLIENT_") and r.worker >= 0]
    assert set(ours) == {"CLIENT_OP"}


# -- the readers, on a made-up trace ------------------------------------------------

def _made_up_op(op, kind, worker, at, base_id):
    """A served op's records from its eleven instants (milliseconds)."""
    (call, sent, frame, arrived, replied, send_end, header, msg, done,
     woken, ret) = (int(x * MS) for x in at)
    rows = [
        (0, 0, "CLIENT_OP", call, ret - call, op),
        (0, 0, "CLIENT_SUBMIT", call, sent - call, op),
        (0, 0, "CLIENT_REPLY_READ", header, msg - header, op),
        (0, 0, "CLIENT_REPLY_DECODE", msg, done - msg, op),
        (0, 0, "CLIENT_WAKE", done, woken - done, op),
        (0, 0, "CLIENT_RETURN", woken, ret - woken, op),
        (base_id, 0, "NET_FRAME_READ", frame, MS // 10, op),
        (0, 0, "net_recv", arrived, 0, op),
        (base_id + 1, 0, "WIRE_REPLY", replied - MS // 10,
         send_end - replied + MS // 5, op),
        (0, base_id + 1, "reply_sent", replied, 0, op),
        (base_id + 2, base_id + 1, "NET_SEND", replied + MS // 20,
         send_end - replied - MS // 20, op),
    ]
    return [dashboard.OpRecord._make((0, i, p, stage, start, dur, 0, o))
            ._replace(path=kind if stage == "CLIENT_OP" else "",
                      worker=worker if stage.startswith("CLIENT_") else -1)
            for i, p, stage, start, dur, o in rows]


# an Add whose 512 KB the server reads while the client still sends, and a
# Get whose reply header the client has while the server still sends
ADD_AT = (0.0, 0.5, 0.4, 1.0, 9.0, 9.2, 9.3, 9.6, 9.7, 9.8, 10.0)
GET_AT = (20.0, 20.2, 20.3, 20.5, 30.5, 31.5, 30.7, 32.0, 32.4, 32.5, 32.6)


def _made_up(*ops, extra=()):
    records = [r for op in ops for r in op] + list(extra)
    records = [r._replace(seq=k) for k, r in enumerate(records)]
    return SimpleNamespace(
        _op_trace=op_trace.Trace(records, 0, 100 * MS),
        result={"op_ms": {"add": [10.0], "get": [12.5]}})


def _probe_records(late_ms):
    return [dashboard.OpRecord._make((0, 0, 0, "INTERP_WAKE_DELAY",
                                      k * 20 * MS, int(x * MS), 0, 0))
            for k, x in enumerate(late_ms)]


def _served_only(op, at):
    """The parent's program: the server's records, no client half."""
    return [r for r in _made_up_op(op, "get", 0, at, op * 10)
            if not r.stage.startswith("CLIENT_")]


READER_RUNS = {
    "two_ops": lambda: _made_up(
        _made_up_op(11, "add", 3, ADD_AT, 110),
        _made_up_op(12, "get", 3, GET_AT, 120),
        extra=_probe_records([0.1, 0.3, 2.0, 0.0])),
    "parent": lambda: _made_up(_served_only(11, ADD_AT),
                               _served_only(12, GET_AT)),
}


@pytest.mark.parametrize("name, expected", [
    # mean submit: (0.5 + 0.2) / 2
    ("client_submit_ms", {"two_ops": 0.35, "parent": None}),
    # median sent -> net_recv: (0.5, 0.3)
    ("wire_out_ms", {"two_ops": 0.4, "parent": None}),
    # median reply_sent -> reply_header: (0.3, 0.2)
    ("wire_back_ms", {"two_ops": 0.25, "parent": None}),
    # mean reply_header -> ret: (0.7 + 1.9) / 2
    ("client_reply_ms", {"two_ops": 1.3, "parent": None}),
    # median of the probe's four wakes
    ("interp_wake_delay_ms", {"two_ops": 0.2, "parent": None}),
])
def test_the_five_readers_on_a_made_up_trace(name, expected, capsys):
    for which, want in expected.items():
        got = common.load_module("layers", name).read(READER_RUNS[which]())
        assert got == (want if want is None else pytest.approx(want)), which
    capsys.readouterr()


def test_the_timeline_line_of_a_made_up_trace(capsys):
    found = remote_timeline.of(READER_RUNS["two_ops"]())
    assert (found["served"], found["joined"], found["workers"]) == (2, 2, 1)
    add, get = found["ops"]["add"], found["ops"]["get"]
    assert add["tiles_ms"]["wire_out"]["mean"] == pytest.approx(-0.1)
    assert get["tiles_ms"]["wire_back"]["mean"] == pytest.approx(-0.8)
    assert found["overlap"]["wire_out"] == {
        "count": 1, "share": 50.0, "min_ms": pytest.approx(-0.1)}
    assert found["causality_breaks"] == 0
    assert add["client_op_ms"]["median"] == pytest.approx(10.0)
    assert (add["driver_op_median_ms"], get["driver_op_median_ms"]) == (
        10.0, 12.5)
    # CLIENT_OP less [frame_in .. send]: 10 - 8.8 and 12.6 - 11.2
    assert add["client_half_ms"] == pytest.approx(1.2)
    assert get["client_half_ms"] == pytest.approx(1.4)
    assert found["think_ms"] == pytest.approx(10.0)   # ret 10 -> call 20
    assert add["sum_of_mean_tiles_ms"] == pytest.approx(10.0)
    printed = capsys.readouterr().out
    assert printed.count('{"remote_op_timeline"') == 1
    probe = remote_timeline.probe(READER_RUNS["two_ops"]())
    assert probe == {"count": 4, "mean_ms": pytest.approx(0.6),
                     "median_ms": pytest.approx(0.2),
                     "p95_ms": pytest.approx(1.745), "max_ms": 2.0,
                     "over_1ms_share": 25.0}


@pytest.mark.parametrize("why", ["joined_share", "causality"])
def test_a_timeline_that_cannot_be_trusted_gives_none(why, capsys):
    """Under 90% of the served ops joined; a reply's header at the client
    before the server stamped `reply_sent`."""
    shift = lambda at, by: tuple(x + by for x in at)  # noqa: E731
    if why == "joined_share":
        ops = [_made_up_op(k, "get", 3, shift(GET_AT, 40 * k - 20), k * 10)
               for k in range(1, 9)]
        ops += [_served_only(k, shift(GET_AT, 40 * k - 20)) for k in (9, 10)]
    else:
        early = GET_AT[:6] + (30.4,) + GET_AT[7:]
        ops = [_made_up_op(1, "get", 3, early, 10)]
    run = _made_up(*ops)
    run._op_trace = op_trace.Trace(run._op_trace.records, 0, 1000 * MS)
    assert remote_timeline.of(run) is None
    assert f'"refused": "{why}"' in capsys.readouterr().out
    for name in remote_timeline.METRICS:
        assert common.load_module("layers", name).read(run) is None


# -- the interpreter probe -----------------------------------------------------------

def _probes():
    return [t for t in threading.enumerate()
            if t.name == "mv-interp-probe" and t.is_alive()]


def _probe_window(seconds):
    t0 = time.perf_counter()
    time.sleep(seconds)
    records, _ = RING.window(t0, time.perf_counter())
    return [r.dur_ns / MS for r in records if r.stage == "INTERP_WAKE_DELAY"]


def test_the_probe_reads_a_holder_of_the_interpreter(tracing):
    """An idle process reads the timer and the scheduler alone; beside a
    thread that spins 3 ms at a time the probe waits for the lock."""
    mv.init()
    table = mv.create_table("array", 8)
    table.add(np.ones(8, np.float32))   # a drain under the switch
    idle = _probe_window(1.2)
    assert len(idle) >= 30
    assert all(r.id == 0 and r.parent == 0 for r in
               RING.window(0, time.perf_counter())[0]
               if r.stage == "INTERP_WAKE_DELAY")
    stop = threading.Event()

    def hold():
        while not stop.is_set():
            until = time.perf_counter_ns() + 3 * MS
            while time.perf_counter_ns() < until:
                pass
            time.sleep(0)

    holder = threading.Thread(target=hold, daemon=True)
    holder.start()
    try:
        held = _probe_window(1.5)
    finally:
        stop.set()
        holder.join(timeout=10)
    assert not holder.is_alive()
    assert np.median(idle) < 1.0 < np.mean(held), (idle, held)
    # no monitor: a run that is not traced pays nothing for the probe
    assert "INTERP_WAKE_DELAY" not in Dashboard.snapshot()["monitors"]


def test_the_probe_runs_only_under_the_switch():
    """Switch off: no thread, whatever the dispatcher serves. The first
    drain under the switch starts it, the switch going off ends it, and
    the next drain under the switch starts another."""
    mv.init()
    table = mv.create_table("array", 8)
    table.add(np.ones(8, np.float32))
    time.sleep(0.1)
    assert not _probes()
    assert not [r for r in RING.window(0, time.perf_counter())[0]
                if r.stage == "INTERP_WAKE_DELAY"]
    for _ in range(2):
        Dashboard.profile_annotations = True
        try:
            table.add(np.ones(8, np.float32))
            assert len(_probes()) == 1
            assert len(_probe_window(0.3)) >= 5
        finally:
            Dashboard.profile_annotations = False
        limit = time.monotonic() + 5
        while _probes():
            assert time.monotonic() < limit
            time.sleep(0.01)
        assert Zoo.instance().server._probe is None
    mv.shutdown()


def test_the_probe_stops_with_the_dispatcher(tracing):
    mv.init()
    table = mv.create_table("array", 8)
    table.add(np.ones(8, np.float32))
    assert len(_probes()) == 1
    mv.shutdown()
    assert not _probes()
