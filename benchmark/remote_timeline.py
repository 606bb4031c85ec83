"""A served op tiled across its two processes, from the worker's call to the
worker's return, and the serving process's wait for its interpreter: what the
readers of the client's half of the wire share.

    timeline = remote_timeline.of(run)    # None: see below
    remote_timeline.metric(run, name)     # one number of it, for layers/<name>.py
    remote_timeline.probe(run)            # the INTERP_WAKE_DELAY records' fold

Both are pulled once a run, on `op_trace.of(run)`, and each prints one JSON
line: `{"remote_op_timeline": ...}` and `{"interp_wake_delay": ...}`.

**The tiling.** While the serving process traces, its replies say so (the
frame header's profile bit) and every client records seven instants of each op
its proxies send, posts them back in batches, and the serving process writes
them into its own op trace as six `CLIENT_*` records under the request's
`req_id` (`multiverso_tpu/runtime/remote.py`; `docs/observability.md` 2.3).
`perf_counter_ns` is one clock for the processes of a host, so the client's
instants and the server's lie on one line, and eleven of them cut a served op
into ten tiles that are contiguous by construction and sum to `CLIENT_OP`:

    submit       call -> sent                       the client's thread: argument
                                                    work, encode, the send
    wire_out     sent -> the request's              see "overlap" below
                 NET_FRAME_READ begins
    frame_in     -> net_recv                        the server's receive thread:
                                                    read, check and copy
    residence    -> reply_sent                      `op_trace.Request`'s four
                                                    parts (`residence_parts_ms`)
    send         -> the end of the NET_SEND
                 under the op's WIRE_REPLY
    wire_back    -> reply_header                    see "overlap" below
    reply_read   -> reply_msg                       the client's receive thread,
                                                    its mailbox, the pump's wake
    reply_decode -> done                            the pump: `wire.decode`
    wake         -> woken                           the caller's thread runs again
    ret          -> ret                             `process_reply_get`, the return

**Overlap.** A frame's header is at the other side while its tail is still
being sent: the server's NET_FRAME_READ begins before the client's send of a
512 KB Add has returned, and a client has a Get's reply header before the
server's NET_SEND ends. `wire_out` and `wire_back` are then negative by the
time the two sides spent on the same frame at once, the sum stays exact, and
the line says how often and by how much (`overlap`). The metrics that cross
the boundary add the tiles on both sides of it and are unaffected. What can
NOT happen on one clock: a request's header arriving before its op was called,
a reply's before the server stamped `reply_sent`. Those are counted
(`causality_breaks`), and past 1% of the joined ops nothing is reported.

`of` gives None, and never raises, where the run was not traced, on a program
that writes no `CLIENT_OP` (the parent of the PR that brought it), where under
`JOINED_FLOOR` percent of the window's served ops (an `op` with a `reply_sent`)
joined, and where causality broke.
"""

import json
import statistics

from benchmark import common, op_trace
from benchmark.op_trace import NS_PER_MS

TILES = ("submit", "wire_out", "frame_in", "residence", "send", "wire_back",
         "reply_read", "reply_decode", "wake", "ret")
CLIENT_STAGES = ("CLIENT_OP", "CLIENT_SUBMIT", "CLIENT_REPLY_READ",
                 "CLIENT_REPLY_DECODE", "CLIENT_WAKE", "CLIENT_RETURN")
# what an op's eleven instants are read from: the client's six, the server's four
JOINED_STAGES = CLIENT_STAGES + ("NET_FRAME_READ", "net_recv", "reply_sent",
                                 "WIRE_REPLY")
JOINED_FLOOR = 90.0          # percent of the served ops that must join
CAUSALITY_CEILING = 0.01     # share of the joined ops that may break it
PROBE = "INTERP_WAKE_DELAY"


def _end(record):
    return record.start_ns + record.dur_ns


def _fold(values_ns):
    return {"mean": statistics.fmean(values_ns) / NS_PER_MS,
            "median": statistics.median(values_ns) / NS_PER_MS,
            "p95": common.percentile(values_ns, 95) / NS_PER_MS}


def _first_by_op(trace, stages):
    """{(op, stage): the first record of it in the window}."""
    first = {}
    for stage in stages:
        for r in trace.spans(stage):
            first.setdefault((r.op, stage), r)
    return first


def boundaries(trace):
    """{op: (kind, worker, the eleven instants, whether the client's six
    records are contiguous among themselves)} for every op of the window
    whose records give all eleven, and the number of served ops."""
    first = _first_by_op(trace, JOINED_STAGES)
    served = [op for (op, stage) in first if stage == "reply_sent"]
    out = {}
    for op in served:
        found = [first.get((op, stage)) for stage in JOINED_STAGES]
        if None in found:
            continue
        (whole, submit, read, decode, wake, ret, frame, arrived, replied,
         reply) = found
        sends = [r for r in trace.children(reply.id) if r.stage == "NET_SEND"]
        if not sends:
            continue
        contiguous = (submit.start_ns == whole.start_ns
                      and decode.start_ns == _end(read)
                      and ret.start_ns == _end(wake)
                      and _end(ret) == _end(whole))
        out[op] = (whole.path, whole.worker, (
            whole.start_ns, _end(submit), frame.start_ns, arrived.start_ns,
            replied.start_ns, _end(sends[0]), read.start_ns, _end(read),
            _end(decode), _end(wake), _end(whole)), contiguous)
    return out, len(served)


def timeline(trace, op_ms=None, ring_size=None):
    """The line of one traced window, None as the module says. `op_ms`: the
    driver's own samples of the same ops, {"add": [...], "get": [...]} in
    milliseconds on the workers' clocks."""
    found = _timeline(trace, op_ms, ring_size)
    return None if found is None or "refused" in found else found


def _timeline(trace, op_ms, ring_size):
    """`timeline`, with the counts and the reason (`refused`) where it gives
    None over a program that does record."""
    if not trace.spans("CLIENT_OP"):
        return None
    ops, served = boundaries(trace)
    joined_share = 100.0 * len(ops) / served if served else 0.0
    out = {"served": served, "joined": len(ops), "joined_share": joined_share}
    if joined_share < JOINED_FLOOR:
        return dict(out, refused="joined_share")
    kinds, breaks, unsummed = {}, 0, 0
    overlap = {"wire_out": [], "wire_back": []}
    by_worker = {}
    for kind, worker, at, contiguous in ops.values():
        tiles = [b - a for a, b in zip(at, at[1:])]
        # the tiles telescope; what can fail is the carried records' own
        # boundaries disagreeing
        unsummed += not contiguous
        # a header before its op's call, a reply's before `reply_sent`
        breaks += at[2] < at[0] or at[6] < at[4]
        slot = kinds.setdefault(kind, {name: [] for name in TILES + (
            "client_op", "server_side")})
        for name, tile in zip(TILES, tiles):
            slot[name].append(tile)
            if name in overlap and tile < 0:
                overlap[name].append(tile)
        slot["client_op"].append(at[-1] - at[0])
        slot["server_side"].append(at[5] - at[2])   # frame_in .. send
        by_worker.setdefault(worker, []).append((at[0], at[-1]))
    out["causality_breaks"] = breaks
    out["tiles_unsummed"] = unsummed
    if breaks > CAUSALITY_CEILING * len(ops):
        return dict(out, refused="causality")
    out["overlap"] = {
        name: {"count": len(found), "share": 100.0 * len(found) / len(ops),
               "min_ms": min(found) / NS_PER_MS if found else 0.0}
        for name, found in overlap.items()}
    parts = {q.op: q for q in trace.requests()}
    out["ops"] = {}
    for kind, slot in sorted(kinds.items()):
        entry = {"count": len(slot["client_op"]),
                 "client_op_ms": _fold(slot["client_op"]),
                 "tiles_ms": {name: _fold(slot[name]) for name in TILES}}
        entry["sum_of_mean_tiles_ms"] = sum(
            t["mean"] for t in entry["tiles_ms"].values())
        entry["client_half_ms"] = (entry["client_op_ms"]["mean"]
                                   - statistics.fmean(slot["server_side"])
                                   / NS_PER_MS)
        mine = [parts[op] for op, found in ops.items()
                if found[0] == kind and op in parts]
        if mine:
            entry["residence_parts_ms"] = {
                part: statistics.fmean(getattr(q, part) for q in mine)
                for part in op_trace.Request.__slots__[1:5]}
        if op_ms and op_ms.get(kind):
            entry["driver_op_median_ms"] = statistics.median(op_ms[kind])
        out["ops"][kind] = entry
    everything = {name: [t for slot in kinds.values() for t in slot[name]]
                  for name in TILES + ("client_op", "server_side")}
    out["all"] = {"tiles_ms": {name: _fold(everything[name])
                               for name in TILES},
                  "client_op_ms": _fold(everything["client_op"])}
    out["client_half_ms"] = (out["all"]["client_op_ms"]["mean"]
                             - statistics.fmean(everything["server_side"])
                             / NS_PER_MS)
    # the lists of `everything` are in one order: tiles add up op by op
    out["metrics"] = {
        name: _fold([sum(tiles) for tiles in zip(
            *(everything[tile] for tile in names))])[fold]
        for name, (fold, names) in METRICS.items()}
    think = [b[0] - a[1] for spans in by_worker.values()
             for a, b in zip(sorted(spans), sorted(spans)[1:])]
    out["think_ms"] = statistics.fmean(think) / NS_PER_MS if think else None
    out["workers"] = len(by_worker)
    if ring_size:
        out["ring_occupancy"] = 100.0 * len(trace.records) / ring_size
    return out


def of(run):
    if not hasattr(run, "_remote_timeline"):
        run._remote_timeline = _pull(run)
    return run._remote_timeline


def _pull(run):
    trace = op_trace.of(run)
    if trace is None:
        return None
    from multiverso_tpu.dashboard import RING
    found = _timeline(trace, run.result.get("op_ms"),
                      getattr(RING, "size", None))
    if found is not None:
        print(json.dumps({"remote_op_timeline": found}), flush=True)
    return None if found is None or "refused" in found else found


METRICS = {
    # metric: (fold, the tiles added up op by op)
    "client_submit_ms": ("mean", ("submit",)),
    "wire_out_ms": ("median", ("wire_out", "frame_in")),
    "wire_back_ms": ("median", ("send", "wire_back")),
    "client_reply_ms": ("mean", ("reply_read", "reply_decode", "wake",
                                 "ret")),
}


def metric(run, name):
    """One of `METRICS` over every joined op of the window, Adds and Gets
    together; None where there is no timeline."""
    found = of(run)
    return found and found["metrics"][name]


def probe(run):
    """The fold of the window's INTERP_WAKE_DELAY records (how long after its
    timer a sleeping thread of the serving process ran Python again, fifty a
    second while it traces), pulled once and printed; None where the program has no probe."""
    if not hasattr(run, "_interp_probe"):
        trace = op_trace.of(run)
        run._interp_probe = trace and probe_fold(trace.spans(PROBE))
        if run._interp_probe:
            print(json.dumps({"interp_wake_delay": run._interp_probe}),
                  flush=True)
    return run._interp_probe


def probe_fold(records):
    if not records:
        return None
    late = [r.dur_ns / NS_PER_MS for r in records]
    return {"count": len(late), "mean_ms": statistics.fmean(late),
            "median_ms": statistics.median(late),
            "p95_ms": common.percentile(late, 95), "max_ms": max(late),
            "over_1ms_share": 100.0 * sum(x > 1.0 for x in late) / len(late)}
