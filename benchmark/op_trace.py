"""The program's op trace, cut to the benchmark's window: what the per-layer
readers of the dispatcher, the table op and the wire's server half share.

While `Dashboard.profile_annotations` is on (run.py sets it for a traced
run) the program appends one record for every section an op crosses, every
queue wait and every `hop` to a ring in memory, on the `perf_counter_ns`
clock that `run.window` is taken on (`multiverso_tpu/dashboard.py`,
`OpRecord`: seq, id, parent, stage, start_ns, dur_ns, cpu_ns, op, n;
docs/observability.md section 2.1 lists the stages). A reader in
`layers/<metric>.py` asks for what it needs:

    trace = op_trace.of(run)          # None: the program keeps no ring
    trace.spans("WIRE_REPLY")         # the window's records of one stage
    trace.children(span.id)           # the records a span caused
    trace.requests()                  # one Request per served op

`of` pulls the window once per run, raises if the ring overwrote part of it
(a smaller number would be reported as the whole window's) and prints one
JSON line, `{"op_trace": ...}`: every stage's count, mean wall and CPU
milliseconds and summed `n` over the window and for each second of it, so a
level shift inside a window can be laid against the stage that stretched and
against whether its CPU time stretched with it, and the medians and means
of the served requests' parts beside their residence (the means add up to
it; medians of skewed parts do not). A program without the ring
(the parent of the PR that brought it) gives None, and the reader returns
None."""

import json
import statistics

NS_PER_MS = 1e6
KIND_OF = {"SERVER_PROCESS_ADD_MSG": ".add", "TABLE_PROCESS_ADD": ".add",
           "SERVER_PROCESS_GET_MSG": ".get", "TABLE_PROCESS_GET": ".get"}


class Request:
    """One served request's passage through the serving process, in
    milliseconds, tiled so that the parts add up to `residence`: `ingress`
    from the frame's arrival (`net_recv`) to the dispatcher's queue (mailbox
    wait and the serve thread), `queue_wait` until its service begins,
    `service` until its reply begins (for an Add that rode a fused apply:
    the group's merge and apply, and the replies sent before its own),
    `reply` until `reply_sent` (encode and dedup store; the send follows the
    stamp and is in the WIRE_REPLY span, not here)."""

    __slots__ = ("op", "ingress", "queue_wait", "service", "reply",
                 "residence")

    def __init__(self, op, arrived, enqueued, began, replying, sent):
        self.op = op
        self.ingress = (enqueued - arrived) / NS_PER_MS
        self.queue_wait = (began - enqueued) / NS_PER_MS
        self.service = (replying - began) / NS_PER_MS
        self.reply = (sent - replying) / NS_PER_MS
        self.residence = (sent - arrived) / NS_PER_MS


class Trace:
    def __init__(self, records, t0_ns, t1_ns):
        self.records = records
        self.t0_ns, self.t1_ns = t0_ns, t1_ns
        self.window_s = (t1_ns - t0_ns) * 1e-9
        self._by_stage, self._by_parent, self._by_id = {}, {}, {}
        for r in records:
            self._by_stage.setdefault(r.stage, []).append(r)
            if r.parent:
                self._by_parent.setdefault(r.parent, []).append(r)
            if r.id:
                self._by_id[r.id] = r

    def spans(self, stage):
        return self._by_stage.get(stage, [])

    def children(self, span_id):
        return self._by_parent.get(span_id, [])

    def requests(self):
        """A Request for every op whose arrival, enqueue, queue wait, reply
        span and `reply_sent` all lie in the window. Where client and
        server share a process (tests) an op has two `net_recv`; the first
        is the request's arrival."""
        first = {}
        for stage in ("net_recv", "dispatch_enqueue", "reply_sent",
                      "SERVER_QUEUE_WAIT", "WIRE_REPLY"):
            for r in self.spans(stage):
                first.setdefault((r.op, stage), r)
        out = []
        for (op, stage), arrived in first.items():
            if stage != "net_recv":
                continue
            rest = [first.get((op, s)) for s in (
                "dispatch_enqueue", "SERVER_QUEUE_WAIT", "WIRE_REPLY",
                "reply_sent")]
            if None in rest:
                continue
            enqueued, waited, replying, sent = rest
            out.append(Request(op, arrived.start_ns, enqueued.start_ns,
                               waited.start_ns + waited.dur_ns,
                               replying.start_ns, sent.start_ns))
        return out

    def _kind(self, record):
        """'.add' or '.get' for a record inside the serving of an Add or a
        Get (a stage costs the two differently), else ''."""
        spans = self._by_id
        while record is not None:
            if record.stage in KIND_OF:
                return KIND_OF[record.stage]
            record = spans.get(record.parent)
        return ""

    def stage_table(self):
        """{stage: {count, wall_ms, cpu_ms, n}} over the window and the
        same per second of it (means; `n` summed), spans only; a stage
        inside the serving of an Add or a Get is listed as `STAGE.add` or
        `STAGE.get`."""
        seconds = max(1, int(self.window_s + 0.999))

        def cell():
            return {"count": 0, "wall_ms": 0.0, "cpu_ms": 0.0, "n": 0}

        keyed = {}
        for stage, records in self._by_stage.items():
            if stage.isupper():
                for r in records:
                    key = stage if stage in KIND_OF else stage + self._kind(r)
                    keyed.setdefault(key, []).append(r)
        whole, by_second = {}, {}
        for stage, records in sorted(keyed.items()):
            total = whole[stage] = cell()
            rows = by_second[stage] = [cell() for _ in range(seconds)]
            for r in records:
                end = r.start_ns + r.dur_ns - self.t0_ns
                row = rows[min(seconds - 1, int(end * 1e-9))]
                for c in (total, row):
                    c["count"] += 1
                    c["wall_ms"] += r.dur_ns / NS_PER_MS
                    c["cpu_ms"] += r.cpu_ns / NS_PER_MS
                    c["n"] += r.n
            for c in [total] + rows:
                if c["count"]:
                    c["wall_ms"] /= c["count"]
                    c["cpu_ms"] /= c["count"]
        return {"stages": whole,
                "per_second": {
                    stage: {k: [round(c[k], 4) for c in rows]
                            for k in ("count", "wall_ms", "cpu_ms", "n")}
                    for stage, rows in by_second.items()}}


def of(run):
    """The Trace of `run.window`, pulled once per run; None where the
    program has no ring or wrote nothing into the window."""
    if not hasattr(run, "_op_trace"):
        run._op_trace = _pull(run)
    return run._op_trace


def _pull(run):
    try:
        from multiverso_tpu.dashboard import RING
    except ImportError:
        return None
    t0, t1 = run.window
    records, overwrote = RING.window(t0, t1)
    if overwrote:
        raise RuntimeError(
            f"the op ring overwrote part of the window ({RING.overwritten} "
            f"records lost): its numbers would be of a part of the window")
    if not records:
        return None
    trace = Trace(records, int(t0 * 1e9), int(t1 * 1e9))
    requests = trace.requests()
    print(json.dumps({"op_trace": dict(
        trace.stage_table(), records=len(records), window_s=trace.window_s,
        requests={"count": len(requests), **{
            name: {part: fold(getattr(q, part) for q in requests)
                   for part in Request.__slots__[1:]}
            for name, fold in (("median_ms", median), ("mean_ms", mean))
        }})}), flush=True)
    return trace


def mean_ms(records):
    return (sum(r.dur_ns for r in records) / len(records) / NS_PER_MS
            if records else None)


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def mean(values):
    values = list(values)
    return statistics.fmean(values) if values else None
