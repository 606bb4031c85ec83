#!/usr/bin/env python
"""``make metrics-smoke``: run a short remote-training session with the
MetricsLogger on, then assert the JSONL snapshot stream parses and the
key latency histograms are non-empty — the end-to-end contract between
the telemetry flags (``metrics_path`` / ``metrics_interval_seconds``),
the Dashboard registry, and the ingestion format
(``obs/logger.py:load_metrics``). Runs standalone (not a pytest module):

    JAX_PLATFORMS=cpu python tests/metrics_smoke.py [out.jsonl]
"""

import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable from the repo root OR anywhere (make metrics-smoke contract)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import multiverso_tpu as mv  # noqa: E402
from multiverso_tpu.obs.logger import load_metrics  # noqa: E402


def main() -> None:
    path = (sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        tempfile.mkdtemp(prefix="mv-metrics-smoke-"), "metrics.jsonl"))
    if os.path.exists(path):
        os.remove(path)
    mv.init(remote_workers=1, metrics_path=path,
            metrics_interval_seconds=0.2)
    table = mv.create_table("array", 64, np.float32)
    endpoint = mv.serve("127.0.0.1:0")
    client = mv.remote_connect(endpoint)
    rt = client.table(table.table_id)
    rng = np.random.default_rng(0)
    for _ in range(40):
        rt.add(rng.standard_normal(64).astype(np.float32))
        rt.get()
    # the live stats RPC sees the same traffic the JSONL will record
    snap = mv.stats(endpoint)
    req = snap.histogram("CLIENT_REQUEST_SECONDS")
    assert req is not None and req.count >= 40 and req.p99 > 0, \
        "stats RPC returned an empty request-latency histogram"
    time.sleep(0.5)  # let at least one periodic snapshot land
    client.close()
    mv.shutdown()  # flushes the final snapshot

    snaps = load_metrics(path)
    assert snaps, f"no metrics snapshots in {path}"
    last = snaps[-1]
    for key in ("t", "monitors", "counters", "gauges", "histograms"):
        assert key in last, f"snapshot missing {key!r}"
    for name in ("CLIENT_REQUEST_SECONDS", "SERVER_PROCESS_ADD_MSG",
                 "FRAME_ENCODE_SECONDS"):
        hist = last["histograms"].get(name)
        assert hist and hist["count"] > 0, f"histogram {name} is empty"
    assert last["gauges"].get("SERVER_DEDUP_OCCUPANCY", 0) > 0
    print(f"metrics-smoke: ok ({len(snaps)} snapshot(s); request latency "
          f"p50={req.p50 * 1e6:.0f}us p95={req.p95 * 1e6:.0f}us "
          f"p99={req.p99 * 1e6:.0f}us) -> {path}")


if __name__ == "__main__":
    main()
