"""Tiered beyond-RAM table storage (multiverso_tpu/store/,
docs/tiered_storage.md): cold-segment codec + CRC framing, TinyLFU
admission, LRU demotion to budget, tiered servers' bit-equivalence with
their in-RAM counterparts, snapshot interchange, and the MV_TIER_KILL
SIGKILL-mid-demotion drill (zero acked Adds lost, zero doubled).

``make tiered`` runs this file; the CI job additionally replays the kill
drill once per crash arm by exporting MV_TIER_KILL.
"""

import os

# Scrub the chaos arm from OUR environment before anything imports the
# store: a global MV_TIER_KILL would SIGKILL the pytest process itself on
# the first in-process demotion. The drill re-injects it into the CHILD's
# environment only; when the CI matrix sets an arm, only that arm runs.
_TIER_KILL = os.environ.pop("MV_TIER_KILL", "")

import socket
import subprocess
import sys

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.io import MemoryStream
from multiverso_tpu.store import ColdStore, FrequencySketch, TieredStore
from multiverso_tpu.tables.kv_table import KVServer, TieredKVServer
from multiverso_tpu.tables.sparse_table import SparseServer, TieredSparseServer
from traffic_gen import TrafficGen

_CHILD = os.path.join(os.path.dirname(__file__), "tiered_kill_child.py")


# -- cold store: segment codec, CRC framing, lifecycle ------------------------

def test_coldstore_raw_roundtrip_and_release(tmp_path):
    cs = ColdStore(str(tmp_path / "c"), width=3, dtype=np.float32,
                   bits=0, table_id=7)
    keys = np.array([5, 42, 9_000_000_000], np.int64)
    rows = np.arange(9, dtype=np.float32).reshape(3, 3)
    cs.write_batch(keys, rows)
    assert len(cs) == 3 and 42 in cs
    np.testing.assert_array_equal(cs.fetch(42), rows[1])
    assert sorted(dict(cs.items())) == sorted(keys.tolist())

    # superseding every key of segment 0 in segment 1 deletes segment 0
    cs.write_batch(keys, rows * 2.0)
    assert cs.segment_count == 1
    np.testing.assert_array_equal(cs.fetch(5), rows[0] * 2.0)

    # remove drops the key; the segment goes when its last key goes
    cs.remove(5)
    cs.remove(42)
    assert cs.fetch(42) is None and len(cs) == 1
    cs.remove(9_000_000_000)
    assert cs.segment_count == 0 and cs.total_bytes == 0
    cs.close()


def test_coldstore_quantized_segments_smaller_and_close(tmp_path):
    rng = np.random.default_rng(0)
    keys = np.arange(64, dtype=np.int64)
    rows = rng.normal(0, 3, (64, 16)).astype(np.float32)
    raw = ColdStore(str(tmp_path / "raw"), 16, np.float32, bits=0)
    q = ColdStore(str(tmp_path / "q"), 16, np.float32, bits=8)
    raw.write_batch(keys, rows)
    q.write_batch(keys, rows)
    assert q.total_bytes < raw.total_bytes
    lo, hi = rows.min(), rows.max()
    step = (hi - lo) / 255.0
    for k in (0, 31, 63):
        np.testing.assert_array_equal(raw.fetch(k), rows[k])
        np.testing.assert_allclose(q.fetch(k), rows[k], atol=step)
    raw.close()
    q.close()


def test_coldstore_nonfinite_rows_fall_back_to_raw(tmp_path):
    cs = ColdStore(str(tmp_path / "c"), 4, np.float32, bits=8)
    rows = np.array([[1.0, np.inf, -2.0, np.nan]], np.float32)
    cs.write_batch(np.array([3], np.int64), rows)
    out = cs.fetch(3)
    assert np.isinf(out[1]) and np.isnan(out[3])
    np.testing.assert_array_equal(out[[0, 2]], rows[0][[0, 2]])
    cs.close()


def test_coldstore_wipes_stale_spill_on_init(tmp_path):
    d = str(tmp_path / "c")
    cs = ColdStore(d, 2, np.float32, bits=0)
    cs.write_batch(np.array([1], np.int64), np.ones((1, 2), np.float32))
    cs.close()
    # a fresh incarnation treats the directory as disposable spill
    cs2 = ColdStore(d, 2, np.float32, bits=0)
    assert len(cs2) == 0 and cs2.segment_count == 0
    assert not [f for f in os.listdir(d) if f.endswith(".mvcold")]
    cs2.close()


# -- admission sketch ---------------------------------------------------------

def test_frequency_sketch_counts_and_ages():
    sk = FrequencySketch(size=1024)
    assert sk.estimate(99) == 0
    sk.touch(99)
    assert sk.estimate(99) == 1
    for _ in range(40):
        sk.touch(99)
    assert sk.estimate(99) == 15  # saturates at 4 bits
    # aging halves every counter so stale popularity decays
    sk._rows >>= 1
    assert sk.estimate(99) == 7


# -- tier policy --------------------------------------------------------------

def _tier(tmp_path, rows_budget=8, width=4, bits=0, admit=2):
    return TieredStore(width, np.float32, resident_bytes=rows_budget * width * 4,
                       cold_bits=bits, directory=str(tmp_path / "tier"),
                       admit_touches=admit)


def test_tiered_demotes_to_budget_and_serves_both_tiers(tmp_path):
    Dashboard.reset()
    ts = _tier(tmp_path, rows_budget=10)
    for k in range(100):
        ts.put(k, np.full(4, float(k), np.float32))
    assert ts.maintain() == 90
    assert ts.hot_rows == 10 and ts.cold_rows == 90 and len(ts) == 100
    assert ts.resident_bytes <= ts.budget
    for k in (0, 55, 99):  # both tiers serve reads
        np.testing.assert_array_equal(ts.get(k), np.full(4, float(k)))
    assert Dashboard.counter_value("TIER_DEMOTIONS") == 90
    assert Dashboard.gauge_value("TIER_COLD_BYTES") > 0
    ts.close()


def test_tiered_lru_picks_untouched_victims(tmp_path):
    ts = _tier(tmp_path, rows_budget=4)
    for k in range(8):
        ts.put(k, np.zeros(4, np.float32))
    for k in (1, 3, 5, 7):  # freshen the odd keys
        ts.get(k)
    ts.maintain()
    assert sorted(ts._hot) == [1, 3, 5, 7]
    ts.close()


def test_tiered_admission_blocks_one_shot_scan(tmp_path):
    Dashboard.reset()
    ts = _tier(tmp_path, rows_budget=4, admit=2)
    for k in range(16):
        ts.put(k, np.full(4, float(k), np.float32))
    ts.maintain()
    cold_key = next(k for k in range(16) if k not in ts._hot)
    ts.get(cold_key)  # first touch: served cold, NOT promoted
    assert cold_key not in ts._hot
    assert Dashboard.counter_value("TIER_PROMOTIONS") == 0
    ts.get(cold_key)  # second touch passes admission
    assert cold_key in ts._hot
    assert Dashboard.counter_value("TIER_PROMOTIONS") == 1
    ts.close()


def test_tiered_add_path_always_promotes(tmp_path):
    ts = _tier(tmp_path, rows_budget=4, admit=100)  # Get would never admit
    for k in range(16):
        ts.put(k, np.full(4, float(k), np.float32))
    ts.maintain()
    cold_key = next(k for k in range(16) if k not in ts._hot)
    row = ts.get_for_update(cold_key)
    assert cold_key in ts._hot  # read-modify-write lands hot
    row += 1.0
    np.testing.assert_array_equal(ts.get(cold_key),
                                  np.full(4, float(cold_key) + 1.0))
    ts.close()


def test_tiered_quant_integer_grid_survives_demotion_exactly(tmp_path):
    """bits=8 is exact when values sit on the pinned 0..255 integer grid
    (step=1): embeddings-of-counts style payloads round-trip bit-for-bit."""
    ts = _tier(tmp_path, rows_budget=2, width=8, bits=8)
    rng = np.random.default_rng(1)
    rows = {k: rng.integers(0, 256, 8).astype(np.float32) for k in range(20)}
    rows[0][0], rows[1][0] = 0.0, 255.0  # pin the quant range
    for k, v in rows.items():
        ts.put(k, v)
    ts.maintain()
    assert ts.cold_rows >= 18
    for k, v in rows.items():
        np.testing.assert_array_equal(ts.get(k), v)
    ts.close()


# -- tiered servers: equivalence with the in-RAM tables -----------------------

def _uniform_rounds(rng, key_space):
    """30 rounds of up to 11 uniform keys against a budget of 6 rows:
    nearly every row written ends cold."""
    for _ in range(30):
        n = int(rng.integers(1, 12))
        yield (rng.integers(0, key_space, n).astype(np.int64),
               rng.integers(0, key_space, 8).astype(np.int64))


def _zipf_rounds(rng, key_space):
    """Every key written once (the table is 8x its budget), then 200
    rounds of Zipf(1.1) traffic, one Add to nineteen Gets."""
    keys = np.arange(key_space, dtype=np.int64)
    for start in range(0, key_space, 500):
        yield keys[start:start + 500], keys[start:start + 8]
    gen = TrafficGen(key_space, zipf_s=1.1, seed=3)
    for _ in range(200):
        yield (np.array([gen.draw_key()], np.int64),
               np.array([gen.draw_key() for _ in range(19)], np.int64))


@pytest.mark.parametrize("key_space,hot_rows,rounds,min_hot_hit_rate", [
    (10_000, 6, _uniform_rounds, 0.0),
    # skewed reads of a table 8x over budget: admission and LRU keep
    # the hot set hot (0.59 over the first 1,000 Gets, 0.78 by the last)
    (4_000, 500, _zipf_rounds, 0.5),
], ids=["uniform-6-rows", "zipf-8x-over-budget"])
def test_tiered_sparse_server_matches_plain_sparse(
        tmp_path, key_space, hot_rows, rounds, min_hot_hit_rate):
    Dashboard.reset()
    plain = SparseServer(key_space, width=4)
    tiered = TieredSparseServer(key_space, width=4,
                                resident_bytes=hot_rows * 4 * 4,
                                cold_bits=0,
                                tier_dir=str(tmp_path / "tier"))
    rng = np.random.default_rng(2)
    for keys, probe in rounds(rng, key_space):
        vals = rng.normal(0, 1, (len(keys), 4)).astype(np.float32)
        for srv in (plain, tiered):
            srv.process_add((keys, vals, None))
        np.testing.assert_array_equal(plain.process_get((probe, None)),
                                      tiered.process_get((probe, None)))
    hot = Dashboard.counter_value("TIER_HOT_HITS")
    cold = Dashboard.counter_value("TIER_COLD_HITS")
    assert hot / max(hot + cold, 1) >= min_hot_hit_rate
    lk_p, lv_p = plain.process_get((None, None))
    lk_t, lv_t = tiered.process_get((None, None))
    np.testing.assert_array_equal(lk_p, lk_t)
    np.testing.assert_array_equal(lv_p, lv_t)
    stats = tiered.tier_stats()
    assert stats["cold_rows"] > stats["hot_rows"]  # it really spilled
    tiered._tier.close()


def test_tiered_kv_server_matches_plain_kv(tmp_path):
    plain = KVServer(value_dtype=np.float32)
    tiered = TieredKVServer(value_dtype=np.float32,
                            resident_bytes=4 * 4, cold_bits=0,
                            tier_dir=str(tmp_path / "tier"))
    rng = np.random.default_rng(3)
    for _ in range(25):
        n = int(rng.integers(1, 6))
        keys = rng.integers(0, 200, n).astype(np.int64)
        vals = rng.normal(0, 1, n).astype(np.float32)
        for srv in (plain, tiered):
            srv.process_add((keys, vals, None))
        probe = rng.integers(0, 200, 5).astype(np.int64)
        assert plain.process_get((probe, None)) == \
            tiered.process_get((probe, None))
    assert plain.process_get((None, None)) == tiered.process_get((None, None))
    assert tiered.tier_stats()["cold_rows"] > 0
    tiered._tier.close()


def test_tiered_sparse_snapshot_interchanges_with_plain(tmp_path):
    """store()/load() keep the plain sparse wire format, so snapshots move
    between tiered and in-RAM servers in both directions."""
    tiered = TieredSparseServer(1000, width=2, resident_bytes=3 * 2 * 4,
                                cold_bits=0, tier_dir=str(tmp_path / "a"))
    keys = np.arange(0, 900, 90, dtype=np.int64)
    vals = np.arange(20, dtype=np.float32).reshape(10, 2)
    tiered.process_add((keys, vals, None))
    buf = MemoryStream()
    tiered.store(buf)
    buf.seek(0)
    plain = SparseServer(1000, width=2)
    plain.load(buf)
    np.testing.assert_array_equal(plain.process_get((keys, None)), vals)

    buf.seek(0)
    tiered2 = TieredSparseServer(1000, width=2, resident_bytes=3 * 2 * 4,
                                 cold_bits=0, tier_dir=str(tmp_path / "b"))
    tiered2.load(buf)
    np.testing.assert_array_equal(tiered2.process_get((keys, None)), vals)
    assert tiered2.tier_stats()["cold_rows"] > 0  # load re-tiered
    tiered._tier.close()
    tiered2._tier.close()


def test_tiered_sparse_worker_via_dispatcher(mv_env, tmp_path):
    """The registered ``tiered_sparse`` kind, through the real dispatcher
    (every mutation — demotions included — is dispatcher-serialized)."""
    t = mv.create_table("tiered_sparse", 1_000_000, 4,
                        resident_bytes=8 * 4 * 4, cold_bits=0,
                        tier_dir=str(tmp_path / "tier"))
    keys = np.arange(0, 64_000, 1000, dtype=np.int64)
    vals = np.ones((64, 4), np.float32)
    t.add(keys, vals)
    t.add(keys[:5], vals[:5] * 2.0)
    out = t.get(keys[:5])
    np.testing.assert_array_equal(out, np.full((5, 4), 3.0, np.float32))
    stats = t._server_table.tier_stats()
    assert stats["hot_rows"] + stats["cold_rows"] == 64
    assert stats["cold_rows"] > 0


# -- MV_TIER_KILL drill: SIGKILL mid-demotion, recover, exactly-once ----------

def _free_port() -> int:
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


def _spawn_child(args, kill_arm=""):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(_CHILD)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("MV_TIER_KILL", None)
    if kill_arm:
        env["MV_TIER_KILL"] = kill_arm
    return subprocess.Popen([sys.executable, _CHILD, *args],
                            stdout=subprocess.PIPE, text=True, env=env)


def _await_serving(child):
    seen = []
    while len(seen) < 50:  # log INFO lines precede the ready marker
        line = child.stdout.readline()
        if not line:
            break
        line = line.strip()
        seen.append(line)
        if line.startswith("serving "):
            _, endpoint, table_id = line.split()
            return endpoint, int(table_id)
    raise AssertionError(f"child never reported serving: {seen}")


@pytest.mark.parametrize("arm", ["before_commit", "after_commit"])
def test_tier_kill_mid_demotion_recovers_exactly_once(arm, tmp_path):
    """SIGKILL the serving process inside the cold-segment write the 9th
    Add triggers (before or after the manifest commit), restart with
    --recover, and finish: zero acknowledged Adds lost, zero doubled.
    The cold spill is disposable — WAL replay rebuilds the whole table,
    re-demoting as it goes."""
    if _TIER_KILL and arm != _TIER_KILL:
        pytest.skip(f"CI matrix runs arm {_TIER_KILL!r} only")
    port = _free_port()
    wal, tier = str(tmp_path / "wal"), str(tmp_path / "tier")
    child = _spawn_child([str(port), wal, tier], kill_arm=arm)
    child2 = None
    try:
        endpoint, table_id = _await_serving(child)
        mv.set_flag("request_retry_seconds", 0.5)
        mv.set_flag("reconnect_deadline_seconds", 90.0)
        mv.set_flag("retry_base_seconds", 0.1)
        mv.set_flag("heartbeat_seconds", 0.5)
        client = mv.remote_connect(endpoint)
        rt = client.table(table_id)
        width = 8
        # 8 acked Adds fill the hot tier exactly (integer-valued floats:
        # sums stay exact whatever order recovery re-applies them)
        for k in range(8):
            rt.add([k * 1000], np.full((1, width), float(2 ** k), np.float32))
        # the 9th overflows the budget -> demotion -> segment write -> kill
        handle = rt.add_async([8000], np.full((1, width), 256.0, np.float32))
        child.wait(timeout=60)
        assert child.returncode == -9  # died by SIGKILL inside write_batch
        child2 = _spawn_child([str(port), wal, tier, "--recover"])
        _await_serving(child2)
        rt.wait(handle)  # settles via reconnect-resume (+ dedup re-reply)
        rt.add([0], np.full((1, width), 1.0, np.float32))
        keys = [k * 1000 for k in range(9)]
        final = np.asarray(rt.get(keys), np.float32)
        want = np.stack([np.full(width, float(2 ** k), np.float32)
                         for k in range(9)])
        want[0] += 1.0
        np.testing.assert_array_equal(final, want)
        client.close()
    finally:
        for proc in (child, child2):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
