"""Delta+merge device column cache: DML lands in bounded per-(region, table)
delta overlays the TPU kernel reads as ``base ⊕ delta`` (mask superseded /
deleted base rows, union fresh ones), and a background merge folds deltas
into the fixed-size device blocks re-uploading ONLY dirty blocks — the
in-process analog of TiFlash's raft-learner delta tree. Block size and the
delta knobs are shrunk so the suite covers the multi-block machinery on CPU.
"""

import dataclasses
import threading

import numpy as np
import pytest

import tidb_tpu
from tidb_tpu import config as _config
from tidb_tpu.copr import colcache, tpu_engine
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.utils import failpoint
from tidb_tpu.utils import metrics as _m

BLOCK = 256
CAP = 64


@pytest.fixture()
def deltadb(monkeypatch):
    monkeypatch.setattr(colcache, "DEVICE_BLOCK_ROWS", BLOCK)
    monkeypatch.setattr(tpu_engine, "_BLOCK", BLOCK)
    old = _config.current()
    _config.set_current(
        dataclasses.replace(
            old, device_delta_cap=CAP, device_delta_merge_rows=8, device_delta_min_rows=1
        )
    )
    db = tidb_tpu.open(region_split_keys=1 << 62)
    db.execute("CREATE TABLE d (id BIGINT PRIMARY KEY, g VARCHAR(2), v BIGINT)")
    rng = np.random.default_rng(7)
    n = 1000  # 4 device blocks
    bulk_load(
        db,
        "d",
        [
            np.arange(n, dtype=np.int64),
            np.array([b"aa", b"bb", b"cc"], dtype="S2")[rng.integers(0, 3, n)],
            rng.integers(0, 100, n).astype(np.int64),
        ],
    )
    yield db
    _config.set_current(old)


def both(db, sql):
    s = db.session()
    out = {}
    for eng in ("tpu", "host"):
        s.execute(f"SET tidb_isolation_read_engines = '{eng}'")
        out[eng] = s.query(sql)
    return out["tpu"], out["host"]


def _h2d():
    return _m.DEVICE_TRANSFER.get(dir="h2d")


Q1 = "SELECT g, COUNT(*), SUM(v), MIN(v), MAX(v) FROM d GROUP BY g ORDER BY g"
Q6 = "SELECT COUNT(*), SUM(v) FROM d WHERE v >= 20 AND v < 80"
TOPN = "SELECT id, v FROM d ORDER BY v DESC, id LIMIT 9"


def test_delta_read_fresh_and_parity(deltadb):
    s = deltadb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    base = s.query("SELECT COUNT(*), SUM(v) FROM d")
    s.query("SELECT COUNT(*), SUM(v) FROM d")  # device columns resident
    s.execute("UPDATE d SET v = v + 1 WHERE id < 10")
    s.execute("DELETE FROM d WHERE id BETWEEN 20 AND 24")
    s.execute("INSERT INTO d VALUES (5000,'aa',7),(5001,'bb',8)")
    h0 = _h2d()
    fresh = s.query("SELECT COUNT(*), SUM(v) FROM d")
    paid = _h2d() - h0
    # fresh: +10 from updates, -5 deleted rows, +2 inserts
    assert fresh[0][0] == base[0][0] - 5 + 2
    # the read shipped ONLY the small delta operand, never the base blocks
    assert paid < BLOCK * 9 * 2, f"base re-upload detected ({paid} bytes)"
    # the delta is pending (not merged) and the gauge sees it
    cache = colcache.cache_for(deltadb.store)
    assert cache.delta_rows_pending() == 17
    assert _m.DEVICE_DELTA_ROWS.get() >= 17
    for q in (Q1, Q6, TOPN, "SELECT id, v FROM d WHERE v >= 90", "SELECT id FROM d LIMIT 7"):
        t, h = both(deltadb, q)
        assert t == h, (q, t[:5], h[:5])


def test_delta_tie_and_scan_order_parity(deltadb):
    """Delta rows sit at the kernel's positional tail but must come out in
    host scan (handle) order: plain scans, LIMIT-without-order, and sort-key
    TIES spanning base and delta rows all follow ascending handle."""
    s = deltadb.session()
    s.query("SELECT COUNT(*) FROM d")  # warm the base entry
    # duplicate an existing v (ties!) on fresh rows + updates
    s.execute("UPDATE d SET v = 50 WHERE id IN (3, 700)")
    s.execute("DELETE FROM d WHERE id = 450")
    s.execute("INSERT INTO d VALUES (450, 'aa', 50), (5002, 'cc', 50)")
    t, h = both(deltadb, "SELECT id, v FROM d WHERE v = 50 ORDER BY v LIMIT 5")
    assert t == h
    t, h = both(deltadb, "SELECT id FROM d WHERE v = 50")
    assert t == h  # unordered scan parity = handle order restored
    t, h = both(deltadb, "SELECT id FROM d LIMIT 12")
    assert t == h


def test_merge_reuploads_only_dirty_blocks(deltadb):
    s = deltadb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    q = "SELECT COUNT(*), SUM(v) FROM d"
    s.query(q)
    s.query(q)  # all blocks resident
    # burst confined to block 0 (handles < 256)
    s.execute("UPDATE d SET v = v + 1 WHERE id < 10")
    s.query(q)  # delta read
    merged = deltadb.run_delta_merge()
    assert merged == 1
    assert colcache.cache_for(deltadb.store).delta_rows_pending() == 0
    h0 = _h2d()
    s.query(q)
    paid = _h2d() - h0
    # handles + g + v lanes of ONE dirty block, not four
    assert paid < 3.5 * BLOCK * 10, f"merge re-uploaded clean blocks ({paid} bytes)"
    tid = deltadb.catalog.table("test", "d").id
    entry = colcache.cache_for(deltadb.store)._entries[(1, tid)]
    assert entry.block_vers is not None
    assert len(set(entry.block_vers)) > 1  # block 0 fresh, the rest carried


def test_append_only_ingest_carries_prefix_blocks(deltadb):
    s = deltadb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    q = "SELECT COUNT(*), SUM(v) FROM d"
    h_cold = _h2d()
    s.query(q)
    s.query(q)
    warm = _h2d() - h_cold  # what the four blocks cost to bring up
    # 200-row columnar append (> CAP → merge path with tail carry)
    bulk_load(
        deltadb,
        "d",
        [
            np.arange(1000, 1200, dtype=np.int64),
            np.full(200, b"aa", dtype="S2"),
            np.zeros(200, dtype=np.int64),
        ],
    )
    h0 = _h2d()
    out = s.query(q)
    paid = _h2d() - h0
    assert out[0][0] == 1200
    # only the dirty tail block(s) ship; prefix blocks carry their arrays
    assert paid < 3.5 * BLOCK * 10 * 2, f"append re-uploaded the table ({paid} bytes)"
    assert paid < 0.6 * warm, f"append re-uploaded {paid} of the warm-up's {warm} bytes"
    t, h = both(deltadb, Q1)
    assert t == h


def test_cross_table_dml_keeps_sibling_device_cache(deltadb):
    """DML on table E shares the region with D (one giant region): D's entry
    revalidates in place — no rebuild, no re-upload."""
    deltadb.execute("CREATE TABLE e (id BIGINT PRIMARY KEY, w BIGINT)")
    deltadb.execute("INSERT INTO e VALUES (1, 1), (2, 2)")
    s = deltadb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    q = "SELECT COUNT(*), SUM(v) FROM d"
    r0 = s.query(q)
    s.query(q)
    s.execute("UPDATE e SET w = w + 1 WHERE id = 1")  # bumps the region version
    h0 = _h2d()
    assert s.query(q) == r0
    assert _h2d() - h0 < BLOCK, "sibling-table DML re-uploaded this table"


def test_explain_analyze_shows_delta_path(deltadb):
    s = deltadb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    s.query("SELECT COUNT(*) FROM d")
    s.execute("UPDATE d SET v = v + 1 WHERE id = 1")
    rows = s.query("EXPLAIN ANALYZE SELECT COUNT(*), SUM(v) FROM d")
    txt = "\n".join(str(r) for r in rows)
    assert "delta_rows: 1" in txt, txt


def test_compactor_chaos_mid_merge(deltadb):
    """Kill the merge between the rebuild and the swap: the old base + the
    delta + the change log survive untouched (no torn block is ever visible),
    and the next merge attempt succeeds."""
    s = deltadb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    q = "SELECT COUNT(*), SUM(v) FROM d"
    s.query(q)
    s.execute("UPDATE d SET v = v + 1 WHERE id < 20")
    fresh = s.query(q)  # delta read
    cache = colcache.cache_for(deltadb.store)
    assert cache.delta_rows_pending() == 20

    def die(*a):
        raise ConnectionError("chaos: compactor store died mid-merge")

    with failpoint.enabled("colcache_merge", die):
        with pytest.raises(ConnectionError):
            cache.merge_pending(threshold=1)
    # deltas survived; reads stay fresh and host-parity-identical
    assert cache.delta_rows_pending() == 20
    assert s.query(q) == fresh
    t, h = both(deltadb, Q1)
    assert t == h
    # the re-merge completes and folds the delta
    assert cache.merge_pending(threshold=1) == 1
    assert cache.delta_rows_pending() == 0
    assert s.query(q) == fresh
    t, h = both(deltadb, Q1)
    assert t == h


def test_mixed_oltp_olap_race_with_merges(deltadb):
    """Concurrent point writers racing Q1/Q6/TopN scans on the tpu engine;
    TPU-vs-host parity asserted after every merge round."""
    stop = threading.Event()
    errors: list = []

    def writer(seed):
        try:
            s = deltadb.session()
            rng = np.random.default_rng(seed)
            k = 0
            while not stop.is_set() and k < 60:
                op = k % 3
                hid = int(rng.integers(0, 1000))
                if op == 0:
                    s.execute(f"UPDATE d SET v = v + 1 WHERE id = {hid}")
                elif op == 1:
                    s.execute(f"INSERT INTO d VALUES ({10000 + seed * 1000 + k}, 'bb', {k % 100})")
                else:
                    s.execute(f"DELETE FROM d WHERE id = {20000 + hid}")  # mostly no-op
                k += 1
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    def scanner():
        try:
            s = deltadb.session()
            s.execute("SET tidb_isolation_read_engines = 'tpu'")
            while not stop.is_set():
                for q in (Q1, Q6, TOPN):
                    s.query(q)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(2)]
    threads.append(threading.Thread(target=scanner))
    for t in threads:
        t.start()
    for t in threads[:2]:
        t.join()
    stop.set()
    threads[2].join()
    assert not errors, errors
    # quiesced: merge, then assert exact parity on every shape
    deltadb.run_delta_merge()
    for q in (Q1, Q6, TOPN):
        t, h = both(deltadb, q)
        assert t == h, q
    # and again after a second DML + merge round
    deltadb.execute("UPDATE d SET v = 0 WHERE id < 5")
    deltadb.run_delta_merge()
    for q in (Q1, Q6, TOPN):
        t, h = both(deltadb, q)
        assert t == h, q


def test_merge_metrics_observed(deltadb):
    s = deltadb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    s.query("SELECT COUNT(*) FROM d")
    n0 = _m.DEVICE_MERGE_SECONDS.count
    s.execute("UPDATE d SET v = v + 1 WHERE id < 9")
    s.query("SELECT COUNT(*) FROM d")
    assert deltadb.run_delta_merge() == 1
    assert _m.DEVICE_MERGE_SECONDS.count == n0 + 1


def test_window_with_pending_delta_merges_eagerly(deltadb):
    """Window DAGs cannot take the delta operand — a pending delta folds
    into the base first (merge_now), keeping parity and clean-block carry."""
    s = deltadb.session()
    s.query("SELECT COUNT(*) FROM d")  # warm the base entry
    s.execute("UPDATE d SET v = v + 3 WHERE id < 4")
    s.execute("INSERT INTO d VALUES (6001, 'bb', 42)")
    q = "SELECT id, SUM(v) OVER (PARTITION BY g) FROM d ORDER BY id LIMIT 20"
    t, h = both(deltadb, q)
    assert t == h
    # the merge folded the delta away
    assert colcache.cache_for(deltadb.store).delta_rows_pending() == 0


def test_single_block_path_delta(deltadb):
    """Tables under one device block take the single-kernel path — the delta
    operand must work there too (and for agg/rows shapes alike)."""
    deltadb.execute("CREATE TABLE sm (id BIGINT PRIMARY KEY, v BIGINT)")
    deltadb.execute("INSERT INTO sm VALUES " + ",".join(f"({i},{i % 7})" for i in range(100)))
    s = deltadb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    s.query("SELECT COUNT(*), SUM(v) FROM sm")  # warm the base
    s.execute("UPDATE sm SET v = 100 WHERE id = 50")
    s.execute("DELETE FROM sm WHERE id = 51")
    s.execute("INSERT INTO sm VALUES (200, 5)")
    for q in (
        "SELECT COUNT(*), SUM(v), MIN(v), MAX(v) FROM sm",
        "SELECT v, COUNT(*) FROM sm GROUP BY v ORDER BY v",
        "SELECT id FROM sm WHERE v >= 5 ORDER BY id",
        "SELECT id, v FROM sm ORDER BY v DESC, id LIMIT 6",
        "SELECT id FROM sm LIMIT 8",
    ):
        t, h = both(deltadb, q)
        assert t == h, (q, t[:8], h[:8])


# -- point-get batcher satellites -------------------------------------------


def test_index_join_inner_point_reads_batched():
    """Index-join PK probes ride the cross-session point-get batcher: ONE
    batched dispatch for the probe set, visible in the batch-size histogram
    (count = dispatches, sum = keys — sum/count >> 1 proves coalescing)."""
    db = tidb_tpu.open()
    db.execute("CREATE TABLE oo (id BIGINT PRIMARY KEY, k BIGINT)")
    db.execute("CREATE TABLE ii (id BIGINT PRIMARY KEY, v BIGINT)")
    db.execute("INSERT INTO oo VALUES " + ",".join(f"({i},{i % 20})" for i in range(40)))
    db.execute("INSERT INTO ii VALUES " + ",".join(f"({i},{i * 3})" for i in range(20)))
    db.execute("ANALYZE TABLE oo")
    db.execute("ANALYZE TABLE ii")
    s = db.session()
    n0, s0 = _m.POINTGET_BATCH.count, _m.POINTGET_BATCH._sum
    rows = s.query(
        "SELECT /*+ INL_JOIN(ii) */ oo.id, ii.v FROM oo JOIN ii ON oo.k = ii.id ORDER BY oo.id"
    )
    assert len(rows) == 40
    assert all(v == k * 3 for (_i, v), k in zip(rows, [i % 20 for i in range(40)]))
    dispatches = _m.POINTGET_BATCH.count - n0
    keys = _m.POINTGET_BATCH._sum - s0
    assert dispatches >= 1 and keys >= 20
    assert keys / dispatches >= 10, (keys, dispatches)  # histogram proves batching


def test_dirty_txn_gets_batched():
    """Batch point gets inside a dirty transaction route through
    Txn.batch_get → the batcher, with the membuffer overlay respected."""
    db = tidb_tpu.open()
    db.execute("CREATE TABLE tb (id BIGINT PRIMARY KEY, v BIGINT)")
    db.execute("INSERT INTO tb VALUES " + ",".join(f"({i},{i})" for i in range(16)))
    s = db.session()
    s.execute("BEGIN")
    s.execute("UPDATE tb SET v = 100 WHERE id = 3")  # dirty write in the membuffer
    s.execute("DELETE FROM tb WHERE id = 5")
    n0, s0 = _m.POINTGET_BATCH.count, _m.POINTGET_BATCH._sum
    rows = s.query("SELECT id, v FROM tb WHERE id IN (1,2,3,4,5,6,7,8)")
    assert rows == [(1, 1), (2, 2), (3, 100), (4, 4), (6, 6), (7, 7), (8, 8)]
    dispatches = _m.POINTGET_BATCH.count - n0
    keys = _m.POINTGET_BATCH._sum - s0
    # 6 snapshot misses coalesce into one dispatch (3 and 5 come from the buffer)
    assert dispatches == 1 and keys == 6, (dispatches, keys)
    s.execute("ROLLBACK")


def test_store_colmerge_sweep_fires_and_stops(monkeypatch, thread_hygiene):
    """PR 7 leftover, closed: a REMOTE StoreServer runs its own periodic
    delta-merge sweep (the embedded owner-gated 'colmerge' timer mirrored
    onto the storage tier) — it calls merge_pending on the configured
    cadence with the server's stop event as the cooperative fence, and the
    thread dies with shutdown()."""
    import time

    from tidb_tpu.copr import colcache as _colcache
    from tidb_tpu.kv import remote as _remote
    from tidb_tpu.kv.memstore import MemStore

    old = _config.current()
    _config.set_current(dataclasses.replace(old, store_colmerge_interval_s=0.05))
    calls = []

    class _Stub:
        def merge_pending(self, threshold=None, should_stop=None):
            calls.append(should_stop() if should_stop is not None else None)
            return 0

    monkeypatch.setattr(_colcache, "cache_for", lambda store: _Stub())
    srv = _remote.StoreServer(MemStore(region_split_keys=1 << 62))
    try:
        srv.start()
        deadline = time.time() + 5
        while not calls and time.time() < deadline:
            time.sleep(0.02)
        assert calls, "store-colmerge sweep never fired"
        assert calls[0] is False  # the fence callable reports not-stopped
    finally:
        srv.shutdown()
        _config.set_current(old)
    assert not any(
        t.name == "store-colmerge" and t.is_alive() for t in threading.enumerate()
    ), "store-colmerge thread survived shutdown"
