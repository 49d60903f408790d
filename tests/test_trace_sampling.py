"""Always-on sampled tracing + per-shard MPP straggler attribution.

Tentpole coverage (see OBSERVABILITY.md): the per-statement sampling coin in
``Session.execute`` (seeded/deterministic under test), the bounded trace
reservoir with tail-keep of slow statements, the strict zero-cost path when
the coin says no, the slow-log/Top-SQL → reservoir cross-links, the
``/traces`` endpoint and ``information_schema.trace_reservoir`` surfaces,
and the ``mpp_task: {..., slowest: shard k}`` line under a chaos-injected
slow shard."""

import random
import re
import threading

import pytest

import tidb_tpu
from tidb_tpu.utils import failpoint
from tidb_tpu.utils.tracing import TraceEntry, TraceReservoir, Tracer


def _mk_db(split=100):
    db = tidb_tpu.open(region_split_keys=split)
    s = db.session()
    s.execute("SET tidb_isolation_read_engines = 'host'")
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i})" for i in range(300)))
    return db, s


# -- the sampling coin -------------------------------------------------------


def test_rate_zero_is_strictly_zero_cost(monkeypatch):
    """Rate 0 (the default): no Tracer is EVER constructed, no reservoir
    entry appears, and the cop path sees Request.tracer is None — the
    zero-allocation guarantee the trace_off_overhead_ms lane times."""
    import tidb_tpu.utils.tracing as tracing_mod

    db, s = _mk_db()
    orig = tracing_mod.Tracer

    class Boom(orig):
        def __init__(self, *a, **k):
            raise AssertionError("Tracer constructed with sampling off")

    monkeypatch.setattr(tracing_mod, "Tracer", Boom)
    for _ in range(10):
        assert s.query("SELECT COUNT(*) FROM t") == [(300,)]
    assert s.tracer is None
    assert len(db.trace_reservoir) == 0


def test_rate_one_samples_every_statement():
    db, s = _mk_db()
    s.execute("SET tidb_tpu_trace_sample_rate = 1")
    before = len(db.trace_reservoir)
    s.query("SELECT COUNT(*) FROM t")
    s.query("SELECT SUM(v) FROM t")
    traces = db.trace_reservoir.traces()
    assert len(traces) >= before + 2
    e = traces[-1]
    assert e.trace_id and e.duration_s > 0
    names = [sp[0] for sp in e.spans]
    # the root statement span plus the real instrumentation-site spans
    assert names[0] == "statement"
    assert "execute" in names
    assert any(n.startswith("cop.r") for n in names)  # multi-region cop spans
    # sampling turned itself off after the statement
    assert s.tracer is None


def test_seeded_coin_is_deterministic():
    """Rate 0.5 with a seed reproduces the exact accept/reject sequence of
    random.Random(seed) — two sessions with the same seed sample the same
    statements."""

    def run_pattern():
        db, s = _mk_db()
        s.execute("SET tidb_tpu_trace_sample_rate = 0.5")
        s.execute("SET tidb_tpu_trace_sample_seed = 42")
        pattern = []
        for _ in range(24):
            before = len(db.trace_reservoir)
            s.query("SELECT COUNT(*) FROM t")
            pattern.append(len(db.trace_reservoir) - before)
        return pattern

    p1, p2 = run_pattern(), run_pattern()
    rng = random.Random(42)
    expected = [1 if rng.random() < 0.5 else 0 for _ in range(24)]
    assert p1 == expected
    assert p2 == expected
    assert 0 < sum(p1) < 24  # genuinely probabilistic, not all-or-nothing


def test_sampled_flag_rides_the_trace_context():
    """The previously-unused TraceContext.sampled flag now travels: a
    sampled tracer emits sampled=1, and an explicitly UNSAMPLED tracer is
    treated as tracing-off by the cop clients (no spans recorded)."""
    tr = Tracer(sampled=True)
    assert tr.context().to_pb() == {"tid": tr.trace_id, "sampled": 1}
    db, s = _mk_db()
    unsampled = Tracer(sampled=False)
    s.tracer = unsampled
    try:
        s.query("SELECT COUNT(*) FROM t")
    finally:
        s.tracer = None
    # session spans (plan/execute) record locally, but the cop client
    # refused the unsampled context: no per-task spans
    names = [sp.name for sp in unsampled.spans]
    assert not any(n.startswith("cop") for n in names), names


# -- the reservoir -----------------------------------------------------------


def test_reservoir_ring_bound_and_tail_keep():
    """The ring holds N recent traces; a slow statement's trace is pinned in
    the tail-keep section and survives arbitrarily many fast statements."""
    db, s = _mk_db()
    db.trace_reservoir = TraceReservoir(capacity=3, slow_capacity=2)
    s.execute("SET tidb_tpu_trace_sample_rate = 1")
    s.execute("SET tidb_slow_log_threshold = 0")  # everything is "slow"
    s.query("SELECT SUM(v) FROM t WHERE v < 250")
    slow_id = db.trace_reservoir.traces()[-1].trace_id
    slow_entry = db.trace_reservoir.get(slow_id)
    assert slow_entry is not None and slow_entry.slow
    # fast statements rotate the ring far past its bound
    s.execute("SET tidb_slow_log_threshold = 300000")
    for i in range(10):
        s.query(f"SELECT COUNT(*) FROM t WHERE id > {i}")
    traces = db.trace_reservoir.traces()
    assert len(traces) <= 3 + 2  # ring + pinned tail-keep
    assert db.trace_reservoir.get(slow_id) is not None, "tail-keep lost the slow trace"
    assert any(e.trace_id == slow_id for e in traces)


def test_reservoir_entry_threadless():
    """The reservoir is deliberately threadless — deposits ride the
    statement's own thread (the conftest thread_hygiene fixture flags any
    trace-* thread as a regression)."""
    db, s = _mk_db()
    s.execute("SET tidb_tpu_trace_sample_rate = 1")
    s.query("SELECT COUNT(*) FROM t")
    assert not [t for t in threading.enumerate() if t.name.startswith("trace-")]


def test_slow_log_cross_links_trace_id():
    """Slow-log → reservoir pivot: the structured SlowEntry carries the
    sampled statement's trace id, in information_schema.slow_query and the
    /slowlog JSON alike."""
    db, s = _mk_db()
    s.execute("SET tidb_tpu_trace_sample_rate = 1")
    s.execute("SET tidb_slow_log_threshold = 0")
    s.query("SELECT MAX(v) FROM t")
    s.execute("SET tidb_slow_log_threshold = 300")
    rows = [
        r for r in s.query("SELECT trace_id, query FROM information_schema.slow_query")
        if "MAX(v)" in r[1]
    ]
    assert rows and rows[-1][0], rows
    tid = rows[-1][0]
    hit = db.trace_reservoir.get(tid)
    assert hit is not None and "MAX(v)" in hit.sql


def test_traces_endpoint_and_memtable():
    import json
    import urllib.request

    from tidb_tpu.server.status import StatusServer

    db, s = _mk_db()
    s.execute("SET tidb_tpu_trace_sample_rate = 1")
    s.execute("SET tidb_slow_log_threshold = 0")
    s.query("SELECT SUM(v) FROM t")
    s.execute("SET tidb_slow_log_threshold = 300")
    # SQL surface
    mrows = s.query(
        "SELECT trace_id, query, slow, spans FROM information_schema.trace_reservoir"
    )
    assert mrows
    tid = next(r[0] for r in mrows if "SUM(v)" in r[1])
    st = StatusServer(db)
    port = st.start()
    try:
        data = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port}/traces", timeout=10).read()
        )
        assert isinstance(data, list) and data
        rec = next(r for r in data if r["trace_id"] == tid)
        assert rec["slow"] is True
        assert rec["spans"] and rec["spans"][0][0] == "statement"
        # the ?id= pivot an operator lands on from /slowlog
        one = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/traces?id={tid}", timeout=10
            ).read()
        )
        assert len(one) == 1 and one[0]["trace_id"] == tid
        # /slowlog carries the same id
        slow = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port}/slowlog", timeout=10).read()
        )
        assert any(r.get("trace_id") == tid for r in slow)
    finally:
        st.close()


def test_remote_sampled_statement_records_store_spans():
    """Wire propagation: a coin-sampled statement against a remote store
    grafts the STORE-recorded spans (tagged @host:port) into the reservoir
    entry — the full distributed tree, with no TRACE statement involved."""
    from tidb_tpu.kv.memstore import MemStore
    from tidb_tpu.kv.remote import StoreServer
    from tidb_tpu.session.session import open_db

    store = MemStore(region_split_keys=100)
    srv = StoreServer(store)
    port = srv.start()
    try:
        db = open_db(remote=f"127.0.0.1:{port}")
        s = db.session()
        s.execute("SET tidb_isolation_read_engines = 'host'")
        s.execute("CREATE TABLE r (id BIGINT PRIMARY KEY, v BIGINT)")
        s.execute("INSERT INTO r VALUES " + ",".join(f"({i},{i})" for i in range(300)))
        s.execute("SET tidb_tpu_trace_sample_rate = 1")
        s.query("SELECT COUNT(*) FROM r")
        e = db.trace_reservoir.traces()[-1]
        nodes = {sp[4] for sp in e.spans}
        assert f"127.0.0.1:{port}" in nodes, e.spans  # remote-recorded spans
        assert any(sp[0].startswith("cop-rpc.r") for sp in e.spans)
    finally:
        srv.shutdown()


# -- per-shard MPP straggler attribution ------------------------------------


@pytest.fixture()
def mpp_db():
    import numpy as np

    from tidb_tpu.executor.load import bulk_load

    db = tidb_tpu.open()
    db.execute("CREATE TABLE so (k BIGINT PRIMARY KEY, d BIGINT)")
    db.execute("CREATE TABLE sl (k BIGINT, p BIGINT)")
    rng = np.random.default_rng(11)
    bulk_load(db, "so", [np.arange(400, dtype=np.int64), rng.integers(0, 20, 400)])
    bulk_load(db, "sl", [rng.integers(0, 400, 4000), rng.integers(1, 100, 4000)])
    s = db.session()
    s.execute("ANALYZE TABLE so")
    s.execute("ANALYZE TABLE sl")
    s.execute("SET tidb_enforce_mpp = 1")
    return db, s


@pytest.fixture
def shard_probes(monkeypatch):
    """The straggler probes are off as shipped (a program with a host callback
    does not persist in the compile cache): a test that reads the per-shard
    breakdown turns them on; the ``mpp_shard_slow`` failpoint does by itself."""
    from tidb_tpu.parallel import gather

    monkeypatch.setattr(gather, "PROBES_ENABLED", True)


def test_mpp_per_shard_breakdown(mpp_db, shard_probes):
    """Every MPP gather records one [shard, ms, rows, bytes] row per mesh
    shard, rendered into the mpp_task line and fed to MPP_SHARD_SECONDS."""
    from tidb_tpu.utils import metrics as _m

    db, s = mpp_db
    q = "SELECT d, SUM(p) FROM sl, so WHERE sl.k = so.k GROUP BY d"
    before = _m.MPP_SHARD_SECONDS.count
    s.query(q)
    if not s.mpp_details:
        pytest.skip("planner did not choose MPP on this host")
    det = s.mpp_details[0]
    assert det.shards, "fragment program recorded no shard probes"
    assert len(det.shards) == det.ndev
    assert {int(sh[0]) for sh in det.shards} == set(range(det.ndev))
    assert all(sh[1] >= 0 for sh in det.shards)
    assert any(sh[3] > 0 for sh in det.shards)  # exchange moved bytes
    assert _m.MPP_SHARD_SECONDS.count >= before + det.ndev
    line = det.render()
    assert re.search(r"shards: \d+, shard max/min/p95: [\d.]+/[\d.]+/[\d.]+ms, slowest: shard \d+", line), line


@pytest.mark.chaos
def test_mpp_straggler_named_from_explain_analyze(mpp_db):
    """The acceptance shape: with an injected sleep on one shard, EXPLAIN
    ANALYZE's mpp_task line names that shard as slowest — a straggler is
    identifiable by id from the SQL surface alone."""
    db, s = mpp_db
    q = "SELECT d, SUM(p) FROM sl, so WHERE sl.k = so.k GROUP BY d"
    s.query(q)  # warm: compile outside the injected window
    if not s.mpp_details:
        pytest.skip("planner did not choose MPP on this host")
    ndev = s.mpp_details[0].ndev
    if ndev < 2:
        pytest.skip("single-device mesh: no straggler to attribute")
    victim = ndev - 2  # any non-trivial shard id

    def slow_shard(i):
        if i == victim:
            import time

            time.sleep(0.25)

    with failpoint.enabled("mpp_shard_slow", slow_shard):
        rows = s.execute("EXPLAIN ANALYZE " + q).rows
    text = "\n".join(r[0] for r in rows)
    m = re.search(r"slowest: shard (\d+)", text)
    assert m, text
    assert int(m.group(1)) == victim, text
    # and the slow shard's recorded time dominates
    det = s.mpp_details[0]
    by_id = {int(sh[0]): float(sh[1]) for sh in det.shards}
    others = [ms for i, ms in by_id.items() if i != victim]
    assert by_id[victim] >= max(others) + 200.0, by_id


def test_mpp_remote_dispatch_ships_shard_breakdown(shard_probes):
    """Remote MPP: the server's shard probes travel home in the exec
    sidecar, so the dispatching SQL layer renders the same straggler line."""
    import numpy as np

    from tidb_tpu.executor.load import bulk_load
    from tidb_tpu.kv.memstore import MemStore
    from tidb_tpu.kv.remote import StoreServer
    from tidb_tpu.session.session import open_db

    store = MemStore()
    srv = StoreServer(store)
    port = srv.start()
    try:
        db = open_db(remote=f"127.0.0.1:{port}")
        db.execute("CREATE TABLE ro (k BIGINT PRIMARY KEY, d BIGINT)")
        db.execute("CREATE TABLE rl (k BIGINT, p BIGINT)")
        rng = np.random.default_rng(5)
        bulk_load(db, "ro", [np.arange(400, dtype=np.int64), rng.integers(0, 20, 400)])
        bulk_load(db, "rl", [rng.integers(0, 400, 4000), rng.integers(1, 100, 4000)])
        s = db.session()
        s.execute("ANALYZE TABLE ro")
        s.execute("ANALYZE TABLE rl")
        s.execute("SET tidb_enforce_mpp = 1")
        s.query("SELECT d, SUM(p) FROM rl, ro WHERE rl.k = ro.k GROUP BY d")
        if not s.mpp_details:
            pytest.skip("planner did not choose MPP on this host")
        det = s.mpp_details[0]
        assert det.store, "expected the remote-dispatch path"
        assert det.shards and len(det.shards) == det.ndev, det.shards
        assert "slowest: shard" in det.render()
    finally:
        srv.shutdown()


# -- misc glue ---------------------------------------------------------------


def test_trace_statement_inside_sampled_session():
    """TRACE under an armed sampling coin: the explicit TRACE wins its
    statement, the sampler still deposits its own (outer) trace, and nothing
    leaks into the next statement."""
    db, s = _mk_db()
    s.execute("SET tidb_tpu_trace_sample_rate = 1")
    res = s.execute("TRACE SELECT COUNT(*) FROM t")
    assert res.columns == ["operation", "startTS", "duration"]
    assert s.tracer is None
    assert s.query("SELECT COUNT(*) FROM t") == [(300,)]


def test_reservoir_unit_roundtrip():
    r = TraceReservoir(capacity=2, slow_capacity=1)
    for i in range(4):
        r.add(TraceEntry(f"t{i}", float(i), f"q{i}", "", 0.01, slow=(i == 0), spans=[]))
    # ring keeps the 2 newest; t0 survives only through tail-keep
    ids = {e.trace_id for e in r.traces()}
    assert ids == {"t0", "t2", "t3"}
    assert r.get("t1") is None
    assert r.get("t0").slow
    r.clear()
    assert len(r) == 0 and r.traces() == []
