"""Two-process execution: a SQL-layer process over a storage-server process
(ref: the TiDB↔TiKV seam — kv.Storage over the wire, coprocessor DAGs
executed store-side: copr/coprocessor.go:87, kv/mpp.go:189). The server
subprocess owns the MemStore + engines; this process plans SQL and ships
DAG/percolator verbs over TCP."""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import tidb_tpu

_SERVER_SCRIPT = r"""
import sys, time
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import tidb_tpu
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.kv.remote import StoreServer

db = tidb_tpu.open(region_split_keys=200_000)
db.execute("CREATE TABLE li (flag VARCHAR(1), qty DECIMAL(10,2), price DECIMAL(12,2), sd DATE)")
rng = np.random.default_rng(4)
n = 600_000
bulk_load(db, "li", [
    np.array([b"A", b"N", b"R"], dtype="S1")[rng.integers(0, 3, n)],
    rng.integers(100, 5100, n),
    rng.integers(1000, 900000, n),
    8036 + rng.integers(0, 2525, n),
])
db.execute("CREATE TABLE kvt (id BIGINT PRIMARY KEY, v BIGINT)")
db.execute("INSERT INTO kvt VALUES (1, 10), (2, 20)")
db.execute("CREATE TABLE kd (id BIGINT PRIMARY KEY, grp BIGINT)")
db.execute("INSERT INTO kd VALUES " + ", ".join("(%d, %d)" % (i, i % 5) for i in range(100, 400)))
db.execute("CREATE TABLE d (id BIGINT PRIMARY KEY, grp BIGINT)")
db.execute("INSERT INTO d VALUES " + ", ".join("(%d, %d)" % (i, i % 7) for i in range(100, 700)))
srv = StoreServer(db.store)
port = srv.start()
print(f"PORT {{port}}", flush=True)
while True:
    time.sleep(1)
"""


def _start_server():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SCRIPT.format(repo=repo)],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    got: list = []

    def reader():
        for line in proc.stdout:
            if line.startswith("PORT "):
                got.append(int(line.split()[1]))
                return

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    t.join(timeout=120)
    if not got:
        proc.kill()
        raise RuntimeError("server did not report a port within 120s")
    return proc, got[0]


@pytest.fixture(scope="module")
def remote():
    proc, port = _start_server()
    db = tidb_tpu.open(remote=f"127.0.0.1:{port}")
    yield proc, db
    if proc.poll() is None:
        proc.kill()
        proc.wait()


def test_q1_against_remote_regions(remote):
    _, db = remote
    s = db.session()
    # schema resolved through the remote catalog KV
    rows = s.query(
        "SELECT flag, SUM(qty), AVG(price), COUNT(*) FROM li"
        " WHERE sd <= DATE '1998-09-02' GROUP BY flag ORDER BY flag"
    )
    assert [r[0] for r in rows] == ["A", "N", "R"]
    total = sum(r[3] for r in rows)
    expected = s.query("SELECT COUNT(*) FROM li WHERE sd <= DATE '1998-09-02'")[0][0]
    assert total == expected > 0
    # multi-region fan-out really happened (600k rows / 200k split keys)
    from tidb_tpu.kv import tablecodec

    t = db.catalog.table("test", "li")
    regions = db.store.pd.regions_in_ranges([tablecodec.record_range(t.id)])
    assert len(regions) > 1


def test_point_get_and_dml_through_the_wire(remote):
    _, db = remote
    s = db.session()
    assert s.query("SELECT v FROM kvt WHERE id = 1") == [(10,)]
    s.execute("INSERT INTO kvt VALUES (3, 30)")
    s.execute("UPDATE kvt SET v = 21 WHERE id = 2")
    assert s.query("SELECT id, v FROM kvt ORDER BY id") == [(1, 10), (2, 21), (3, 30)]
    # explicit txn: percolator verbs travel the wire
    s.execute("BEGIN")
    s.execute("INSERT INTO kvt VALUES (4, 40)")
    assert s.query("SELECT COUNT(*) FROM kvt") == [(4,)]
    s.execute("ROLLBACK")
    assert s.query("SELECT COUNT(*) FROM kvt") == [(3,)]


def test_bulk_load_strings_over_the_wire(remote):
    """Columnar ingest from the SQL-layer process: the codes it ships index
    ITS dictionary, and the server must re-encode them into the store's own
    table dictionary — it used to keep them, and every later read of the
    column died decoding codes its (empty) dictionary never held."""
    import numpy as np

    from tidb_tpu.executor.load import bulk_load

    _, db = remote
    db.execute("CREATE TABLE ws (flag VARCHAR(1), tag VARCHAR(8), v BIGINT)")
    n = 3000
    flags = np.array([b"R", b"A", b"N"], dtype="S1")[np.arange(n) % 3]
    tags = [None if i % 11 == 0 else b"t%d" % (i % 5) for i in range(n)]
    bulk_load(db, "ws", [flags, tags, np.arange(n)])
    # a second block whose values arrive in another order (other client codes)
    bulk_load(db, "ws", [flags[::-1].copy(), tags[::-1], np.arange(n, 2 * n)])
    s = db.session()
    q = "SELECT flag, tag, COUNT(*), SUM(v) FROM ws GROUP BY flag, tag ORDER BY flag, tag"
    s.execute("SET tidb_isolation_read_engines = 'host'")
    host = s.query(q)
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    assert s.query(q) == host
    assert sorted({r[0] for r in host}) == ["A", "N", "R"]
    assert sum(r[2] for r in host) == 2 * n
    assert s.query("SELECT COUNT(*) FROM ws WHERE flag = 'A'") == [(2 * n // 3,)]


MPPQ = (
    "SELECT d.grp, COUNT(*), SUM(li.price) FROM li JOIN d ON li.qty = d.id"
    " GROUP BY d.grp ORDER BY d.grp"
)


def test_mpp_dispatched_to_store_server(remote):
    """A remote SQL layer PLANS MPP and the storage server EXECUTES it (ref:
    kv/mpp.go DispatchMPPTask/EstablishMPPConns) — the round-3 silent
    downgrade to serial host Volcano is dead."""
    _, db = remote
    s = db.session()
    lines = "\n".join(r[0] for r in s.query("EXPLAIN " + MPPQ))
    assert "PhysMPPGather" in lines, lines
    rows = s.query(MPPQ)
    s.execute("SET tidb_allow_mpp = 0")
    host_rows = s.query(MPPQ)
    s.execute("SET tidb_allow_mpp = 1")
    assert rows == host_rows
    assert len(rows) == 7 and sum(r[1] for r in rows) > 0


def test_remote_mpp_carries_warnings(remote):
    """Warnings born inside the storage server's MPP task (division by 0 in
    an agg argument) must cross mpp_conn back into THIS session — the
    per-SelectResponse warning carriage of the reference (tipb)."""
    _, db = remote
    s = db.session()
    s.execute("CREATE TABLE IF NOT EXISTS wmp (id BIGINT PRIMARY KEY, g BIGINT, z BIGINT)")
    s.execute("DELETE FROM wmp")
    s.execute("INSERT INTO wmp VALUES " + ", ".join(f"({i}, {i % 3}, {i % 2})" for i in range(60)))
    s.execute("ANALYZE TABLE wmp")
    s.execute("SET tidb_enforce_mpp = 1")
    try:
        lines = "\n".join(r[0] for r in s.query("EXPLAIN SELECT g, SUM(id / z) FROM wmp GROUP BY g ORDER BY g"))
        assert "PhysMPPGather" in lines, lines
        rows = s.execute("SELECT g, SUM(id / z) FROM wmp GROUP BY g ORDER BY g").rows
        warns = s.execute("SHOW WARNINGS").rows
        assert len(rows) == 3
        assert any(w[1] == 1365 for w in warns), warns
    finally:
        s.execute("SET tidb_enforce_mpp = 0")


def test_mpp_remote_txn_dirty_falls_back(remote):
    """The server cannot see this session's uncommitted buffer — a dirty
    transaction must fall back to the host path and still see its own
    writes (the reference keeps MPP off dirty reads the same way)."""
    _, db = remote
    s = db.session()
    s.execute("BEGIN")
    s.execute("INSERT INTO d VALUES (100000, 6)")
    with_dirty = s.query(MPPQ)
    s.execute("ROLLBACK")
    clean = s.query(MPPQ)
    assert with_dirty == clean  # key 100000 joins no li row; plans must agree


def test_mpp_remote_ddl_resync(remote):
    """DDL done by the client lands in the server's catalog snapshot before
    the next dispatch resolves table ids (schema_ver handshake)."""
    _, db = remote
    s = db.session()
    s.execute("CREATE TABLE d2 (id BIGINT PRIMARY KEY, grp BIGINT)")
    s.execute("INSERT INTO d2 VALUES (100, 1), (101, 2)")
    q = (
        "SELECT d2.grp, COUNT(*) FROM li JOIN d2 ON li.qty = d2.id"
        " GROUP BY d2.grp ORDER BY d2.grp"
    )
    lines = "\n".join(r[0] for r in s.query("EXPLAIN " + q))
    assert "PhysMPPGather" in lines, lines
    rows = s.query(q)
    assert len(rows) == 2 and all(r[1] > 0 for r in rows)


def test_killing_the_remote_mid_query_surfaces(remote):
    proc, db = remote
    s = db.session()
    errs: list = []
    started = threading.Event()

    def hammer():
        # alternate a cop query and an MPP dispatch so the SIGKILL lands
        # mid-flight on both protocols (ref: the mid-query region-error path)
        try:
            started.set()
            for i in range(200):
                if i % 2:
                    s.query(
                        "SELECT kd.grp, COUNT(*) FROM li JOIN kd ON li.qty = kd.id GROUP BY kd.grp"
                    )
                else:
                    s.query("SELECT flag, COUNT(*) FROM li GROUP BY flag")
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=hammer)
    t.start()
    started.wait()
    time.sleep(0.3)
    proc.send_signal(signal.SIGKILL)
    proc.wait()
    t.join(timeout=60)
    assert not t.is_alive(), "query thread hung after server death"
    assert errs, "killing the store mid-query must surface an error"
    assert isinstance(errs[0], (ConnectionError, RuntimeError, OSError)), errs[0]

