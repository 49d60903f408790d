"""MySQL wire-protocol server: handshake, COM_QUERY text resultsets, NULLs,
errors, USE/COM_INIT_DB, concurrent connections, processlist + KILL
(ref: pkg/server conn.go dispatch + tests/globalkilltest)."""

import threading
import time

import pytest

import tidb_tpu
from tidb_tpu.server import Client, Server
from tidb_tpu.server.client import MySQLError


@pytest.fixture()
def srv():
    db = tidb_tpu.open()
    db.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, s VARCHAR(20), f DOUBLE, d DATE)")
    db.execute("INSERT INTO t VALUES (1, 'hello', 1.5, '2024-03-04'), (2, NULL, NULL, NULL)")
    server = Server(db)
    port = server.start()
    yield server, port
    server.close()


def test_query_roundtrip(srv):
    _, port = srv
    c = Client(port=port)
    assert c.ping()
    rows = c.query("SELECT id, s, f, d FROM t ORDER BY id")
    assert rows == [("1", "hello", "1.5", "2024-03-04"), ("2", None, None, None)]
    assert c.columns == ["id", "s", "f", "d"]
    assert c.query("INSERT INTO t VALUES (3, 'x', 0.25, '2020-01-01')") == 1
    assert c.query("SELECT COUNT(*) FROM t") == [("3",)]
    c.close()


def test_error_and_use(srv):
    _, port = srv
    c = Client(port=port)
    with pytest.raises(MySQLError):
        c.query("SELECT * FROM nonexistent")
    with pytest.raises(MySQLError):
        c.use("nodb")
    c.query("CREATE DATABASE other")
    c.use("other")
    c.query("CREATE TABLE o (a BIGINT)")
    c.query("INSERT INTO o VALUES (7)")
    assert c.query("SELECT a FROM o") == [("7",)]
    c.close()


def test_connect_with_db(srv):
    _, port = srv
    c = Client(port=port, db="test")
    assert c.query("SELECT id FROM t WHERE id = 1") == [("1",)]
    c.close()


def test_concurrent_connections_and_txn_isolation(srv):
    _, port = srv
    c1 = Client(port=port)
    c2 = Client(port=port)
    c1.query("BEGIN")
    c1.query("INSERT INTO t VALUES (10, 'staged', 0.0, NULL)")
    assert c1.query("SELECT COUNT(*) FROM t") == [("3",)]
    assert c2.query("SELECT COUNT(*) FROM t") == [("2",)]  # uncommitted invisible
    c1.query("COMMIT")
    assert c2.query("SELECT COUNT(*) FROM t") == [("3",)]
    c1.close()
    c2.close()


def test_processlist_and_kill(srv):
    server, port = srv
    c1 = Client(port=port)
    c2 = Client(port=port)
    rows = c1.query("SHOW PROCESSLIST")
    ids = {r[0] for r in rows}
    assert len(rows) >= 2
    # find c2's id: it is the one not running the SHOW
    my_id = next(r[0] for r in rows if "PROCESSLIST" in (r[4] or ""))
    other = next(i for i in ids if i != my_id)
    assert c1.query(f"KILL QUERY {other}") == 0
    # killed flag delivers on c2's next statement
    with pytest.raises(MySQLError):
        c2.query("SELECT COUNT(*) FROM t")
    # and clears afterward
    assert c2.query("SELECT COUNT(*) FROM t") == [("2",)]
    c1.close()
    c2.close()


def test_many_threads(srv):
    _, port = srv
    errs = []

    def worker(i):
        try:
            c = Client(port=port)
            for _ in range(5):
                assert c.query("SELECT COUNT(*) FROM t") == [("2",)]
            c.close()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs


def test_binary_prepared_protocol(srv):
    """COM_STMT_PREPARE/EXECUTE/CLOSE — the wire path real drivers use for
    parameterized queries (ref: conn.go:1281-1428 binary protocol)."""
    import datetime

    server, port = srv
    c = Client(port=port)
    c.query("CREATE TABLE bp (id BIGINT PRIMARY KEY, v DECIMAL(8,2), s VARCHAR(16), d DATE, t DATETIME, du TIME)")
    sid, nparams = c.prepare("INSERT INTO bp VALUES (?, ?, ?, ?, ?, ?)")
    assert nparams == 6
    assert c.last_prepare_cols == 0  # DML: no result metadata
    assert c.execute(sid, [1, "12.50", "hello", "2024-03-05", "2024-03-05 10:00:01", "08:30:00"]) == 1
    assert c.execute(sid, [2, None, None, None, None, None]) == 1
    c.stmt_close(sid)

    sid2, np2 = c.prepare("SELECT id, v, s, d, t, du FROM bp WHERE id >= ? ORDER BY id")
    assert np2 == 1
    # prepare-time column definitions (mysql_stmt_result_metadata analog)
    assert c.last_prepare_cols == 6
    rows = c.execute(sid2, [1])
    assert rows == [
        (1, "12.50", "hello", datetime.date(2024, 3, 5),
         datetime.datetime(2024, 3, 5, 10, 0, 1), datetime.timedelta(hours=8, minutes=30)),
        (2, None, None, None, None, None),
    ]
    # re-execute with different params, types carried from first execute
    assert c.execute(sid2, [2]) == [(2, None, None, None, None, None)]
    c.stmt_close(sid2)
    # closed statement is gone
    import pytest as _pytest

    with _pytest.raises(MySQLError):
        c.execute(sid2, [1])
    c.close()


def test_binary_protocol_param_types(srv):
    server, port = srv
    c = Client(port=port)
    c.query("CREATE TABLE bt (a BIGINT, b DOUBLE)")
    sid, _ = c.prepare("INSERT INTO bt VALUES (?, ?)")
    c.execute(sid, [-5, 2.25])
    sid2, _ = c.prepare("SELECT a, b FROM bt WHERE a = ? AND b < ?")
    assert c.execute(sid2, [-5, 3.0]) == [(-5, 2.25)]
    c.close()


def test_caching_sha2_password_auth(srv):
    """caching_sha2_password fast auth, incl. the auth-switch leg when the
    client announces the wrong plugin (ref: conn.go auth-switch)."""
    server, port = srv
    root = Client(port=port)
    root.query("CREATE USER 'sha2u'@'%' IDENTIFIED WITH 'caching_sha2_password' BY 'secret2'")
    root.query("GRANT SELECT ON *.* TO 'sha2u'@'%'")
    # right plugin announced up front
    c = Client(port=port, user="sha2u", password="secret2", auth_plugin="caching_sha2_password")
    assert c.query("SELECT 1 + 1") == [("2",)]
    # wrong plugin announced → server sends AuthSwitchRequest
    c2 = Client(port=port, user="sha2u", password="secret2")
    assert c2.query("SELECT 2 + 2") == [("4",)]
    import pytest as _pytest

    with _pytest.raises(Exception, match="Access denied"):
        Client(port=port, user="sha2u", password="wrong", auth_plugin="caching_sha2_password")


def test_tls_roundtrip():
    """Encrypted wire: SSLRequest upgrade, then normal auth + queries."""
    import tidb_tpu
    from tidb_tpu.server.server import Server

    db = tidb_tpu.open()
    db.execute("CREATE TABLE tlst (id BIGINT PRIMARY KEY, v VARCHAR(8))")
    db.execute("INSERT INTO tlst VALUES (1, 'enc')")
    server = Server(db, tls=True)
    port = server.start()
    try:
        c = Client(port=port, tls=True)
        assert c.tls
        assert c.query("SELECT v FROM tlst WHERE id = 1") == [("enc",)]
        # TLS + caching_sha2 combined
        c.query("CREATE USER 'tu'@'%' IDENTIFIED WITH 'caching_sha2_password' BY 'pw9'")
        c.query("GRANT SELECT ON *.* TO 'tu'@'%'")
        c2 = Client(port=port, user="tu", password="pw9", tls=True, auth_plugin="caching_sha2_password")
        assert c2.query("SELECT COUNT(*) FROM tlst") == [("1",)]
        # plaintext clients still work against a TLS-capable server
        c3 = Client(port=port)
        assert c3.query("SELECT 5") == [("5",)]
        # tls=True against a plaintext server fails with a CLEAR error
        db2 = tidb_tpu.open()
        plain = Server(db2)
        pport = plain.start()
        try:
            try:
                Client(port=pport, tls=True)
                raise AssertionError("tls against plaintext server must fail")
            except MySQLError as e:
                assert "TLS" in str(e)
        finally:
            plain.close()
    finally:
        server.close()


def test_warning_count_on_the_wire(srv):
    """The OK/EOF warning-count field carries session warnings (ref: the
    OK_Packet/EOF_Packet warnings u16 MySQL clients read)."""
    _, port = srv
    c = Client("127.0.0.1", port)
    try:
        rows = c.query("SELECT 1/0")
        assert rows == [(None,)] or rows == [("NULL",)] or rows[0][0] is None
        assert c.warning_count == 1, c.warning_count
        c.query("CREATE TABLE ww (x DECIMAL(6,2), i BIGINT)")
        c.query("INSERT INTO ww VALUES (1.005, '9zz')")
        assert c.warning_count == 2, c.warning_count  # 1265 + 1366
        warns = c.query("SHOW WARNINGS")
        assert len(warns) == 2
        c.query("SELECT 1")
        assert c.warning_count == 0
    finally:
        c.close()


def test_a_closed_server_lets_go_of_its_database():
    """``close()`` has to wake the thread inside ``accept()``: left blocked, it
    holds the server, the DB and the whole store for as long as the process
    lives (ISSUE 35: at SF10 ~13 GB that the benchmark's reference then works
    beside)."""
    import gc
    import threading
    import time
    import weakref

    db = tidb_tpu.open()
    db.execute("CREATE TABLE held (id BIGINT PRIMARY KEY, v BIGINT)")
    db.execute("INSERT INTO held VALUES (1, 10), (2, 20)")
    server = Server(db)
    port = server.start()
    c = Client(port=port, db="test")
    assert c.query("SELECT SUM(v) FROM held") == [("30",)]
    accept = server._accept_thread
    alive = weakref.ref(db.store)
    c.close()
    server.close()
    accept.join(timeout=5)
    assert not accept.is_alive()
    del db, server, c
    for _ in range(50):  # the connection's thread ends on its closed socket
        gc.collect()
        if alive() is None:
            break
        time.sleep(0.1)
    assert alive() is None, [t.name for t in threading.enumerate()]
