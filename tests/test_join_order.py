"""Planner rules of PR 29: selections pushed through NESTED inner/cross joins
down to the readers (WHERE equalities become join keys at every level), and
the greedy join order: a side joined on a unique key of its own builds, the
side that is not probes; a tree that already builds on unique keys, or carries
a join hint, stays as written."""

import pytest

import tidb_tpu


@pytest.fixture(scope="module")
def db():
    db = tidb_tpu.open()
    db.execute("CREATE TABLE cust (ck BIGINT PRIMARY KEY, seg VARCHAR(10))")
    db.execute("CREATE TABLE ord (ok BIGINT PRIMARY KEY, ck BIGINT, od BIGINT)")
    db.execute("CREATE TABLE item (ok BIGINT, price BIGINT, sd BIGINT)")
    db.execute("INSERT INTO cust VALUES (1, 'A'), (2, 'B'), (3, 'A'), (4, 'C')")
    db.execute("INSERT INTO ord VALUES (10, 1, 5), (11, 1, 9), (12, 2, 3), (13, 3, 4), (14, 4, 1), (15, 3, 8)")
    db.execute("INSERT INTO item VALUES (10, 100, 7), (10, 50, 2), (11, 70, 9), (12, 30, 8), (13, 20, 9), (13, 5, 9), (15, 1, 9), (99, 1000, 9)")
    return db


Q = ("SELECT item.ok, SUM(price), od FROM cust, ord, item WHERE seg = 'A' AND cust.ck = ord.ck AND item.ok = ord.ok"
     " AND od < 8 AND sd > 5 GROUP BY item.ok, od ORDER BY item.ok")
WANT = [(10, 100, 5), (13, 25, 4)]


def _explain(s, sql):
    return "\n".join(r[0] for r in s.query("EXPLAIN " + sql))


@pytest.mark.parametrize("engine", ["host", "tpu"])
def test_where_of_a_comma_join_reaches_every_reader(db, engine):
    s = db.session()
    s.execute(f"SET tidb_isolation_read_engines = '{engine}'")
    s.execute("SET tidb_allow_mpp = 0")
    text = _explain(s, Q)
    assert "cross" not in text and "PhysSelection" not in text, text
    assert text.count("PhysHashJoin inner") == 2, text
    for pushed in ("cust: Scan -> Selection(eq(seg", "ord: Scan -> Selection(lt(od", "item: Scan -> Selection(gt(sd"):
        assert pushed in text, text
    assert [tuple(int(c) for c in r) for r in s.query(Q)] == WANT


def test_join_on_spelling_pushes_its_where_below_the_first_join_too(db):
    s = db.session()
    s.execute("SET tidb_allow_mpp = 0")
    q = ("SELECT item.ok, SUM(price), od FROM cust JOIN ord ON cust.ck = ord.ck JOIN item ON item.ok = ord.ok"
         " WHERE seg = 'A' AND od < 8 AND sd > 5 GROUP BY item.ok, od ORDER BY item.ok")
    text = _explain(s, q)
    assert "PhysSelection" not in text and "cross" not in text, text
    assert [tuple(int(c) for c in r) for r in s.query(q)] == WANT


def _leaves(text: str) -> list[str]:
    return [ln.split("] ")[1].split(":")[0] for ln in text.splitlines() if "PhysTableReader" in ln]


def test_the_side_without_a_unique_join_key_probes(db):
    """As written the chain builds `ord` on `ord.ck` and `item` on `item.ok`,
    neither unique; from `item`, both builds are primary-key lookups."""
    s = db.session()
    s.execute("SET tidb_allow_mpp = 0")
    text = _explain(s, Q)
    assert _leaves(text) == ["item", "ord", "cust"], text
    # the tree's columns come back in the statement's order, under a projection
    assert "PhysProjection" in text.split("PhysHashJoin")[0], text
    s.execute("SET tidb_allow_mpp = 1")
    s.execute("SET tidb_enforce_mpp = 1")
    text = _explain(s, Q)
    assert "lookup ord(unique), cust(unique, in ord)" in text, text
    assert [tuple(int(c) for c in r) for r in s.query(Q)] == WANT


@pytest.mark.parametrize("sql,order,count", [
    ("SELECT COUNT(*) FROM ord, item WHERE item.ok = ord.ok", ["item", "ord"], 7),
    ("SELECT COUNT(*) FROM cust JOIN ord ON cust.ck = ord.ck", ["ord", "cust"], 6),
])
def test_two_tables_swap_when_only_the_written_probe_is_unique(db, sql, order, count):
    s = db.session()
    s.execute("SET tidb_allow_mpp = 0")
    assert _leaves(_explain(s, sql)) == order
    assert [int(r[0]) for r in s.query(sql)] == [count]


@pytest.mark.parametrize("sql", [
    "SELECT COUNT(*) FROM item JOIN ord ON item.ok = ord.ok JOIN cust ON cust.ck = ord.ck",  # builds on two primary keys already
    "SELECT /*+ HASH_JOIN(ord) */ COUNT(*) FROM ord JOIN item ON item.ok = ord.ok",  # a hinted join is the user's
    "SELECT COUNT(*) FROM ord LEFT JOIN item ON item.ok = ord.ok",  # outer joins do not commute
])
def test_trees_that_stay_as_written(db, sql):
    s = db.session()
    s.execute("SET tidb_allow_mpp = 0")
    text = _explain(s, sql)
    written = [w.strip(",") for w in sql.replace("JOIN", ",").split("FROM")[1].split() if w.strip(",") in ("item", "ord", "cust")]
    order = list(dict.fromkeys(written))
    assert _leaves(text) == order, text
