"""The gather's program cache holds executables compiled for the lanes' padded
shapes (PR 29: the compile belongs to the `program` phase), so the key holds
those shapes: a statement whose specs stay the same while a lane's padded
length changes gets a program of its own, where the cached `jax.jit` of
before retraced."""

import pytest

import tidb_tpu
from tidb_tpu.parallel import gather

JOIN = "SELECT o.d, SUM(l.p), COUNT(*) FROM {lines} l JOIN {orders} o ON l.k = o.k GROUP BY o.d ORDER BY o.d"
FEW = list(range(0, 100, 5))  # 20 keys and all 100: the same power-of-two bounds, other padded lengths
ALL = list(range(100))


def _fill(db, orders: str, lines: str, keys) -> None:
    db.execute(f"INSERT INTO {orders} VALUES " + ", ".join(f"({k}, {k % 7})" for k in keys))
    db.execute(f"INSERT INTO {lines} VALUES " + ", ".join(f"({k}, {3 * k})" for k in keys for _ in range(2)))


def _want(keys) -> list:
    ds = sorted({k % 7 for k in keys})
    return [(d, sum(6 * k for k in keys if k % 7 == d), 2 * sum(1 for k in keys if k % 7 == d)) for d in ds]


def _session(db):
    s = db.session()
    s.execute("SET tidb_enforce_mpp = 1")
    return s


def _rows(s, sql: str) -> list:
    out = [tuple(int(c) for c in r) for r in s.query(sql)]
    assert len(s.mpp_details) == 1  # answered by one gather, not by the host after a failed one
    return out


@pytest.fixture
def db():
    db = tidb_tpu.open()
    for orders, lines in (("o1", "l1"), ("o2", "l2")):
        db.execute(f"CREATE TABLE {orders} (k BIGINT PRIMARY KEY, d BIGINT)")
        db.execute(f"CREATE TABLE {lines} (k BIGINT, p BIGINT)")
    return db


def test_a_join_side_that_grows_past_its_padded_length_is_answered(db):
    """Few rows a shard: the join's row caps stay at their floor (64) and the
    keys' bounds in their bucket while the lanes' padded length a shard grows,
    so only the shapes tell the two programs apart."""
    s = _session(db)
    _fill(db, "o1", "l1", FEW)
    sql = JOIN.format(orders="o1", lines="l1")
    assert _rows(s, sql) == _want(FEW)
    programs = len(gather._MPP_FN_CACHE)
    _fill(db, "o1", "l1", [k for k in ALL if k not in FEW])
    assert _rows(s, sql) == _want(ALL)
    assert len(gather._MPP_FN_CACHE) == programs + 1
    assert _rows(s, sql) == _want(ALL) and len(gather._MPP_FN_CACHE) == programs + 1  # and that one is found again


def test_two_tables_of_one_schema_and_different_sizes_do_not_share_an_executable(db):
    s = _session(db)
    _fill(db, "o1", "l1", FEW)
    _fill(db, "o2", "l2", ALL)
    assert _rows(s, JOIN.format(orders="o1", lines="l1")) == _want(FEW)
    assert _rows(s, JOIN.format(orders="o2", lines="l2")) == _want(ALL)
    assert _rows(s, JOIN.format(orders="o1", lines="l1")) == _want(FEW)


def test_a_second_table_pair_in_the_same_buckets_finds_the_first_pair_s_program(db):
    """The reuse the cache exists for: the same statement shape over OTHER
    tables of other sizes, every lane's padded length and every key's bounds
    in the first pair's power-of-two bucket, is answered without a compile."""
    from tidb_tpu.utils import metrics

    s = _session(db)
    nearly = ALL[:90]
    _fill(db, "o1", "l1", ALL)
    _fill(db, "o2", "l2", nearly)
    assert _rows(s, JOIN.format(orders="o1", lines="l1")) == _want(ALL)  # pays the one compile
    programs = len(gather._MPP_FN_CACHE)
    missed = metrics.MPP_PROGRAM_CACHE.get(result="miss")
    assert _rows(s, JOIN.format(orders="o2", lines="l2")) == _want(nearly)
    assert metrics.MPP_PROGRAM_CACHE.get(result="miss") == missed and len(gather._MPP_FN_CACHE) == programs
    assert s.mpp_details[-1].compiles == 0


def test_a_single_reader_gather_whose_table_crosses_a_bucket_is_answered(db):
    """The group capacity comes from ANALYZE's NDV, not from the rows: the spec
    is the same before and after the table grows fivefold."""
    s = _session(db)
    sql = "SELECT d, SUM(k), COUNT(*) FROM o1 GROUP BY d ORDER BY d"

    def want(keys):
        return [(d, sum(k for k in keys if k % 7 == d), sum(1 for k in keys if k % 7 == d)) for d in sorted({k % 7 for k in keys})]

    _fill(db, "o1", "l1", FEW)
    db.execute("ANALYZE TABLE o1")
    assert _rows(s, sql) == want(FEW)
    _fill(db, "o1", "l1", [k for k in ALL if k not in FEW])
    assert _rows(s, sql) == want(ALL)
