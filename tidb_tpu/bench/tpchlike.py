"""`chip_smoke.py`'s data: the tables, the loader and the statement texts of
the on-chip smoke, and nothing else's. It is never a benchmark cell and
nothing it runs is timed as a speed: the cells are `BENCHMARK.json`'s, their
data `benchmark/generators/tpch.py`'s.

The data is NOT TPC-H: `lineitem` is seven uniform-random columns (four
DECIMAL(12,2), two VARCHAR(1), one DATE) under TPC-H's column names, and the
Q3-shaped join runs on two narrow tables (`lineitem2` ⋈ `orders`, 10 : 1).
"""

from __future__ import annotations

import time

import numpy as np

Q1 = """SELECT l_returnflag, l_linestatus,
    SUM(l_quantity), SUM(l_extendedprice),
    SUM(l_extendedprice * (1 - l_discount)),
    SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
    AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(*)
  FROM lineitem WHERE l_shipdate <= DATE '1998-09-02'
  GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus"""

Q6 = """SELECT SUM(l_extendedprice * l_discount) FROM lineitem
  WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'
    AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"""

# full-scan count, Q10-style TopN pushdown, Q3-style MPP join (2-way
# exchange), and a windowed statement (ranking + framed agg over sorted
# partitions — the device window kernel)
WINDOWED = """SELECT l_returnflag, MAX(rn), MAX(cum) FROM (
    SELECT l_returnflag,
           ROW_NUMBER() OVER (PARTITION BY l_returnflag ORDER BY l_extendedprice) AS rn,
           SUM(l_quantity) OVER (PARTITION BY l_returnflag ORDER BY l_extendedprice) AS cum
    FROM lineitem WHERE l_shipdate < DATE '1994-01-01') t
    GROUP BY l_returnflag ORDER BY l_returnflag"""
COUNT_STAR = "SELECT COUNT(*) FROM lineitem"
Q10 = """SELECT l_returnflag, l_extendedprice FROM lineitem
  WHERE l_shipdate >= DATE '1994-01-01'
  ORDER BY l_extendedprice DESC LIMIT 20"""
Q3 = """SELECT o_odate, SUM(l_extendedprice) AS rev FROM lineitem2, orders
  WHERE l_orderkey = o_orderkey GROUP BY o_odate ORDER BY rev DESC, o_odate LIMIT 10"""
Q1_ROLLUP = """SELECT l_returnflag, l_linestatus, COUNT(*), SUM(l_quantity),
    SUM(l_extendedprice) FROM lineitem WHERE l_shipdate <= DATE '1998-09-02'
  GROUP BY l_returnflag, l_linestatus WITH ROLLUP
  ORDER BY GROUPING(l_returnflag), GROUPING(l_linestatus), l_returnflag, l_linestatus"""

DDL = {
    "lineitem": """CREATE TABLE lineitem (
        l_quantity DECIMAL(12,2), l_extendedprice DECIMAL(12,2),
        l_discount DECIMAL(12,2), l_tax DECIMAL(12,2),
        l_returnflag VARCHAR(1), l_linestatus VARCHAR(1), l_shipdate DATE)""",
    "orders": "CREATE TABLE orders (o_orderkey BIGINT PRIMARY KEY, o_odate BIGINT)",
    "lineitem2": "CREATE TABLE lineitem2 (l_orderkey BIGINT, l_extendedprice DECIMAL(12,2))",
}

RETURNFLAGS = np.array([b"A", b"N", b"R"], dtype="S1")
LINESTATUSES = np.array([b"F", b"O"], dtype="S1")


def gen_tables(seed: int, n_rows: int, n_join: int) -> dict:
    """table name → list of physical columns (DECIMAL(12,2) as scaled ints,
    DATE as days since the epoch), all drawn from one ``seed``."""
    rng = np.random.default_rng(seed)
    n = n_rows
    lineitem = [
        rng.integers(100, 5100, n),  # qty  (scaled 2)
        rng.integers(100000, 9000000, n),  # extendedprice
        rng.integers(0, 11, n),  # discount
        rng.integers(0, 9, n),  # tax
        RETURNFLAGS[rng.integers(0, 3, n)],
        LINESTATUSES[rng.integers(0, 2, n)],
        8036 + rng.integers(0, 2525, n),  # 1992-01-01 .. ~1998-12
    ]
    # Q3-style join tables: lineitem2 ⋈ orders on an integer key
    n_orders = max(n_join // 10, 1)
    orders = [np.arange(n_orders), 8036 + rng.integers(0, 100, n_orders)]
    lineitem2 = [rng.integers(0, n_orders, n_join), rng.integers(100000, 9000000, n_join)]
    return {"lineitem": lineitem, "orders": orders, "lineitem2": lineitem2}


def load_tables(db, tables: dict) -> float:
    """CREATE + bulk_load + ANALYZE (join tables) on ``db`` — embedded or
    remote-backed alike. Returns the seconds `lineitem`'s load took."""
    from tidb_tpu.executor.load import bulk_load

    load_s = 0.0
    for name in ("lineitem", "orders", "lineitem2"):
        db.execute(DDL[name])
        t0 = time.time()
        bulk_load(db, name, tables[name])
        if name == "lineitem":
            load_s = time.time() - t0
    db.execute("ANALYZE TABLE orders")
    db.execute("ANALYZE TABLE lineitem2")
    return load_s
