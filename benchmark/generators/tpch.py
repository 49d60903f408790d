"""TPC-H population (spec rev 3, clause 4.2.3) for `customer`, `orders` and
`lineitem`, vectorised in numpy and drawn from one seed. Not a port of dbgen's
random streams: the same DISTRIBUTIONS (value ranges, dependencies between
columns, sparse order keys, 1-7 lines an order), another generator.

Columns come back in the physical form `bulk_load` takes: integers as int64,
DECIMAL(15,2) as scaled integers (cents), DATE as days since 1970-01-01,
strings as fixed-width numpy bytes. The reference reads the same arrays.

Comment columns are drawn from a seeded pool of `COMMENT_POOL` strings of the
spec's lengths (assumed; dbgen's text grammar is not reproduced).
"""

from __future__ import annotations

import numpy as np

from reference.common import date_text, dec_text
from reference.common import day_of as days


STARTDATE = days(1992, 1, 1)
CURRENTDATE = days(1995, 6, 17)
ENDDATE = days(1998, 12, 31)
ORDERDATE_MAX = ENDDATE - 151  # clause 4.2.3: [STARTDATE .. ENDDATE - 151 days]
COMMENT_POOL = 65536

PRIORITIES = np.array([b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED", b"5-LOW"], dtype="S15")
INSTRUCTS = np.array([b"DELIVER IN PERSON", b"COLLECT COD", b"NONE", b"TAKE BACK RETURN"], dtype="S25")
MODES = np.array([b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"], dtype="S10")
SEGMENTS = np.array([b"AUTOMOBILE", b"BUILDING", b"FURNITURE", b"MACHINERY", b"HOUSEHOLD"], dtype="S10")
RETURNED = np.array([b"R", b"A"], dtype="S1")

BASE_ROWS = {"customer": 150_000, "orders": 1_500_000}  # per unit of scale factor
COLUMNS = {
    "customer": ["c_custkey", "c_name", "c_address", "c_nationkey", "c_phone", "c_acctbal",
                 "c_mktsegment", "c_comment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice", "o_orderdate",
               "o_orderpriority", "o_clerk", "o_shippriority", "o_comment"],
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
                 "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus",
                 "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct", "l_shipmode",
                 "l_comment"],
}


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One independent stream per purpose, all from the run's seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(stream)]))


def _text_pool(rng, lo: int, hi: int, n: int = COMMENT_POOL) -> np.ndarray:
    """``n`` lower-case strings with lengths uniform in [lo, hi]."""
    letters = rng.integers(97, 123, (n, hi), dtype=np.uint8)
    letters[rng.random((n, hi)) < 0.15] = 32  # word gaps
    letters[:, 0] = rng.integers(97, 123, n)  # no leading blank
    keep = np.arange(hi)[None, :] < rng.integers(lo, hi + 1, n)[:, None]
    # a string must not END in a blank either: CHAR/VARCHAR comparison pads
    last = np.maximum(keep.sum(1) - 1, 0)
    rows = np.arange(n)
    letters[rows, last] = np.where(letters[rows, last] == 32, 120, letters[rows, last])
    return np.where(keep, letters, 0).astype(np.uint8).view(f"S{hi}").reshape(n)


def _numbered(prefix: bytes, numbers: np.ndarray, width: int) -> np.ndarray:
    """prefix + zero-padded decimal, e.g. Customer#000000042, as fixed bytes."""
    n = len(numbers)
    out = np.empty((n, len(prefix) + width), dtype=np.uint8)
    out[:, : len(prefix)] = np.frombuffer(prefix, dtype=np.uint8)
    rest = numbers.astype(np.int64).copy()
    for k in range(width - 1, -1, -1):
        out[:, len(prefix) + k] = 48 + rest % 10
        rest //= 10
    return out.view(f"S{len(prefix) + width}").reshape(n)


def order_keys(first: int, n: int, refresh: bool = False) -> np.ndarray:
    """Sparse keys: of every 32 consecutive values the first 8 are populated
    (clause 4.2.3); the refresh stream takes the next 8 of each 32."""
    i = np.arange(first, first + n, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1 + (8 if refresh else 0)


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """p_retailprice (clause 4.2.3), in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def gen_orders_lineitem(rng, sf: float, keys: np.ndarray) -> tuple[list, list]:
    """`orders` rows for ``keys`` and their `lineitem` rows; used for the
    initial population and for the refresh stream alike."""
    n = len(keys)
    n_cust, n_part, n_supp = int(sf * 150_000), int(sf * 200_000), int(sf * 10_000)
    j = rng.integers(0, n_cust - n_cust // 3, n)
    custkey = j + j // 2 + 1  # customers whose key is a multiple of 3 place no order
    odate = rng.integers(STARTDATE, ORDERDATE_MAX + 1, n)
    lines = rng.integers(1, 8, n)
    m = int(lines.sum())
    first = np.cumsum(lines) - lines
    oi = np.repeat(np.arange(n, dtype=np.int32), lines)
    linenumber = np.arange(m, dtype=np.int32) - first.astype(np.int32)[oi] + 1

    i32 = np.int32
    partkey = rng.integers(1, n_part + 1, m, dtype=i32)
    supp_i = rng.integers(0, 4, m, dtype=i32)
    suppkey = (partkey + supp_i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1
    qty = rng.integers(1, 51, m, dtype=i32)
    eprice = qty * retail_price_cents(partkey)  # at most 50 x 209,900 cents: int32 holds it
    disc = rng.integers(0, 11, m, dtype=i32)
    tax = rng.integers(0, 9, m, dtype=i32)
    odate_l = odate.astype(i32)[oi]
    ship = odate_l + rng.integers(1, 122, m, dtype=i32)
    commit = odate_l + rng.integers(30, 91, m, dtype=i32)
    receipt = ship + rng.integers(1, 31, m, dtype=i32)
    # returnflag: R or A once received by CURRENTDATE, else N; linestatus: F once shipped, else O
    flag = np.where(receipt <= CURRENTDATE, np.where(rng.random(m) < 0.5, 82, 65), 78).astype(np.uint8).view("S1")
    shipped = ship <= CURRENTDATE
    status = np.where(shipped, 70, 79).astype(np.uint8).view("S1")
    l_comment = _text_pool(rng, 10, 43).take(rng.integers(0, COMMENT_POOL, m, dtype=i32))
    lineitem = [
        keys[oi], partkey, suppkey, linenumber, qty * 100, eprice, disc, tax, flag, status,
        ship, commit, receipt, INSTRUCTS.take(rng.integers(0, 4, m, dtype=i32)),
        MODES.take(rng.integers(0, 7, m, dtype=i32)), l_comment,
    ]

    eprice = eprice.astype(np.int64)
    charge = eprice * (100 - disc) // 100 * (100 + tax) // 100  # dbgen's integer arithmetic
    total = np.add.reduceat(charge, first)
    n_f = np.add.reduceat(shipped.astype(np.int64), first)
    ostatus = np.where(n_f == lines, np.bytes_(b"F"), np.where(n_f == 0, np.bytes_(b"O"), np.bytes_(b"P"))).astype("S1")
    clerk = _numbered(b"Clerk#", rng.integers(1, max(int(sf * 1000), 1) + 1, n), 9)
    o_comment = _text_pool(rng, 19, 78)[rng.integers(0, COMMENT_POOL, n)]
    orders = [
        keys, custkey, ostatus, total, odate, PRIORITIES[rng.integers(0, 5, n)], clerk,
        np.zeros(n, dtype=np.int64), o_comment,
    ]
    return orders, lineitem


def gen_customer(rng, sf: float) -> list:
    n = int(sf * BASE_ROWS["customer"])
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n)
    digits = rng.integers(0, 10, (n, 10))
    phone = np.empty((n, 15), dtype=np.uint8)
    phone[:, 0], phone[:, 1] = 48 + (nation + 10) // 10, 48 + (nation + 10) % 10
    d = 0
    for pos in range(2, 15):
        if pos in (2, 6, 10):
            phone[:, pos] = 45  # '-'
        else:
            phone[:, pos] = 48 + digits[:, d]
            d += 1
    address = _text_pool(rng, 10, 40, n)  # one random string a row
    c_comment = _text_pool(rng, 29, 116)[rng.integers(0, COMMENT_POOL, n)]
    return [
        key, _numbered(b"Customer#", key, 9), address, nation, phone.view("S15").reshape(n),
        rng.integers(-99999, 999999 + 1, n), SEGMENTS[rng.integers(0, 5, n)], c_comment,
    ]


def generate(seed: int, config: dict) -> dict:
    """table name -> list of columns, for the tables the configuration loads."""
    sf = float(config["scale_factor"])
    n_orders = int(sf * BASE_ROWS["orders"])
    orders, lineitem = gen_orders_lineitem(rng_for(seed, 1), sf, order_keys(0, n_orders))
    made = {"orders": orders, "lineitem": lineitem, "customer": gen_customer(rng_for(seed, 2), sf)}
    return {name: made[name] for name in config["tables"]}


def refresh_orders(seed: int, config: dict, n: int) -> tuple[list, list]:
    """The RF1 stream's first ``n`` new orders with their line items (clause
    2.6): keys from the part of the key space the population left empty."""
    return gen_orders_lineitem(rng_for(seed, 3), float(config["scale_factor"]), order_keys(0, n, refresh=True))


# -- writes as SQL text (the refresh stream goes over the wire) ---------------

KINDS = {
    "orders": ["int", "int", "str", "dec2", "date", "str", "str", "int", "str"],
    "lineitem": ["int", "int", "int", "int", "dec2", "dec2", "dec2", "dec2", "str", "str",
                 "date", "date", "date", "str", "str", "str"],
}


def _literal(kind: str, v) -> str:
    if kind == "int":
        return str(int(v))
    if kind == "dec2":
        return dec_text(v, 2)
    if kind == "date":
        return f"'{date_text(v)}'"
    return "'" + bytes(v).decode().replace("'", "''") + "'"


def insert_sql(table: str, cols: list, lo: int, hi: int) -> str:
    """One INSERT for rows [lo, hi) of ``cols``."""
    kinds = KINDS[table]
    rows = ("(" + ", ".join(_literal(k, c[r]) for k, c in zip(kinds, cols)) + ")" for r in range(lo, hi))
    return f"INSERT INTO {table} VALUES " + ", ".join(rows)


def refresh_transactions(seed: int, config: dict, n: int) -> list[dict]:
    """RF1 as ``n`` transactions: one new order and its line items each.
    Each entry: the SQL statements between BEGIN and COMMIT, and the rows
    written as columns by table (what the reference adds once acknowledged)."""
    orders, lineitem = refresh_orders(seed, config, n)
    first = np.concatenate([[0], np.cumsum(np.bincount(np.searchsorted(orders[0], lineitem[0]), minlength=n))])
    out = []
    for i in range(n):
        lo, hi = int(first[i]), int(first[i + 1])
        out.append({
            "sql": [insert_sql("orders", orders, i, i + 1), insert_sql("lineitem", lineitem, lo, hi)],
            "rows": {
                "orders": {c: col[i : i + 1] for c, col in zip(COLUMNS["orders"], orders)},
                "lineitem": {c: col[lo:hi] for c, col in zip(COLUMNS["lineitem"], lineitem)},
            },
        })
    return out
