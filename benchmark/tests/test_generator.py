"""The generator: dbgen's distributions (clause 4.2.3) and one seed, one data set."""

import numpy as np
import pytest

from generators import tpch

CONFIG = {"scale_factor": 0.02, "tables": ["customer", "orders", "lineitem"]}


@pytest.fixture(scope="module")
def tables():
    made = tpch.generate(2147483659, CONFIG)
    return {t: dict(zip(tpch.COLUMNS[t], cols)) for t, cols in made.items()}


def test_same_seed_same_data_other_seed_other_data():
    a, b, c = (tpch.generate(s, CONFIG) for s in (7, 7, 8))
    for t in a:
        for x, y in zip(a[t], b[t]):
            assert np.array_equal(x, y)
    assert not np.array_equal(a["lineitem"][4][:1000], c["lineitem"][4][:1000])
    big = tpch.generate(2**31 + 12345, CONFIG)  # seeds past 32 signed bits
    assert len(big["orders"][0]) == 30000


def test_row_counts_and_keys(tables):
    o, li, c = tables["orders"], tables["lineitem"], tables["customer"]
    assert len(o["o_orderkey"]) == 30000 and len(c["c_custkey"]) == 3000
    # sparse keys: the first 8 of every 32
    assert set(np.unique((o["o_orderkey"] - 1) % 32)) == set(range(8))
    assert len(np.unique(o["o_orderkey"])) == 30000
    lines = np.bincount(np.searchsorted(o["o_orderkey"], li["l_orderkey"]))
    assert lines.min() == 1 and lines.max() == 7 and abs(lines.mean() - 4.0) < 0.05
    assert (o["o_custkey"] % 3 != 0).all() and o["o_custkey"].max() <= 3000
    # line numbers count from 1 inside each order
    first = np.concatenate([[True], li["l_orderkey"][1:] != li["l_orderkey"][:-1]])
    assert (li["l_linenumber"][first] == 1).all()


def test_value_domains(tables):
    li = tables["lineitem"]
    assert li["l_quantity"].min() == 100 and li["l_quantity"].max() == 5000
    assert set(np.unique(li["l_discount"])) == set(range(11))
    assert set(np.unique(li["l_tax"])) == set(range(9))
    price = tpch.retail_price_cents(li["l_partkey"].astype(np.int64))
    assert np.array_equal(li["l_extendedprice"], li["l_quantity"] // 100 * price)
    assert li["l_extendedprice"].max() < 2**24  # domains.json: three bytes hold it
    assert li["l_shipdate"].min() >= tpch.days(1992, 1, 2) and li["l_shipdate"].max() <= tpch.days(1998, 12, 1)
    assert li["l_shipdate"].max() - tpch.days(1992, 1, 2) < 2**16
    # uniform enough: each discount value near 1/11 of the rows
    share = np.bincount(li["l_discount"]) / len(li["l_discount"])
    assert abs(share - 1 / 11).max() < 0.01


def test_dependent_columns(tables):
    o, li = tables["orders"], tables["lineitem"]
    odate = o["o_orderdate"][np.searchsorted(o["o_orderkey"], li["l_orderkey"])]
    assert ((li["l_shipdate"] - odate >= 1) & (li["l_shipdate"] - odate <= 121)).all()
    assert ((li["l_commitdate"] - odate >= 30) & (li["l_commitdate"] - odate <= 90)).all()
    assert ((li["l_receiptdate"] - li["l_shipdate"] >= 1) & (li["l_receiptdate"] - li["l_shipdate"] <= 30)).all()
    late = li["l_receiptdate"] > tpch.CURRENTDATE
    assert (li["l_returnflag"][late] == b"N").all() and np.isin(li["l_returnflag"][~late], [b"R", b"A"]).all()
    assert ((li["l_linestatus"] == b"O") == (li["l_shipdate"] > tpch.CURRENTDATE)).all()
    # the four (returnflag, linestatus) groups of Q1, and no others
    groups = set(zip(li["l_returnflag"].tolist(), li["l_linestatus"].tolist()))
    assert groups == {(b"A", b"F"), (b"N", b"F"), (b"N", b"O"), (b"R", b"F")}
    # o_totalprice is the sum of its lines' charges, o_orderstatus follows their statuses
    charge = li["l_extendedprice"].astype(np.int64) * (100 - li["l_discount"]) // 100 * (100 + li["l_tax"]) // 100
    total = np.bincount(np.searchsorted(o["o_orderkey"], li["l_orderkey"]), weights=charge)
    assert np.array_equal(total.astype(np.int64), o["o_totalprice"])
    assert set(np.unique(o["o_orderstatus"])) == {b"F", b"O", b"P"}


def test_strings(tables):
    c, li, o = tables["customer"], tables["lineitem"], tables["orders"]
    assert c["c_name"][41] == b"Customer#000000042"
    assert (np.char.str_len(li["l_comment"]) >= 10).all() and li["l_comment"].dtype.itemsize == 43
    assert (np.char.str_len(o["o_comment"]) >= 19).all()
    assert len(np.unique(li["l_comment"])) <= tpch.COMMENT_POOL
    assert set(np.unique(li["l_shipmode"])) == set(tpch.MODES.tolist())
    assert c["c_phone"][0][2:3] == b"-" and int(c["c_phone"][0][:2]) == c["c_nationkey"][0] + 10


def test_refresh_stream_is_disjoint_and_renders(tables):
    txns = tpch.refresh_transactions(11, CONFIG, 5)
    keys = [int(t["rows"]["orders"]["o_orderkey"][0]) for t in txns]
    assert all((k - 1) % 32 in range(8, 16) for k in keys)
    assert not set(keys) & set(tables["orders"]["o_orderkey"].tolist())
    for t in txns:
        n = len(t["rows"]["lineitem"]["l_orderkey"])
        assert 1 <= n <= 7 and (t["rows"]["lineitem"]["l_orderkey"] == t["rows"]["orders"]["o_orderkey"][0]).all()
        assert t["sql"][1].count("(") == n and t["sql"][0].startswith("INSERT INTO orders VALUES (")
