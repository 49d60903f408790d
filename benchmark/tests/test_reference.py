"""The reference against Q1, Q6 and Q3 worked out by hand on tiny tables, and
the control (the reference in float32) coming out different at a size a test
holds."""

import numpy as np

from generators import tpch
from reference import common, q1, q3, q6
from reference.common import day_of


def li(rows):
    cols = ["l_quantity", "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus", "l_shipdate"]
    out = {}
    for i, c in enumerate(cols):
        v = [r[i] for r in rows]
        out[c] = np.array(v, dtype="S1") if isinstance(v[0], bytes) else np.array(v)
    return out


TINY = li([
    # qty    price   disc tax flag  status shipdate
    (1700, 2116823, 4, 2, b"N", b"O", day_of(1996, 3, 13)),
    (3600, 4598316, 9, 6, b"N", b"O", day_of(1996, 4, 12)),
    (800, 1392832, 10, 2, b"R", b"F", day_of(1994, 1, 29)),
    (2800, 2873352, 6, 6, b"A", b"F", day_of(1994, 4, 21)),
    (2400, 2234808, 5, 0, b"R", b"F", day_of(1994, 6, 30)),
    (100, 100000, 0, 0, b"N", b"O", day_of(1998, 11, 30)),  # after every Q1 cutoff
])


def test_q1_by_hand():
    got = q1.rows(q1.state(TINY, {"delta": 90}))
    assert q1.bind({"delta": 90}) == {"cutoff": "1998-09-02"}
    # A/F: one row
    assert got[0] == ("A", "F", "28.00", "28733.52", "27009.5088", "28630.079328", "28.000000", "28733.520000", "0.060000", "1")
    # N/O: rows 1 and 2 (the 1998-11-30 row is past the cutoff)
    #   disc_price = 21168.23*0.96 + 45983.16*0.91 = 20321.5008 + 41844.6756
    #   charge     = 20321.5008*1.02 + 41844.6756*1.06 = 20727.930816 + 44355.356136
    assert got[1] == ("N", "O", "53.00", "67151.39", "62166.1764", "65083.286952", "26.500000", "33575.695000", "0.065000", "2")
    # R/F: rows 3 and 5; avg_disc = 0.15 / 2
    assert got[2][:4] == ("R", "F", "32.00", "36276.40")
    assert got[2][4] == "33766.1640" and got[2][8] == "0.075000" and got[2][9] == "2"
    assert len(got) == 3


def test_q6_by_hand():
    drawn = {"year": 1994, "discount_pct": 6, "quantity": 25}
    assert q6.bind(drawn) == {"date_lo": "1994-01-01", "date_hi": "1995-01-01", "disc_lo": "0.05", "disc_hi": "0.07", "quantity": "25"}
    # 1994 rows: disc 10 (out), disc 6 qty 28 (qty out), disc 5 qty 24 (in): 22348.08 * 0.05
    assert q6.rows(q6.state(TINY, drawn)) == [("1117.4040",)]
    assert q6.rows(q6.state(TINY, {"year": 1993, "discount_pct": 6, "quantity": 25})) == [(None,)]


def table(names, rows):
    return {c: np.array([r[i] for r in rows], dtype="S10" if isinstance(rows[0][i], bytes) else np.int64) for i, c in enumerate(names)}


MARCH = lambda d: day_of(1995, 3, d)  # noqa: E731
Q3_TINY = {
    "customer": table(["c_custkey", "c_mktsegment"], [(1, b"BUILDING"), (2, b"AUTOMOBILE"), (4, b"BUILDING")]),
    "orders": table(["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"], [
        (1, 1, MARCH(1), 0), (2, 4, MARCH(1), 0), (3, 1, MARCH(1), 0), (4, 1, MARCH(5), 0), (5, 4, MARCH(5), 0),
        (6, 1, MARCH(4), 0), (7, 1, MARCH(1), 0), (8, 1, MARCH(1), 0), (9, 4, MARCH(1), 0),
        (10, 1, MARCH(2), 0), (11, 1, MARCH(2), 0),  # tie on revenue and date across the cut after the tenth row
        (12, 1, MARCH(3), 0),  # the same revenue a day later: behind both
        (13, 2, MARCH(1), 0),  # another segment's customer
        (14, 1, MARCH(15), 0),  # not before the date
        (15, 1, MARCH(1), 0),  # no line shipped after the date: no group
    ]),
    "lineitem": table(["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"], [
        (1, 99000, 0, MARCH(16)), (1, 500000, 0, MARCH(15)),  # the second line shipped ON the date: not counted
        (2, 109000, 10, MARCH(20)),  # 1090.00 x 0.90 = 981.0000
        (3, 50000, 0, MARCH(16)), (3, 47000, 0, MARCH(30)),  # two lines, one group: 970.0000
        (4, 96000, 0, MARCH(16)), (5, 100000, 4, MARCH(16)),  # 960.0000 twice (1000.00 x 0.96), the same date: either order
        (6, 96000, 0, MARCH(16)),  # 960.0000 a day earlier: before both
        (7, 93000, 0, MARCH(16)), (8, 92000, 0, MARCH(16)), (9, 91000, 0, MARCH(16)),
        (10, 50000, 0, MARCH(16)), (11, 50000, 0, MARCH(16)), (12, 50000, 0, MARCH(16)),
        (13, 999000, 0, MARCH(16)), (14, 999000, 0, MARCH(16)), (15, 999000, 0, MARCH(14)),
    ]),
}


def test_q3_by_hand_and_rows_that_tie():
    drawn = {"segment": 1, "day": 15}
    assert q3.bind(drawn) == {"segment": "BUILDING", "date": "1995-03-15"}
    st = q3.state(Q3_TINY, drawn)
    want = [
        ("1", "990.0000", "1995-03-01", "0"), ("2", "981.0000", "1995-03-01", "0"), ("3", "970.0000", "1995-03-01", "0"),
        ("6", "960.0000", "1995-03-04", "0"), ("4", "960.0000", "1995-03-05", "0"), ("5", "960.0000", "1995-03-05", "0"),
        ("7", "930.0000", "1995-03-01", "0"), ("8", "920.0000", "1995-03-01", "0"), ("9", "910.0000", "1995-03-01", "0"),
        ("10", "500.0000", "1995-03-02", "0"),
    ]
    assert q3.rows(st) == want and q3.same(want, st)
    swap = lambda rows, i, j: [rows[j] if k == i else rows[i] if k == j else r for k, r in enumerate(rows)]  # noqa: E731
    eleven = ("11", "500.0000", "1995-03-02", "0")
    # the spec leaves open: the order of rows 5 and 6, and which of orders 10 and 11 is the tenth row
    assert q3.same(swap(want, 4, 5), st) and q3.same(want[:9] + [eleven], st) and q3.same(swap(want, 4, 5)[:9] + [eleven], st)
    assert q3.same([(k, r.rstrip("0"), d, p) for k, r, d, p in want], st)  # '990.' = '990.0000': text, not value
    # and nothing else
    assert not q3.same(swap(want, 3, 4), st)  # order 6 is a day earlier: it comes first
    assert not q3.same(want[:9] + [("12", "500.0000", "1995-03-03", "0")], st)  # a day later: behind 10 and 11
    assert not q3.same(want[:4] + [want[4], want[4]] + want[6:], st)  # one group twice
    assert not q3.same(want[:9] + [("10", "500.0000", "1995-03-02", "1")], st)  # another o_shippriority
    assert not q3.same(want[:9] + [("16", "500.0000", "1995-03-02", "0")], st)  # no such group
    assert not q3.same(want[:9], st) and not q3.same(want + [eleven], st) and not q3.same(None, st)
    assert not q3.same(want[:9] + [("10", None, "1995-03-02", "0")], st)
    # another segment, another date: order 13 alone; the line of order 1 shipped on 03-15 now counts
    assert q3.rows(q3.state(Q3_TINY, {"segment": 0, "day": 14}))[0] == ("13", "9990.0000", "1995-03-01", "0")
    assert q3.rows(q3.state(Q3_TINY, {"segment": 1, "day": 14}))[0] == ("1", "5990.0000", "1995-03-01", "0")
    assert q3.rows(q3.state(Q3_TINY, {"segment": 2, "day": 14})) == [] and q3.same([], q3.state(Q3_TINY, {"segment": 2, "day": 14}))


def test_avg_rounds_half_away_from_zero():
    assert common.avg_text(1, 3, 2, 6) == "0.003333"
    assert common.avg_text(2, 3, 2, 6) == "0.006667"
    assert common.avg_text(5, 1000000, 0, 6) == "0.000005"
    assert common.avg_text(15, 10000000, 0, 6) == "0.000002"  # 0.0000015 -> half up
    assert common.avg_text(-15, 10000000, 0, 6) == "-0.000002"
    assert common.dec_text(-5, 2) == "-0.05" and common.dec_text(123456, 4) == "12.3456"


def test_states_merge_like_one_scan():
    made = tpch.generate(5, {"scale_factor": 0.01, "tables": ["lineitem", "orders"]})
    cols = dict(zip(tpch.COLUMNS["lineitem"], made["lineitem"]))
    half = len(cols["l_quantity"]) // 2
    a = {k: v[:half] for k, v in cols.items()}
    b = {k: v[half:] for k, v in cols.items()}
    for ref, drawn in ((q1, {"delta": 77}), (q6, {"year": 1995, "discount_pct": 4, "quantity": 24})):
        whole = ref.state(cols, drawn)
        assert common.merge_states(ref.state(a, drawn), ref.state(b, drawn)) == whole
        assert ref.rows(whole) != ref.rows(ref.state(a, drawn))


def test_float32_control_differs_from_exact():
    """The control: the same reference in float32. At 120k rows (SF 0.02) its
    sums already differ from the exact ones in every Q1 group and in Q6."""
    made = tpch.generate(9, {"scale_factor": 0.02, "tables": ["lineitem", "orders"]})
    cols = dict(zip(tpch.COLUMNS["lineitem"], made["lineitem"]))
    exact, low = q1.rows(q1.state(cols, {"delta": 90})), q1.rows(q1.state(cols, {"delta": 90}, control=True))
    assert [r[:2] for r in exact] == [r[:2] for r in low]
    assert all(e[5] != c[5] for e, c in zip(exact, low))  # sum_charge
    assert all(e[9] == c[9] for e, c in zip(exact, low))  # counts are counts
    drawn = {"year": 1994, "discount_pct": 6, "quantity": 24}
    assert q6.rows(q6.state(cols, drawn)) != q6.rows(q6.state(cols, drawn, control=True))


def test_q3_float32_control_differs_from_exact():
    """A group is 1-7 lines, so no long sum rounds; the products do: a line's
    price x (100 - discount) at scale 4 is up to 10^9, past float32's 2^24."""
    made = tpch.generate(9, {"scale_factor": 0.02, "tables": ["customer", "orders", "lineitem"]})
    cols = {t: dict(zip(tpch.COLUMNS[t], made[t])) for t in made}
    for drawn in ({"segment": 1, "day": 15}, {"segment": 4, "day": 1}):
        exact, low = q3.state(cols, drawn), q3.state(cols, drawn, control=True)
        assert len(q3.rows(exact)) == 10 and q3.same(q3.rows(exact), exact)
        assert not q3.same(q3.rows(low), exact)
