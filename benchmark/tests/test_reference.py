"""The reference against Q1 and Q6 worked out by hand on a tiny table, and the
control (the reference in float32) coming out different at a size a test holds."""

import numpy as np

from generators import tpch
from reference import common, q1, q6
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
