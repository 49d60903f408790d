"""TPC-H Q3 (clause 2.4.3), the shipping priority query, over `customer`,
`orders` and `lineitem`: the ten unshipped orders of a market segment with
the largest revenue.

The spec orders the rows by revenue descending, then `o_orderdate`, and says
nothing of rows that tie on both, among the ten or across the cut after the
tenth. `same` therefore judges an answer instead of one fixed list: it must
have the reference's (revenue, o_orderdate) at every position, and each of
its rows must be a distinct group of the query with exactly those values and
the right `l_orderkey` and `o_shippriority`. Any order among tied rows, and
any choice among rows tied across the cut, passes; nothing else does. `rows`
gives the one list with ties broken by `l_orderkey` ascending.
"""

from __future__ import annotations

from decimal import Decimal, InvalidOperation

import numpy as np

from reference.common import date_text, day_of, dec_text

TABLES = ("customer", "orders", "lineitem")
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
LIMIT = 10


def bind(drawn: dict) -> dict:
    return {"segment": SEGMENTS[drawn["segment"]], "date": date_text(day_of(1995, 3, drawn["day"]))}


def top(orders: dict, lineitem: dict, order_ok: np.ndarray, day: int, control: bool = False) -> dict:
    """Of the orders ``order_ok`` marks, joined to their line items shipped
    after ``day``: revenue = sum(l_extendedprice * (1 - l_discount)) a group
    (l_orderkey, o_orderdate, o_shippriority), an integer at scale 4. Keeps
    the first LIMIT groups and every group tied with the last of them."""
    okey = orders["o_orderkey"][order_ok]
    by_key = np.argsort(okey, kind="stable")
    okey = okey[by_key]
    odate, oprio = orders["o_orderdate"][order_ok][by_key], orders["o_shippriority"][order_ok][by_key]
    keep = lineitem["l_shipdate"] > day
    lkey = lineitem["l_orderkey"][keep]
    at = np.minimum(np.searchsorted(okey, lkey), max(len(okey) - 1, 0))  # o_orderkey is unique: one order a line or none
    hit = okey[at] == lkey if len(okey) else np.zeros(len(lkey), dtype=bool)
    at = at[hit]
    price, disc = lineitem["l_extendedprice"][keep][hit].astype(np.int64), lineitem["l_discount"][keep][hit].astype(np.int64)
    if control:  # float32 elements and a float32 sum a group (1-7 lines: no blocks to speak of)
        each = price.astype(np.float32) * (100 - disc).astype(np.float32)
        revenue = np.zeros(len(okey), dtype=np.float32)
        np.add.at(revenue, at, each)
        revenue = np.rint(revenue.astype(np.float64)).astype(np.int64)
    else:
        revenue = np.zeros(len(okey), dtype=np.int64)
        np.add.at(revenue, at, price * (100 - disc))
    groups = np.flatnonzero(np.bincount(at, minlength=len(okey)))  # an order with no such line is no group
    order = np.lexsort((okey[groups], odate[groups], -revenue[groups]))  # revenue desc, o_orderdate, l_orderkey
    ranked = groups[order]
    if len(ranked) > LIMIT:
        last = ranked[LIMIT - 1]
        tied = (revenue[ranked] == revenue[last]) & (odate[ranked] == odate[last])
        ranked = ranked[: max(LIMIT, int(np.flatnonzero(tied)[-1]) + 1)]
    return {"groups": [(int(okey[g]), int(revenue[g]), int(odate[g]), int(oprio[g])) for g in ranked]}


def state(cols: dict, drawn: dict, control: bool = False) -> dict:
    customer, orders = cols["customer"], cols["orders"]
    day = day_of(1995, 3, drawn["day"])
    segment = customer["c_mktsegment"] == SEGMENTS[drawn["segment"]].encode()
    order_ok = (orders["o_orderdate"] < day) & np.isin(orders["o_custkey"], customer["c_custkey"][segment])
    return top(orders, cols["lineitem"], order_ok, day, control)


def _text(group: tuple) -> tuple:
    key, revenue, odate, prio = group
    return (str(key), dec_text(revenue, 4), date_text(odate), str(prio))


def rows(st: dict) -> list[tuple]:
    return [_text(g) for g in st["groups"][:LIMIT]]


def same(got: list, st: dict) -> bool:
    """``got``: rows of text cells, as the wire gives them. See the module's text."""
    want = rows(st)
    if not isinstance(got, list) or len(got) != len(want) or any(len(g) != 4 or None in g for g in got):
        return False
    try:
        keyed = [(str(int(g[0])), Decimal(g[1]), g[2], str(int(g[3]))) for g in got]
    except (InvalidOperation, ValueError):
        return False
    groups = {(k, Decimal(r), d, p) for k, r, d, p in map(_text, st["groups"])}
    return (
        [(r, d) for _, r, d, _ in keyed] == [(Decimal(r), d) for _, r, d, _ in want]
        and len(set(keyed)) == len(keyed) and all(k in groups for k in keyed)
    )
