"""TPC-H Q6 (clause 2.4.6), the forecasting revenue change, over `lineitem`."""

from __future__ import annotations

import numpy as np

from reference.common import date_text, day_of, dec_text, exact_sum, float32_sum

TABLE = "lineitem"


def bind(drawn: dict) -> dict:
    d = drawn["discount_pct"]
    return {
        "date_lo": date_text(day_of(drawn["year"], 1, 1)),
        "date_hi": date_text(day_of(drawn["year"] + 1, 1, 1)),
        "disc_lo": dec_text(d - 1, 2), "disc_hi": dec_text(d + 1, 2),
        "quantity": str(drawn["quantity"]),
    }


def state(cols: dict, drawn: dict, control: bool = False) -> dict:
    """() -> (revenue at scale 4, rows selected)."""
    ship, disc, qty = cols["l_shipdate"], cols["l_discount"], cols["l_quantity"]
    d = drawn["discount_pct"]
    keep = (
        (ship >= day_of(drawn["year"], 1, 1)) & (ship < day_of(drawn["year"] + 1, 1, 1))
        & (disc >= d - 1) & (disc <= d + 1) & (qty < drawn["quantity"] * 100)
    )
    price, disc = cols["l_extendedprice"][keep].astype(np.int64), disc[keep].astype(np.int64)
    if control:
        revenue = float32_sum(price.astype(np.float32) * disc.astype(np.float32))
    else:
        revenue = exact_sum(price * disc)
    return {(): (revenue, int(keep.sum()))}


def rows(st: dict) -> list[tuple]:
    revenue, n = st.get((), (0, 0))
    return [(dec_text(revenue, 4) if n else None,)]
