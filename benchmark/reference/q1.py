"""TPC-H Q1 (clause 2.4.1), the pricing summary report, over `lineitem`."""

from __future__ import annotations

import numpy as np

from reference.common import avg_text, date_text, day_of, dec_text, exact_sum, float32_sum

TABLE = "lineitem"


def bind(drawn: dict) -> dict:
    """DELTA days before 1998-12-01, as the literal the text carries."""
    return {"cutoff": date_text(day_of(1998, 12, 1) - drawn["delta"])}


def state(cols: dict, drawn: dict, control: bool = False) -> dict:
    """(returnflag, linestatus) -> (sum_qty, sum_price, sum_disc_price,
    sum_charge, sum_disc, count), integers at scales 2, 2, 4, 6, 2, 0."""
    total = float32_sum if control else exact_sum
    keep = cols["l_shipdate"] <= day_of(1998, 12, 1) - drawn["delta"]
    flag, status = cols["l_returnflag"][keep], cols["l_linestatus"][keep]
    qty, price = cols["l_quantity"][keep].astype(np.int64), cols["l_extendedprice"][keep].astype(np.int64)
    disc, tax = cols["l_discount"][keep].astype(np.int64), cols["l_tax"][keep].astype(np.int64)
    if control:
        p32 = price.astype(np.float32)
        disc_price = p32 * (100 - disc).astype(np.float32)
        charge = disc_price * (100 + tax).astype(np.float32)
    else:
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + tax)
    out = {}
    code = flag.view(np.uint8).astype(np.int64) * 256 + status.view(np.uint8)
    for c in np.unique(code):
        m = code == c
        out[(bytes([c // 256]).decode(), bytes([c % 256]).decode())] = (
            total(qty[m]), total(price[m]), total(disc_price[m]), total(charge[m]),
            total(disc[m]), int(m.sum()),
        )
    return out


def rows(st: dict) -> list[tuple]:
    out = []
    for (flag, status), (qty, price, dprice, charge, disc, n) in sorted(st.items()):
        out.append((
            flag, status, dec_text(qty, 2), dec_text(price, 2), dec_text(dprice, 4),
            dec_text(charge, 6), avg_text(qty, n, 2, 6), avg_text(price, n, 2, 6),
            avg_text(disc, n, 2, 6), str(n),
        ))
    return out
