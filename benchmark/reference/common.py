"""What the per-statement references share: exact decimal text, mergeable
partial states, and the lower-precision arithmetic of the control."""

from __future__ import annotations

import datetime
from fractions import Fraction

import numpy as np

EPOCH = datetime.date(1970, 1, 1)
BLOCK = 65536  # rows per float32 partial sum in the control


def date_text(day: int) -> str:
    return (EPOCH + datetime.timedelta(days=int(day))).isoformat()


def day_of(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


def dec_text(scaled, scale: int) -> str:
    """An integer scaled by 10**scale as DECIMAL text: (1234, 2) -> '12.34'."""
    scaled = int(scaled)
    sign, mag = ("-" if scaled < 0 else ""), abs(scaled)
    if scale == 0:
        return f"{sign}{mag}"
    return f"{sign}{mag // 10**scale}.{mag % 10**scale:0{scale}d}"


def avg_text(total, count: int, scale_in: int, scale_out: int) -> str:
    """sum/count of values scaled by 10**scale_in, rounded half away from
    zero to scale_out digits (MySQL's DECIMAL rounding)."""
    q = Fraction(int(total), int(count)) * 10 ** (scale_out - scale_in)
    n = int(abs(q) + Fraction(1, 2))
    return dec_text(n if q >= 0 else -n, scale_out)


def exact_sum(values: np.ndarray) -> int:
    return int(values.sum(dtype=np.int64))


def float32_sum(values: np.ndarray) -> int:
    """The control's sum: float32 elements and float32 partial sums per block
    of BLOCK rows (what an accelerator's native accumulation would give),
    partials added in float64, then rounded to the column's integer scale."""
    v = values.astype(np.float32)
    if len(v) == 0:
        return 0
    cuts = np.arange(0, len(v), BLOCK)
    parts = np.add.reduceat(v, cuts, dtype=np.float32)
    return int(round(float(parts.astype(np.float64).sum())))


def merge_states(a: dict, b: dict) -> dict:
    """Partial aggregate states are dicts group -> tuple of integers; merging
    adds them element by element."""
    out = dict(a)
    for g, t in b.items():
        out[g] = tuple(x + y for x, y in zip(out[g], t)) if g in out else t
    return out
