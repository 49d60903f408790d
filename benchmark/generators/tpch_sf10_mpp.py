"""`generators/tpch.py`'s tables, value for value, for TPC-H Q3 in MPP mode at
scale factor 10: made under both policies on the process that the two
generators beside it apply alone, and only for a program whose run of this
deployment can end inside the check's limit.

The two policies, by calling those modules: `tpch_kept_heap.py`'s (the
allocator keeps its heap, so that each 480 MB temporary over a 60M-row column
is not mapped and paged in anew; a program that loads row at a time ends with
exit code 4) and `tpch_limited.py`'s (the process is held to 1 TiB of address
space: a plan that left the MPP gather for the host executor would join
`customer x orders`, 2.25 x 10^13 pairs at SF10, and numpy then refuses the
allocation where the host would otherwise end the process at its resident
limit).

Why refuse a program. The check ends a run at 360 s. A program that builds
Q3's fragment program with a fixed 2^16 group slots (every commit before the
first one with `gather.first_group_slots`) overflows them with SF10's ~115k
groups and builds a second program at 2^18: two compiles of ~113 s each on a
checkout's first run, which then takes 405 s on a TPU v5e host (set-up 340.9
s, of it 226.4 s of compiles), and every statement runs both programs.
Such a program is told so at once, before a table is made, with exit code 4
(not 1, a crash; not 2, the harness finding no device). It is known by what
sizes the first group slots by the probe's rows, `first_group_slots`: a
program that renames it changes `sizes_groups_by_rows` too.
"""

from __future__ import annotations

import sys

from generators import tpch_kept_heap, tpch_limited
from generators.tpch import COLUMNS, refresh_transactions  # noqa: F401  (the harness reads them here)


def sizes_groups_by_rows() -> bool:
    from tidb_tpu.parallel import gather

    return hasattr(gather, "first_group_slots")


def generate(seed: int, config: dict) -> dict:
    sf = config["scale_factor"]
    if not tpch_kept_heap.loads_by_arrays():
        print("benchmark: generator tpch_sf10_mpp: this program loads row at a time (no Dictionary.encode_many): "
              f"at scale factor {sf} its run cannot end inside the check's limit", file=sys.stderr)
        raise SystemExit(4)
    if not sizes_groups_by_rows():
        print("benchmark: generator tpch_sf10_mpp: this program builds Q3's fragment program at 2^16 group slots "
              f"(no gather.first_group_slots) and again at 2^18 once they overflow: at scale factor {sf} its first run "
              "cannot end inside the check's limit", file=sys.stderr)
        raise SystemExit(4)
    tpch_kept_heap.keep_heap()
    return tpch_limited.generate(seed, config)  # sets the address-space limit, then makes tpch's tables
