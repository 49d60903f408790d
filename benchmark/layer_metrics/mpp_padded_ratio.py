"""mpp gather: rows the fragment programs were handed over rows the tables
hold: the sum of `rows_padded` over the sum of `rows_valid` on the traced
window's `tidb:mpp.lanes` spans. Lanes are padded to the next power of two of a
table's own rows a shard, so 1.0 to 2.0; what is above 1 is work on rows of no
table. None where the program writes no such span."""
from harness.program_spans import of_run

UNIT = "ratio"


def read(ctx):
    spans = of_run(ctx)
    if spans is None:
        return None
    lanes = [s for s in spans.inside("mpp.lanes", *ctx.trace_window) if "rows_valid" in s.stats]
    valid = sum(int(s.stats["rows_valid"]) for s in lanes)
    return sum(int(s.stats["rows_padded"]) for s in lanes) / valid if valid else None
