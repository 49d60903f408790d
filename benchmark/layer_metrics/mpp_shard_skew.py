"""mpp gather: how unevenly the tables' rows lie over the mesh: the fullest
shard's valid rows over the mean valid rows a shard, summed over the traced
window's `tidb:mpp.lanes` spans (`shard_rows_max`, `rows_valid`, `ndev`). 1.0 =
every chip holds the same; `ndev` = one chip holds everything and the others
compute on padding. None where the program writes no such stat."""
from harness.program_spans import of_run

UNIT = "ratio"


def read(ctx):
    spans = of_run(ctx)
    if spans is None:
        return None
    lanes = [s for s in spans.inside("mpp.lanes", *ctx.trace_window) if "shard_rows_max" in s.stats and "ndev" in s.stats]
    mean = sum(int(s.stats["rows_valid"]) / int(s.stats["ndev"]) for s in lanes)
    return sum(int(s.stats["shard_rows_max"]) for s in lanes) / mean if mean else None
