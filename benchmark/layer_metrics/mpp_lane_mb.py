"""mpp gather: the device bytes of the input lanes a fragment program reads,
MB per statement: the sum of `lane_bytes` over the traced window's
`tidb:mpp.lanes` spans, over the statements answered in it, / 10^6. Every lane
counts, whether the gather found it resident in the device lane pool or put it
there; the chips of a mesh are summed (a chip holds its shard). A span without
the stat is a program's from before it and counts as nothing; None where no
span carries it."""
from harness.program_spans import of_run

UNIT = "MB"


def read(ctx):
    spans = of_run(ctx)
    if spans is None or not ctx.statements:
        return None
    mine = [s for s in spans.inside("mpp.lanes", *ctx.trace_window) if "lane_bytes" in s.stats]
    if not mine:
        return None
    return sum(int(s.stats["lane_bytes"]) for s in mine) / len(ctx.statements) / 1e6
