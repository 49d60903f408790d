"""kernels: the share of the fragment programs' probe rows that were answered
by blocks, in per cent: 100 x the sum of `probe_rows_blocked` over the sum of
`probe_rows` of the traced window's `tidb:mpp.fetch` spans. A program's
direct-address lookup answers "did the row match" for a block of probe rows
in key order from one window of the table's presence bitmap, and for a lane
in no order by one element gather a row; it sees which in its data and
counts both as outputs (padded rows, all shards summed). A span without the
stats is a program's from before them, which gathered an element for every
row: it counts as not blocked, so such a commit reads 0.0. None where the
program wrote no `tidb:mpp.fetch` span at all."""
from harness.program_spans import of_run

UNIT = "%"


def read(ctx):
    spans = of_run(ctx)
    if spans is None:
        return None
    fetches = spans.inside("mpp.fetch", *ctx.trace_window)
    if not fetches:
        return None
    rows = sum(int(s.stats.get("probe_rows", 0)) for s in fetches)
    return 100.0 * sum(int(s.stats.get("probe_rows_blocked", 0)) for s in fetches) / rows if rows else 0.0
