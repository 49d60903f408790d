"""kernels: the share of the grouped aggregation the int8 MXU dot reduced:
100 x the sum of `agg_dot` ÷ the sum of `agg_dot`, `agg_eqmask` and `agg_sort`
over the program's `tidb:exec.dispatch` spans of the traced window. Each stat
is the regions a dispatch sent to programs of that group-by route (padding
slots not counted; a scalar aggregate, Q6, counts in none). A span without
the stats (a program from before them) counts as nothing: such a program
reads nothing here. It says whether Q1's grouped sums ran on the dot or on
the equality mask of the VPU."""
from harness.program_spans import of_run

UNIT = "%"
ROUTES = ("agg_dot", "agg_eqmask", "agg_sort")


def read(ctx):
    spans = of_run(ctx)
    if spans is None:
        return None
    sent = spans.inside("exec.dispatch", *ctx.trace_window)
    total = sum(int(s.stats.get(r, 0)) for s in sent for r in ROUTES)
    if not total:
        return None
    return 100.0 * sum(int(s.stats.get("agg_dot", 0)) for s in sent) / total
