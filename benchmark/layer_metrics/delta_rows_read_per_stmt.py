"""bind + H2D: rows point-read from the store to make the delta overlays the
traced window's reads went through, per statement answered in it: the sum of
`delta_read` over the program's `tidb:exec.bind` spans. A span that names its
`delta_rows` but not `delta_read` is a program's from before the stat, which
read every row of its overlay again on every read beside a writer: it counts
its `delta_rows`. A program that extends the cached overlay reads here the
rows committed between two statements, whatever the overlay holds."""
from harness.program_spans import of_run

UNIT = "rows"


def read(ctx):
    spans = of_run(ctx)
    if spans is None or not ctx.statements:
        return None
    binds = spans.inside("exec.bind", *ctx.trace_window)
    return sum(int(s.stats.get("delta_read", s.stats.get("delta_rows", 0))) for s in binds) / len(ctx.statements)
