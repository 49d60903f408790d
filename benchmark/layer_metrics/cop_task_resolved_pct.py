"""bind + H2D: the share of the traced window's cop tasks that were answered
from a resolved batch task: 100 x the `tidb:exec.bind` spans that say
`resolved=hit` ÷ the number of `tidb:cop.task` spans. A batch task says on the
bind span it opens first how it met what the task before it derived (`hit`,
`miss`, `stale`); a hit opens no other. A task that is not a batch (a written
region read through its delta, alone beside the batch) and a program from
before the stat say nothing and count as not resolved: such a program reads
0.0, a cell of clean static regions close to 100, one whose every statement
is a batch task and a written region's task close to 50."""
from harness.program_spans import of_run

UNIT = "%"


def read(ctx):
    spans = of_run(ctx)
    if spans is None:
        return None
    tasks = spans.inside("cop.task", *ctx.trace_window)
    if not tasks:
        return None
    binds = spans.inside("exec.bind", *ctx.trace_window)
    return 100.0 * sum(1 for s in binds if str(s.stats.get("resolved", "")) == "hit") / len(tasks)
