"""cop dispatch + bind + decode: device program calls per cop task of the
traced window: the sum of `regions` over the program's `tidb:exec.dispatch`
spans (the stat is the number of programs the span sent; a span without it,
a block's own dispatch or a program from before the stat, counts 1) ÷ the
number of `tidb:cop.task` spans. A batch task that calls one program a region
reads its regions here; one that maps a program over the regions sharing a
padded shape reads 1-3, whatever `cop_regions_per_task` says."""
from harness.program_spans import of_run

UNIT = "programs/task"


def read(ctx):
    spans = of_run(ctx)
    if spans is None:
        return None
    tasks = spans.inside("cop.task", *ctx.trace_window)
    if not tasks:
        return None
    sent = spans.inside("exec.dispatch", *ctx.trace_window)
    return sum(int(s.stats.get("regions", 1)) for s in sent) / len(tasks)
