"""cop dispatch + bind + decode: regions served per cop task of the traced
window: the sum of `regions` over the program's `tidb:cop.task` spans ÷ their
number. A span without the stat counts 1 (a program from before the batch cop
task served one region a task); so does the task of a region that left a batch.
It says whether a statement's clean regions went to the engine as one task."""
from harness.program_spans import of_run

UNIT = "regions/task"


def read(ctx):
    spans = of_run(ctx)
    if spans is None:
        return None
    tasks = spans.inside("cop.task", *ctx.trace_window)
    if not tasks:
        return None
    return sum(int(s.stats.get("regions", 1)) for s in tasks) / len(tasks)
