"""wire + session + planner: time in `tidb:plan`: the plan cache's key and lookup, on a miss the
planner. Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.sum_ms("plan")
