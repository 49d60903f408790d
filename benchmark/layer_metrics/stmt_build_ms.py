"""wire + session + planner: time in `tidb:executor.build` (`build_executor(plan, session)`). Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.sum_ms("executor.build")
