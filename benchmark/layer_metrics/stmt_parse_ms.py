"""wire + session + planner: time in `tidb:parse` (0.0 where every text of the window met the
statement cache: `ast=session`). Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.sum_ms("parse")
