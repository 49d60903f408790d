"""device: first device idle while NO `tidb:execute` span of any connection is
open: wire, session, planner, the client's turn. Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.idle_ms("frontend")
