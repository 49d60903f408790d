"""wire + session + planner: the server's own time round a statement: SELF time of `tidb:conn.command`
(the command decoded and dispatched, the OK or error packet) plus `tidb:conn.write` (the
result set's encoding and its socket writes). Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.self_ms("conn.command") + tree.sum_ms("conn.write")
