"""device: first device idle under a `tidb:execute` span and outside the
fetches: bind, inputs, dispatch until the first program starts, decode, the executor. Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.idle_ms("exec_host")
