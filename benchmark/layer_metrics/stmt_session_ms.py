"""wire + session + planner: SELF time of `tidb:statement`: the statement-cache lookup, the
schema lease, the exec-details reset, `_select`'s preamble (deadline, memory tracker, resource
group): what no span under the statement names. Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.self_ms("statement")
