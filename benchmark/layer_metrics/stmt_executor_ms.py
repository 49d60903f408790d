"""wire + session + planner: SELF time of `tidb:execute`: the host executor above its readers
(Q3's TopN, the final aggregate, `CopClient.send` outside its task), less `executor.build`,
`cop.task` and `mpp.gather`. Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.self_ms("execute")
