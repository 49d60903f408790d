"""wire + session + planner: time in `tidb:stmt.finish`: what a statement pays after its answer
is ready (auto-commit, the statement counters, the plan digest, `_assemble_usage`,
`stmt_summary.record`, resource groups, the audit line): `ROADMAP.md` C7 on the session's side. Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.sum_ms("stmt.finish")
