"""wire + session + planner: 100 x the `tidb:plan` spans of the clients' connections that
say `cache=hit` ÷ all of them, over the traced window (`harness/span_tree.py`). Every text of a static cell
is planned in the warm-up, so anything under 100 there is a plan the cache lost."""
from harness import span_tree

UNIT = "%"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.hit_pct("plan", "cache", "hit")
