"""device: first device idle inside a `tidb:exec.fetch` or `tidb:mpp.fetch` span:
the fetch's HOST share (D2H, unstacking, the wake-up after the device is done), which
`exec_fetch_ms` / `mpp_fetch_ms` lump with the wait for the device. Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.idle_ms("fetch")
