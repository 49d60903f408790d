"""write path: the 95th percentile, over all write transactions of the window,
of BEGIN sent to COMMIT acknowledged (host clock, the writer's side)."""
import numpy as np

UNIT = "ms"


def read(ctx):
    if not ctx.write_log:
        return None
    return float(np.percentile([(w["t_ack"] - w["t_begin"]) * 1e3 for w in ctx.write_log], 95))
