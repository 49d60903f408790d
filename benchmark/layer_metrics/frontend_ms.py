"""wire + session + planner: the median, over the window's statements, of the
statement's wall time minus the wall time of its cop spans (host clock)."""
import statistics

UNIT = "ms"


def read(ctx):
    vals = [
        (s["t1"] - s["t0"] - sum(c["wall_s"] for c in cops)) * 1e3
        for s, cops in zip(ctx.statements, ctx.cop_by_stmt) if cops
    ]
    return statistics.median(vals) if vals else None
