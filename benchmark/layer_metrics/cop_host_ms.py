"""cop dispatch + bind + decode: the median, over the traced statements, of
the time inside its cop spans in which no operation ran on the device (both
from the profiler's trace, one clock)."""
import statistics

from harness.trace_reduce import covered, intersect, total, union

UNIT = "ms"


def read(ctx):
    red = ctx.trace
    if red is None or not red.spans.get("cop") or not red.device_ops:
        return None
    cop = union(red.spans["cop"])
    busy = red.busy(min(red.device_ops))
    vals = []
    for lo, hi in red.spans.get("stmt", []):
        mine = intersect(cop, [(lo, hi)])
        if mine:
            vals.append((total(mine) - sum(covered(busy, a, b) for a, b in mine)) * 1e3)
    return statistics.median(vals) if vals else None
