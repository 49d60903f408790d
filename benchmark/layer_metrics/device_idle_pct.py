"""device: 1 - (union of the device's operation intervals) / traced window."""
UNIT = "%"


def read(ctx):
    red = ctx.trace
    if red is None or not red.device_ops:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - red.busy_s(lo, hi) / (hi - lo))
