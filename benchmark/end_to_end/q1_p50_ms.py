"""Median over all `q1` statements of the window, send to last row."""
from end_to_end._latency import percentile_ms

UNIT = "ms"


def read(ctx):
    return percentile_ms(ctx.statements, 50, "q1")
