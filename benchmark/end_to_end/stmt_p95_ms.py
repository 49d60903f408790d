"""95th percentile over all analytic statements of the window."""
from end_to_end._latency import percentile_ms

UNIT = "ms"


def read(ctx):
    return percentile_ms(ctx.statements, 95)
