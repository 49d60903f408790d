"""Analytic statements answered in the window over the window's seconds. The
window runs until the statement in flight at the deadline is answered, so all
the work counts over all the time."""
UNIT = "stmt/s"


def read(ctx):
    return len(ctx.statements) / ctx.window_s if ctx.statements else None
