"""Write transactions acknowledged in the window over the window's seconds:
the refresh stream sends each when the one before is acknowledged."""
UNIT = "txn/s"


def read(ctx):
    return len(ctx.write_log) / ctx.window_s if ctx.write_log else None
