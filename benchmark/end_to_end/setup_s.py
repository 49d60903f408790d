"""Process start to window start: generate, load, connect, warm, compile."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
