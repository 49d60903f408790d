"""bind + H2D: bytes the cop tasks of the window uploaded (the program's
ExecDetails counter), per statement."""
UNIT = "B"


def read(ctx):
    tasks = [t for cops in ctx.cop_by_stmt for c in cops for t in c["tasks"]]
    if not ctx.statements or not tasks:
        return None
    return sum(t["h2d_bytes"] for t in tasks) / len(ctx.statements)
