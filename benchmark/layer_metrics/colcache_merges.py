"""bind + H2D: delta-into-base merges the window's cop tasks triggered (the
program's ExecDetails counter). A merge re-uploads dirty blocks."""
UNIT = "count"


def read(ctx):
    tasks = [t for c in ctx.cop for t in c["tasks"]]
    return sum(t["merges"] for t in tasks) if tasks else None
