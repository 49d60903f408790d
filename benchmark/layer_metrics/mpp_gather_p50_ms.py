"""The median gather, `MPPGatherExec.execute` entered to returned."""
import statistics

UNIT = "ms"


def read(ctx):
    walls = [g["t1"] - g["t0"] for mine in ctx.mpp_by_stmt for g in mine]
    return 1e3 * statistics.median(walls) if walls else None
