"""mpp gather: bytes that leave the chips for another chip, per statement:
the sum of `xchg_bytes` over the traced window's `tidb:mpp.fetch` spans over
the statements answered in it. The program reads them off its compiled form:
over its collectives, the buffer's bytes x (ndev - 1) / ndev a chip, all chips
summed; padded buffers count, so it is what the interconnect carries and not
what the rows need. None where the program writes no such stat (a commit from
before it)."""
from harness.program_spans import of_run

UNIT = "B"


def read(ctx):
    spans = of_run(ctx)
    if spans is None or not ctx.statements:
        return None
    mine = [s for s in spans.inside("mpp.fetch", *ctx.trace_window) if "xchg_bytes" in s.stats]
    if not mine:
        return None
    return sum(int(s.stats["xchg_bytes"]) for s in mine) / len(ctx.statements)
