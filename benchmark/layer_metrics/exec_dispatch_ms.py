"""cop dispatch + bind + decode: time inside the program's `tidb:exec.dispatch`
spans (the host's enqueue of the cop program, `kernel.fn(...)`) of the traced
window, per statement answered in it."""
from harness.program_spans import ms_per_statement

UNIT = "ms"


def read(ctx):
    return ms_per_statement(ctx, "exec.dispatch")
