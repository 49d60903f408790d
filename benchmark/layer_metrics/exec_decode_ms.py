"""cop dispatch + bind + decode: time inside the program's `tidb:exec.decode`
spans (kernel warnings, packed buffers -> Chunk) of the traced window, per
statement answered in it."""
from harness.program_spans import ms_per_statement

UNIT = "ms"


def read(ctx):
    return ms_per_statement(ctx, "exec.decode")
