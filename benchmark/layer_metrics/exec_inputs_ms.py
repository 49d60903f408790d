"""bind + H2D: time inside the program's `tidb:exec.inputs` spans (device-input
assembly: device-LRU lookups, H2D puts, cached ranges and counts) of the traced
window, per statement answered in it."""
from harness.program_spans import ms_per_statement

UNIT = "ms"


def read(ctx):
    return ms_per_statement(ctx, "exec.inputs")
