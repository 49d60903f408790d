"""cop dispatch + bind + decode: time inside the program's `tidb:exec.fetch` spans
(`jax.device_get`: blocked on the device's result, then D2H; then the drop of the
device result, `tidb:exec.release` inside it) of the traced window, per statement
answered in it. The wait for the chip, seen from the host."""
from harness.program_spans import ms_per_statement

UNIT = "ms"


def read(ctx):
    return ms_per_statement(ctx, "exec.fetch")
