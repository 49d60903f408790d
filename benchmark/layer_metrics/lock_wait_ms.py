"""cop dispatch + bind + decode: time threads spent blocked on the program's
named locks (`tidb:lock.wait`: from the failed try to the acquire; the span's
`lock` names which) in the traced window, per statement answered in it."""
from harness.program_spans import ms_per_statement

UNIT = "ms"


def read(ctx):
    return ms_per_statement(ctx, "lock.wait")
