"""cop dispatch + bind + decode: CPU time of the threads that ran the cop tasks of
the traced window (`cpu_us` of `tidb:cop.task`, `time.thread_time()` over the
span, taken by the program), per statement answered in it. Beside the phase
walls it says whether a task computes in Python or sleeps on the runtime."""
from harness.program_spans import ms_per_statement

UNIT = "ms"


def read(ctx):
    return ms_per_statement(ctx, "cop.task", stat="cpu_us", scale=1e-3)
