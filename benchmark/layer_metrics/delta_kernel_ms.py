"""kernels: the device's operation time inside the runs of the cop programs that
read through a delta operand (the `_d` families on the trace's `XLA Modules`
line), in the traced window, per statement answered in it."""
from harness.program_spans import is_delta_family, of_run
from harness.trace_reduce import covered

UNIT = "ms"


def read(ctx):
    spans = of_run(ctx)
    if spans is None or not spans.modules or not ctx.statements:
        return None
    lo, hi = ctx.trace_window
    busy_s = 0.0
    for dev, modules in spans.modules.items():
        busy = ctx.trace.busy(dev)
        busy_s += sum(
            covered(busy, m.start, m.end) for m in modules if is_delta_family(m.family) and m.start >= lo and m.end <= hi
        )
    return busy_s * 1e3 / len(ctx.statements)
