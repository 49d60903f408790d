"""What the four `mpp_<phase>_ms` readers share: time inside the program's
`tidb:mpp.<phase>` spans of the traced window, per statement answered in it.
None where the program writes no `tidb:mpp.gather` span (a commit from before
them), so that the line leaves the metric out."""
from harness.program_spans import ms_per_statement, of_run


def ms(ctx, phase: str):
    spans = of_run(ctx)
    if spans is None or not spans.inside("mpp.gather", *ctx.trace_window):
        return None
    return ms_per_statement(ctx, "mpp." + phase)
