"""kernels: the least time the chip could take for the traced statements over
the time its operations took. Least time = bytes / peak HBM bytes/s; bytes =
rows of the table x the narrowest whole-byte widths that hold the read
columns' spec domains (`domains.json`). Bound by memory: Q1 and Q6 do a
handful of operations per byte. The count is the data's, not the kernel's, so
the share cannot pass 100%."""
import json
import os

UNIT = "%"


def read(ctx):
    red = ctx.trace
    if red is None or not red.device_ops or ctx.platform == "cpu":
        return None  # a rehearsal has no chip to take a share of
    with open(os.path.join(ctx.here, "peaks.json")) as f:
        peaks = json.load(f)
    if ctx.device_kind not in peaks:
        raise KeyError(f"peaks.json has no entry for device kind {ctx.device_kind!r}")
    with open(os.path.join(ctx.here, "domains.json")) as f:
        domains = json.load(f)
    lo, hi = ctx.trace_window
    busy = red.busy_s(lo, hi)
    if busy <= 0:
        return None
    least_bytes = 0
    for s in ctx.statements:
        spec = ctx.mix.templates[s["template"]].spec
        least_bytes += ctx.rows[spec["table"]] * sum(domains[spec["table"]][c]["bytes"] for c in spec["reads"])
    return 100.0 * (least_bytes / peaks[ctx.device_kind]["hbm_bytes_per_s"]) / busy
