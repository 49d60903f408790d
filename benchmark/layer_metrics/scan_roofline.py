"""kernels: the least time the chip could take for the traced statements over
the time its operations took. Least time = bytes / peak HBM bytes/s; bytes =
the sum over the tables a statement reads of rows x the narrowest whole-byte
widths that hold the read columns' spec domains (`domains.json`). Bound by
memory: Q1 and Q6 do a handful of operations per byte, and a join reads each
side at least once. The count is the data's, not the kernel's, so the share
cannot pass 100%. A column `domains.json` lacks is an error, never 0 bytes."""
import json
import os

UNIT = "%"


def least_bytes(mix, rows: dict, statements: list, domains: dict) -> int:
    """The bytes ``statements`` must read at the least, by their templates."""
    per_template = {}
    for name, tpl in mix.templates.items():
        missing = [f"{t}.{c}" for t, cs in tpl.tables.items() for c in cs if c not in domains.get(t, {})]
        if missing:
            raise KeyError(f"domains.json has no width for {', '.join(missing)} (read by queries/{name}.json)")
        per_template[name] = sum(rows[t] * sum(domains[t][c]["bytes"] for c in cs) for t, cs in tpl.tables.items())
    return sum(per_template[s["template"]] for s in statements)


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
    return 100.0 * (least_bytes(ctx.mix, ctx.rows, ctx.statements, domains) / peaks[ctx.device_kind]["hbm_bytes_per_s"]) / busy
