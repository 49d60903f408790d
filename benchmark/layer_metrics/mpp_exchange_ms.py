"""kernels: the device's time in collective operations (`all-to-all`,
`all-gather`, `all-reduce`, `reduce-scatter`, `collective-permute`, their
`-start` / `-done` halves included) inside the runs of the MPP fragment
programs (`jit_mpp_*` on the trace's `XLA Modules` line), the mean over the
devices that ran them, per statement answered in the traced window. What the
exchange between the chips costs a statement on the device's clock. None
where the trace holds no such run or none of them holds a collective (a
one-device mesh exchanges nothing)."""
import os

from harness import trace_reduce
from layer_metrics import mpp_kernel_ms

UNIT = "ms"
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter", "collective-permute")


def is_collective(name: str) -> bool:
    """`name` is `trace_reduce.short_name`'s, the instruction's own name and
    its result's type: `%all-gather.3 = s32[4194304]`; the TPU compiler keeps
    jax's spelling for some (`%all_to_all.41`), so `_` reads as `-`."""
    return name.lstrip("%").split(" ", 1)[0].replace("_", "-").startswith(COLLECTIVES)


def seconds_by_device(ctx) -> dict | None:
    """device -> seconds of collective operations inside the fragment programs'
    runs of the traced window. Kept on ``ctx``: two readers ask, and the trace is
    read once (a four-device window holds millions of events)."""
    if ctx.trace is None or ctx.trace_window is None:
        return None
    if not hasattr(ctx, "_mpp_exchange_s"):
        ctx._mpp_exchange_s = _seconds_by_device(ctx)
    return ctx._mpp_exchange_s


def _seconds_by_device(ctx) -> dict | None:
    path = trace_reduce.newest_xplane(os.path.join(os.path.dirname(ctx.here), "_bench_cache", "trace", ctx.cell["name"]))
    by_dev = mpp_kernel_ms.runs(path) if path else {}
    lo, hi = ctx.trace_window
    out = {}
    for dev, spans in by_dev.items():
        inside = trace_reduce.union([(a, b) for a, b in spans if a >= lo and b <= hi])
        if inside:
            out[dev] = sum(trace_reduce.covered(inside, a, b) for a, b, name in ctx.trace.device_ops.get(dev, []) if is_collective(name))
    return out or None


def read(ctx):
    by_dev = seconds_by_device(ctx)
    if not by_dev or not ctx.statements or not any(by_dev.values()):
        return None
    return sum(by_dev.values()) / len(by_dev) * 1e3 / len(ctx.statements)
