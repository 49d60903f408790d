"""kernels: the device's operation time inside the runs of the MPP fragment
programs (`jit_mpp_<family>(<hash>)` on the trace's `XLA Modules` line; named in
`tidb_tpu/parallel/gather.py`), in the traced window, per statement answered
in it. `harness/program_spans` keeps the cop programs' runs only, so this reads
the line itself. None where the trace holds no such run."""
import os

from harness import trace_reduce
from harness.trace_reduce import covered

UNIT = "ms"
MODULE_PREFIX = "jit_mpp_"


def runs(path: str) -> dict:
    """device -> [(start, end)] of the fragment programs' runs, seconds from the start of the trace."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = int(plane.name.rsplit(":", 1)[1])
        for line in plane.lines:
            if line.name == "XLA Modules":
                out.setdefault(dev, []).extend(
                    (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events if ev.name.startswith(MODULE_PREFIX))
    return out


def read(ctx):
    if ctx.trace is None or ctx.trace_window is None or not ctx.statements:
        return None
    path = trace_reduce.newest_xplane(os.path.join(os.path.dirname(ctx.here), "_bench_cache", "trace", ctx.cell["name"]))
    by_dev = runs(path) if path else {}
    if not any(by_dev.values()):
        return None
    lo, hi = ctx.trace_window
    busy_s = sum(
        covered(ctx.trace.busy(dev), a, b) for dev, spans in by_dev.items() for a, b in spans if a >= lo and b <= hi)
    return busy_s * 1e3 / len(ctx.statements)
