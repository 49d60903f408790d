"""The program's own spans and program names, read from a traced run's
`.xplane.pb` (the same file `trace_reduce` reduces, the same clock: seconds
from the start of the trace).

The program writes a span `tidb:<name>` into the profiler's trace wherever
its one seam (`tidb_tpu/utils/tracing.region`) is called while the profiler
runs; each carries its `meta` as stats, and `stmt`, the id shared by every
span of one statement, cop tasks on pool threads included. A cop program's
XLA module is named after its family (`jit_cop_sel_agg_g2_d(<hash>)`,
`tidb_tpu/ops/dag_kernel.kernel_family`): one event a run on the device
plane's `XLA Modules` line.

A program that has no such spans (a parent commit from before them) gives
empty tables here, and every reader built on this returns None: the metric
is left out of the line.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

from harness import trace_reduce

SPAN_PREFIX = "tidb:"
MODULES_LINE = "XLA Modules"
MODULE_PREFIX = "jit_cop_"


class Span(NamedTuple):
    start: float
    end: float
    stats: dict


class Module(NamedTuple):
    start: float
    end: float
    family: str  # `cop_sel_agg_g2_d`: the module's name less `jit_` and `(<hash>)`


class ProgramSpans:
    def __init__(self):
        self.host: dict[str, list[Span]] = {}  # span name, less the prefix -> spans, by start
        self.modules: dict[int, list[Module]] = {}  # device -> the cop programs it ran, by start

    def inside(self, name: str, lo: float, hi: float) -> list[Span]:
        return [s for s in self.host.get(name, []) if s.start >= lo and s.end <= hi]

    def by_stmt(self, lo: float = float("-inf"), hi: float = float("inf")) -> dict[str, dict[str, list[Span]]]:
        """stmt id -> span name -> spans, over the spans inside [lo, hi]."""
        out: dict[str, dict[str, list[Span]]] = {}
        for name, spans in self.host.items():
            for s in spans:
                if s.start >= lo and s.end <= hi and "stmt" in s.stats:
                    out.setdefault(str(s.stats["stmt"]), {}).setdefault(name, []).append(s)
        return out


def is_delta_family(family: str) -> bool:
    """`_d` marks a program that reads through a delta operand; the tokens
    after `cop_` are executor names, `g<n>`, `d`, `b<n>`."""
    return "d" in family.split("_")[1:]


@functools.lru_cache(maxsize=4)
def load(path: str) -> ProgramSpans:
    from jax.profiler import ProfileData

    out = ProgramSpans()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    if ev.name.startswith(MODULE_PREFIX):
                        family = ev.name[len("jit_"):].split("(", 1)[0]
                        out.modules.setdefault(dev, []).append(
                            Module(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, family))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out.host.setdefault(ev.name[len(SPAN_PREFIX):], []).append(
                            Span(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, dict(ev.stats)))
    for spans in out.host.values():
        spans.sort(key=lambda s: s.start)
    for mods in out.modules.values():
        mods.sort(key=lambda m: m.start)
    return out


def of_run(ctx) -> ProgramSpans | None:
    """The program's spans of the traced run `ctx` describes; None where
    the run was not traced or the program wrote none."""
    if ctx.trace is None or ctx.trace_window is None:
        return None
    path = trace_reduce.newest_xplane(os.path.join(os.path.dirname(ctx.here), "_bench_cache", "trace", ctx.cell["name"]))
    if path is None:
        return None
    spans = load(path)
    return spans if spans.host else None


def ms_per_statement(ctx, name: str, stat: str | None = None, scale: float = 1e3) -> float | None:
    """The sum, over the spans `name` inside the traced window, of their
    duration in ms (or of `stat` x `scale`), per statement answered in it. A
    sum, not a median of per-statement sums: pool threads make the latter a
    matter of attribution. 0.0 where the program writes spans but none of
    this name (no lock was ever contended)."""
    spans = of_run(ctx)
    if spans is None or not ctx.statements:
        return None
    mine = spans.inside(name, *ctx.trace_window)
    if stat is None:
        total = sum(s.end - s.start for s in mine)
    else:
        total = sum(float(s.stats.get(stat, 0)) for s in mine)
    return total * scale / len(ctx.statements)
