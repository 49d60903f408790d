"""From the profiler's `.xplane.pb` to numbers: which intervals the device was
busy in, which operations took the time, and where the benchmark's own spans
lie on the same clock. Read with nothing but jax (`ProfileData`).

A TPU plane is named `/device:TPU:<n>`; its line `XLA Ops` holds one event
per executed operation, and that line alone is counted (the `Steps` and `XLA
Modules` lines cover the same time again). On the CPU backend (rehearsal
only) there is no device plane: the host thread-pool events that carry an
`hlo_op` stand in, so that the code below runs, and its numbers name `cpu`.
"""

from __future__ import annotations

import bisect
import glob
import os

SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"


def newest_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def covered(merged: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the merged, sorted intervals cover."""
    i = max(bisect.bisect_right(merged, (lo, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < hi:
        total += max(0.0, min(hi, merged[i][1]) - max(lo, merged[i][0]))
        i += 1
    return total


def intersect(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(a: list[tuple[float, float]]) -> float:
    return sum(hi - lo for lo, hi in a)


def short_name(hlo: str) -> str:
    """An operation's trace name is its whole HLO line; keep the result's
    name and type: `%fusion.1 = pred[1,12582912]`."""
    return hlo.split("{", 1)[0].split(" fusion(", 1)[0][:72]


class Reduced:
    """Times in seconds from the start of the trace."""

    def __init__(self):
        self.device_ops: dict[int, list[tuple[float, float, str]]] = {}  # device -> (start, end, name)
        self.spans: dict[str, list[tuple[float, float]]] = {}  # kind -> (start, end)
        self.lines_seen: list[str] = []

    def busy(self, device: int) -> list[tuple[float, float]]:
        return union([(a, b) for a, b, _ in self.device_ops.get(device, [])])

    def busy_s(self, lo: float, hi: float) -> float:
        """Device-busy seconds inside [lo, hi], averaged over the devices used."""
        if not self.device_ops:
            return 0.0
        return sum(covered(self.busy(d), lo, hi) for d in self.device_ops) / len(self.device_ops)

    def top_ops(self, lo: float, hi: float, n: int = 10) -> list[list]:
        by: dict[str, float] = {}
        for ops in self.device_ops.values():
            for a, b, name in ops:
                if a >= lo and b <= hi:
                    by[name] = by.get(name, 0.0) + (b - a)
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, lo: float, hi: float, kinds: list[tuple[str, str]], rest: str) -> list[list]:
        """The idle time of [lo, hi] (the first device's gaps), by what the
        host was doing. ``kinds`` is (span kind, label) from the innermost
        outwards; each label gets the idle time its spans cover beyond the
        kinds before it, and ``rest`` what no span covers."""
        dev = min(self.device_ops) if self.device_ops else None
        idle, at = [], lo
        for a, b in self.busy(dev) if dev is not None else []:
            if b <= lo or a >= hi:
                continue
            if a > at:
                idle.append((at, a))
            at = max(at, b)
        if hi > at:
            idle.append((at, hi))
        out, acc, before = {}, [], 0.0
        for kind, label in kinds:
            acc = union(acc + self.spans.get(kind, []))
            now = total(intersect(idle, acc))
            out[label] = now - before
            before = now
        out[rest] = total(idle) - before
        return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1]) if v > 0]


def reduce_file(path: str, platform: str) -> Reduced:
    from jax.profiler import ProfileData

    red = Reduced()
    data = ProfileData.from_file(path)
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        if is_dev:
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                red.lines_seen.append(f"{plane.name}|{line.name}")
                if line.name != OPS_LINE:
                    continue
                ops = red.device_ops.setdefault(dev, [])
                for ev in line.events:
                    ops.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, short_name(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(SPAN_PREFIX):
                        red.spans.setdefault(name[len(SPAN_PREFIX):], []).append(
                            (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        )
                    elif platform == "cpu" and not name.startswith("end: ") and "hlo_op" in dict(ev.stats):
                        red.device_ops.setdefault(0, []).append(
                            (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, name)
                        )
    for ops in red.device_ops.values():
        ops.sort()
    for spans in red.spans.values():
        spans.sort()
    return red
