"""The statement outside its cop task, from the program's own spans
(`program_spans.load`'s tables; the clock of the device operations).

A statement's spans nest: `tidb:conn.command` ⊃ `statement` ⊃ `parse`, `plan`,
`execute` ⊃ `executor.build`, `cop.task` / `mpp.gather`; then `result.rows`,
`stmt.finish`; `conn.write` after `statement`. All carry the statement's
`stmt` id (`c<connection>.<sequence>`), a cop task on a pool thread included.
A span's SELF time is its duration less the union of the spans of the same
statement that lie inside it, whatever thread they ran on, so the self times
and the leaves tile the statement: nothing is counted twice and what no span
names stays with the span it happened under.

Every time is a SUM over the traced window ÷ the ANALYTIC statements answered
in it, in ms (the convention of `exec_*_ms`): the `tidb:statement` spans that
say `type=Select`. The connections that served them are the clients'; a
connection that answered none (HTAP's writer) is left out of the sums. The
device's idle time is cut the same way, by unions of named spans of ANY
connection: the chip does not care whose statement keeps it waiting.

A program without these spans (no `tidb:statement` says its `type`: every
commit before PR 37) gives None, and each reader leaves its metric out.
"""

from __future__ import annotations

from harness import program_spans, trace_reduce
from harness.program_spans import ProgramSpans, Span

FETCHES = ("exec.fetch", "mpp.fetch")  # `device_get`: the wait for the device, then the host's share
TASKS = ("cop.task", "mpp.gather")  # what `tidb:execute` hands the statement to


def self_time(span: Span, same_stmt: list[Span]) -> float:
    """`span`'s seconds less the union of the other spans of its statement
    that lie inside it."""
    inner = [(s.start, s.end) for s in same_stmt if s is not span and s.start >= span.start and s.end <= span.end]
    return (span.end - span.start) - trace_reduce.total(trace_reduce.union(inner))


def idle_intervals(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """What the merged, sorted `busy` leaves of [lo, hi]."""
    idle, at = [], lo
    for a, b in busy:
        if b <= lo or a >= hi:
            continue
        if a > at:
            idle.append((at, a))
        at = max(at, b)
    if hi > at:
        idle.append((at, hi))
    return idle


def idle_cut(idle: list[tuple[float, float]], spans: ProgramSpans) -> dict[str, float]:
    """Seconds of `idle` by what the program was doing: `fetch` inside a
    `device_get` span; `exec_host` under a `tidb:execute` span and outside
    those; `frontend` while no connection has an `execute` span open. Each is
    what its spans cover beyond the one before, so the three sum to `idle`."""
    fetch = trace_reduce.union([(s.start, s.end) for n in FETCHES for s in spans.host.get(n, [])])
    under = trace_reduce.union(fetch + [(s.start, s.end) for s in spans.host.get("execute", [])])
    in_fetch = trace_reduce.total(trace_reduce.intersect(idle, fetch))
    in_exec = trace_reduce.total(trace_reduce.intersect(idle, under))
    return {"fetch": in_fetch, "exec_host": in_exec - in_fetch, "frontend": trace_reduce.total(idle) - in_exec}


class Tree:
    """The spans inside [lo, hi] by statement; `n` analytic statements on
    `conns`; every method a number of ms per analytic statement."""

    def __init__(self, spans: ProgramSpans, lo: float, hi: float):
        by = spans.by_stmt(lo, hi)
        analytic = [sid for sid, t in by.items() if any(str(s.stats.get("type")) == "Select" for s in t.get("statement", []))]
        self.n = len(analytic)
        self.conns = {sid.split(".", 1)[0] for sid in analytic}
        # every statement of the clients' connections: each span beside all the spans of its statement
        self.mine = [
            (t, [s for ss in t.values() for s in ss]) for sid, t in by.items() if sid.split(".", 1)[0] in self.conns
        ]
        self.commands = spans.inside("conn.command", lo, hi)
        self.idle: dict[str, float] | None = None  # `idle_cut`'s, once `of_run` has the device's busy intervals

    def _ms(self, seconds: float) -> float:
        return seconds * 1e3 / self.n

    def sum_ms(self, name: str) -> float:
        return self._ms(sum(s.end - s.start for t, _ in self.mine for s in t.get(name, [])))

    def self_ms(self, name: str) -> float:
        return self._ms(sum(self_time(s, every) for t, every in self.mine for s in t.get(name, [])))

    def covered_ms(self, name: str, by: tuple[str, ...] = TASKS) -> float:
        """Time of the `name` spans that spans `by` of the same statement,
        lying inside them, cover: what a statement spends in its tasks."""
        out = 0.0
        for t, _ in self.mine:
            for s in t.get(name, []):
                out += trace_reduce.total(trace_reduce.union(
                    [(c.start, c.end) for n in by for c in t.get(n, []) if c.start >= s.start and c.end <= s.end]))
        return self._ms(out)

    def turnaround_ms(self) -> float:
        """Per connection of the clients, the end of one `conn.command` to the
        start of the next: the client, the socket both ways and the server
        thread's wake-up. The read itself is under no span."""
        by_conn: dict[str, list[Span]] = {}
        for s in self.commands:
            by_conn.setdefault(f"c{s.stats.get('conn')}", []).append(s)
        gaps = 0.0
        for conn in self.conns:
            mine = by_conn.get(conn, [])  # by start, as the table is
            gaps += sum(max(0.0, b.start - a.end) for a, b in zip(mine, mine[1:]))
        return self._ms(gaps)

    def hit_pct(self, name: str, stat: str, value: str) -> float | None:
        mine = [s for t, _ in self.mine for s in t.get(name, [])]
        if not mine:
            return None
        return 100.0 * sum(1 for s in mine if str(s.stats.get(stat)) == value) / len(mine)

    def idle_ms(self, label: str) -> float | None:
        return None if self.idle is None else self._ms(self.idle[label])


_LAST: tuple = (None, None, None, None)  # (spans, trace, window, Tree): thirteen readers of one run build one tree


def of_run(ctx) -> Tree | None:
    """The tree of the traced run `ctx` describes; None where it was not
    traced or the program says no statement's `type`."""
    global _LAST
    spans = program_spans.of_run(ctx)
    if spans is None:
        return None
    window = tuple(ctx.trace_window)
    if _LAST[0] is not spans or _LAST[1] is not ctx.trace or _LAST[2] != window:
        tree = Tree(spans, *window)
        if tree.n and ctx.trace.device_ops:
            busy = ctx.trace.busy(min(ctx.trace.device_ops))  # the union of a device's operations: once a run
            tree.idle = idle_cut(idle_intervals(busy, *window), spans)
        _LAST = (spans, ctx.trace, window, tree)
    tree = _LAST[3]
    return tree if tree.n else None
