"""`cop_task_resolved_pct` on the trace recorded on the v5e by PR 26
(`recorded_v5e_program_spans.xplane.pb.gz`, the HTAP cell): that program's
`tidb:exec.bind` spans say nothing of a resolved task, so it reads 0.0, as
every parent of the PR that added the stat does; PR 25's, from before the
program wrote spans, reads nothing. Then the same recording with `resolved`
put on one bind span a cop task, as a program that keeps its batch tasks
writes it."""

import pytest

from harness import program_spans
from test_program_spans import _ctx, _read

NAME = "cop_task_resolved_pct"


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return _ctx(tmp_path_factory, "recorded_v5e_program_spans.xplane.pb.gz", None)


def _stand_in(ctx, monkeypatch, hows):
    """The recording with `resolved=<how>` on the first bind span of the i-th
    cop task of the window, for each `how` given (None: the span is left as it is)."""
    spans = program_spans.of_run(ctx)
    tasks = spans.inside("cop.task", *ctx.trace_window)
    binds = spans.inside("exec.bind", *ctx.trace_window)
    marked, at = [], 0
    for task, how in zip(tasks, hows):
        while at < len(binds) and binds[at].start < task.start:
            marked.append(binds[at])
            at += 1
        assert at < len(binds) and binds[at].end <= task.end, "a cop task of the recording has a bind span inside it"
        marked.append(binds[at] if how is None else binds[at]._replace(stats=dict(binds[at].stats, resolved=how)))
        at += 1
    marked += binds[at:]
    stood_in = program_spans.ProgramSpans()
    stood_in.host = dict(spans.host, **{"exec.bind": marked})
    stood_in.modules = spans.modules
    import layer_metrics.cop_task_resolved_pct as reader

    monkeypatch.setattr(reader, "of_run", lambda _ctx: stood_in)
    return tasks


def test_a_program_without_the_stat_reads_zero(ctx):
    spans = program_spans.of_run(ctx)
    assert spans.inside("cop.task", *ctx.trace_window)
    assert not any("resolved" in s.stats for s in spans.inside("exec.bind", *ctx.trace_window))
    assert _read(NAME, ctx) == 0.0


def test_it_is_the_share_of_the_tasks_whose_bind_says_hit(ctx, monkeypatch):
    n = len(program_spans.of_run(ctx).inside("cop.task", *ctx.trace_window))
    assert n >= 4
    hows = (["hit", "miss", "stale", None] * n)[:n]  # a miss, a stale one and a task that says nothing are not resolved
    tasks = _stand_in(ctx, monkeypatch, hows)
    assert _read(NAME, ctx) == pytest.approx(100.0 * hows.count("hit") / len(tasks))
    _stand_in(ctx, monkeypatch, ["hit"] * n)
    assert _read(NAME, ctx) == pytest.approx(100.0)


def test_it_reads_nothing_where_the_program_wrote_no_spans(tmp_path_factory):
    old = _ctx(tmp_path_factory, "recorded_v5e.xplane.pb.gz", 30)
    assert _read(NAME, old) is None
    old.trace = None
    assert _read(NAME, old) is None
