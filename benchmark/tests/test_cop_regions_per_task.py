"""`cop_regions_per_task` on the traces recorded on the v5e: PR 26's
(`recorded_v5e_program_spans.xplane.pb.gz`) was written by a program that
served one region a cop task and put no `regions` on its `tidb:cop.task`
spans, so it reads 1.0; PR 25's, from before the program wrote spans, reads
nothing. Then the same recording with the stat put on, as a batch task does."""

import pytest

from harness import program_spans
from test_program_spans import _ctx, _read

NAME = "cop_regions_per_task"


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return _ctx(tmp_path_factory, "recorded_v5e_program_spans.xplane.pb.gz", None)


def test_a_span_without_the_stat_counts_one_region(ctx):
    tasks = program_spans.of_run(ctx).inside("cop.task", *ctx.trace_window)
    assert tasks and not any("regions" in s.stats for s in tasks)
    assert _read(NAME, ctx) == 1.0


def test_it_is_regions_over_tasks_where_the_stat_is_there(ctx, monkeypatch):
    spans = program_spans.of_run(ctx)
    tasks = spans.inside("cop.task", *ctx.trace_window)
    # every other task a batch of 22 regions, the rest left as they are: HTAP's statement, 22 + 1 in two tasks
    batched = [s._replace(stats=dict(s.stats, regions=22)) if i % 2 == 0 else s for i, s in enumerate(tasks)]
    stood_in = program_spans.ProgramSpans()
    stood_in.host = dict(spans.host, **{"cop.task": batched})
    stood_in.modules = spans.modules
    import layer_metrics.cop_regions_per_task as reader

    monkeypatch.setattr(reader, "of_run", lambda _ctx: stood_in)
    n_batch = (len(tasks) + 1) // 2
    assert _read(NAME, ctx) == pytest.approx((22 * n_batch + (len(tasks) - n_batch)) / len(tasks))


def test_it_reads_nothing_where_the_program_wrote_no_spans(tmp_path_factory):
    old = _ctx(tmp_path_factory, "recorded_v5e.xplane.pb.gz", 30)
    assert _read(NAME, old) is None
    old.trace = None
    assert _read(NAME, old) is None
