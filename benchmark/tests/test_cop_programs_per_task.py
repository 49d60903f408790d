"""`cop_programs_per_task` on the traces recorded on the v5e: PR 26's
(`recorded_v5e_program_spans.xplane.pb.gz`) was written by a program that
sent one program a cop task and put no `regions` on its `tidb:exec.dispatch`
spans, so it reads 1.0; PR 25's, from before the program wrote spans, reads
nothing. Then the same recording with the stat put on: as PR 27's batch task
writes it (a program a region), and as a task that maps one program over the
regions of a padded shape does."""

import pytest

from harness import program_spans
from test_program_spans import _ctx, _read

NAME = "cop_programs_per_task"


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return _ctx(tmp_path_factory, "recorded_v5e_program_spans.xplane.pb.gz", None)


def test_a_dispatch_without_the_stat_counts_one_program(ctx):
    spans = program_spans.of_run(ctx)
    sent = spans.inside("exec.dispatch", *ctx.trace_window)
    tasks = spans.inside("cop.task", *ctx.trace_window)
    assert sent and not any("regions" in s.stats for s in sent)
    assert _read(NAME, ctx) == pytest.approx(len(sent) / len(tasks)) == 1.0


@pytest.mark.parametrize("programs, want", [(22, 22.0), (2, 2.0)], ids=["a_program_a_region", "a_mapped_program_a_shape"])
def test_it_is_programs_sent_over_tasks_where_the_stat_is_there(ctx, monkeypatch, programs, want):
    spans = program_spans.of_run(ctx)
    sent = spans.inside("exec.dispatch", *ctx.trace_window)
    stood_in = program_spans.ProgramSpans()
    stood_in.host = dict(spans.host, **{"exec.dispatch": [s._replace(stats=dict(s.stats, regions=programs)) for s in sent]})
    stood_in.modules = spans.modules
    import layer_metrics.cop_programs_per_task as reader

    monkeypatch.setattr(reader, "of_run", lambda _ctx: stood_in)
    assert _read(NAME, ctx) == pytest.approx(want)


def test_it_reads_nothing_where_the_program_wrote_no_spans(tmp_path_factory):
    old = _ctx(tmp_path_factory, "recorded_v5e.xplane.pb.gz", 30)
    assert _read(NAME, old) is None
    old.trace = None
    assert _read(NAME, old) is None
