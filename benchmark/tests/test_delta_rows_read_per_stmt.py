"""`delta_rows_read_per_stmt` on the traces recorded on the v5e: PR 26's
(`recorded_v5e_program_spans.xplane.pb.gz`, the HTAP cell) was written by a
program that read every row of its overlay again and put `delta_rows`, not
`delta_read`, on its `tidb:exec.bind` spans, so it reads the overlays' rows a
statement; PR 25's, from before the program wrote spans, reads nothing. Then
the same recording with `delta_read` put on, as a program that extends the
cached overlay writes it."""

import pytest

from harness import program_spans
from test_program_spans import _ctx, _read

NAME = "delta_rows_read_per_stmt"


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return _ctx(tmp_path_factory, "recorded_v5e_program_spans.xplane.pb.gz", None)


def _delta_binds(ctx):
    binds = program_spans.of_run(ctx).inside("exec.bind", *ctx.trace_window)
    return binds, [s for s in binds if "delta_rows" in s.stats]


def test_a_bind_without_the_stat_counts_the_rows_of_its_overlay(ctx):
    binds, through_a_delta = _delta_binds(ctx)
    assert through_a_delta and not any("delta_read" in s.stats for s in binds)
    want = sum(int(s.stats["delta_rows"]) for s in through_a_delta) / len(ctx.statements)
    assert _read(NAME, ctx) == pytest.approx(want) and want > 0


def test_it_is_the_rows_read_where_the_stat_is_there(ctx, monkeypatch):
    spans = program_spans.of_run(ctx)
    binds, through_a_delta = _delta_binds(ctx)
    stood_in = program_spans.ProgramSpans()
    stood_in.host = dict(spans.host, **{"exec.bind": [
        s._replace(stats=dict(s.stats, delta_read=7)) if "delta_rows" in s.stats else s for s in binds]})
    stood_in.modules = spans.modules
    import layer_metrics.delta_rows_read_per_stmt as reader

    monkeypatch.setattr(reader, "of_run", lambda _ctx: stood_in)
    assert _read(NAME, ctx) == pytest.approx(7 * len(through_a_delta) / len(ctx.statements))


def test_it_reads_nothing_where_the_program_wrote_no_spans(tmp_path_factory):
    old = _ctx(tmp_path_factory, "recorded_v5e.xplane.pb.gz", 30)
    assert _read(NAME, old) is None
    old.trace = None
    assert _read(NAME, old) is None
