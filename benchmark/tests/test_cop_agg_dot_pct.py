"""`cop_agg_dot_pct` on the trace recorded on the v5e by PR 26
(`recorded_v5e_program_spans.xplane.pb.gz`, the HTAP cell): that program's
`tidb:exec.dispatch` spans say nothing of a group-by route, so it reads
nothing, as every parent of the PR that added the stats does; PR 25's, from
before the program wrote spans, reads nothing either. Then the same recording
with the stats put on, as a program that counts its routes writes them."""

import pytest

from harness import program_spans
from test_program_spans import _ctx, _read

NAME = "cop_agg_dot_pct"


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return _ctx(tmp_path_factory, "recorded_v5e_program_spans.xplane.pb.gz", None)


def _stand_in(ctx, monkeypatch, stats):
    """The recording with ``stats[i]`` put on the i-th dispatch span of the window (cycled)."""
    spans = program_spans.of_run(ctx)
    sent = spans.inside("exec.dispatch", *ctx.trace_window)
    stood_in = program_spans.ProgramSpans()
    stood_in.host = dict(spans.host, **{"exec.dispatch": [s._replace(stats=dict(s.stats, **stats[i % len(stats)])) for i, s in enumerate(sent)]})
    stood_in.modules = spans.modules
    import layer_metrics.cop_agg_dot_pct as reader

    monkeypatch.setattr(reader, "of_run", lambda _ctx: stood_in)
    return sent


def test_a_program_without_the_stats_reads_nothing(ctx):
    sent = program_spans.of_run(ctx).inside("exec.dispatch", *ctx.trace_window)
    assert sent and not any(k.startswith("agg_") for s in sent for k in s.stats)
    assert _read(NAME, ctx) is None


@pytest.mark.parametrize("stats", [
    [{"agg_dot": 45, "agg_eqmask": 1}, {}],  # a Q1 task of tpch_sf2, then a Q6 task (a scalar aggregate: no route)
    [{"agg_dot": 22}, {"agg_eqmask": 1}],  # the HTAP cell: the clean regions' call, the written region's `_d` program
    [{"agg_eqmask": 46}],  # every region on the equality mask
    [{"agg_sort": 3, "agg_dot": 1}],
], ids=["sf2", "htap", "eqmask", "sort"])
def test_it_is_the_dots_share_of_the_regions_routed(ctx, monkeypatch, stats):
    sent = _stand_in(ctx, monkeypatch, stats)
    put = [stats[i % len(stats)] for i in range(len(sent))]
    assert len(sent) >= 2 * len(stats)
    want = 100.0 * sum(p.get("agg_dot", 0) for p in put) / sum(sum(p.values()) for p in put)
    assert _read(NAME, ctx) == pytest.approx(want)


def test_it_reads_nothing_where_the_program_wrote_no_spans(tmp_path_factory):
    old = _ctx(tmp_path_factory, "recorded_v5e.xplane.pb.gz", 30)
    assert _read(NAME, old) is None
    old.trace = None
    assert _read(NAME, old) is None
