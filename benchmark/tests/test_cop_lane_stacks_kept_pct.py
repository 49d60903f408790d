"""`cop_lane_stacks_kept_pct` on the recorded v5e trace of the HTAP cell
(`recorded_v5e_program_spans.xplane.pb.gz`): that program's `tidb:exec.inputs`
spans say nothing of lane stacks, so it reads nothing, as a program from
before the stats does; the older recording, from before the program wrote
spans, reads nothing either. Then the same recording with the stats put on,
as a program that keeps its lanes stacked writes them."""

import pytest

from harness import program_spans
from test_program_spans import _ctx, _read

NAME = "cop_lane_stacks_kept_pct"


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return _ctx(tmp_path_factory, "recorded_v5e_program_spans.xplane.pb.gz", None)


def _stand_in(ctx, monkeypatch, stats):
    """The recording with ``stats[i]`` put on the i-th inputs span of the window (cycled)."""
    spans = program_spans.of_run(ctx)
    inputs = spans.inside("exec.inputs", *ctx.trace_window)
    stood_in = program_spans.ProgramSpans()
    stood_in.host = dict(spans.host, **{"exec.inputs": [s._replace(stats=dict(s.stats, **stats[i % len(stats)])) for i, s in enumerate(inputs)]})
    stood_in.modules = spans.modules
    import layer_metrics.cop_lane_stacks_kept_pct as reader

    monkeypatch.setattr(reader, "of_run", lambda _ctx: stood_in)
    return inputs


def test_a_program_without_the_stats_reads_nothing(ctx):
    inputs = program_spans.of_run(ctx).inside("exec.inputs", *ctx.trace_window)
    assert inputs and not any(k.startswith("stacks_") for s in inputs for k in s.stats)
    assert _read(NAME, ctx) is None


@pytest.mark.parametrize("stats", [
    [{"stacks_kept": 1}, {}],  # a batch task's one mapped call, then a written region's task: no mapped call
    [{"stacks_kept": 4}, {"stacks_kept": 3, "stacks_built": 1}],  # tpch_sf10: four mapped calls; one built after a change
    [{"stacks_built": 2}],  # every call built its stacks
], ids=["htap", "sf10", "built"])
def test_it_is_the_kept_share_of_the_mapped_calls(ctx, monkeypatch, stats):
    inputs = _stand_in(ctx, monkeypatch, stats)
    put = [stats[i % len(stats)] for i in range(len(inputs))]
    assert len(inputs) >= 2 * len(stats)
    want = 100.0 * sum(p.get("stacks_kept", 0) for p in put) / sum(sum(p.values()) for p in put)
    assert _read(NAME, ctx) == pytest.approx(want)


def test_it_reads_nothing_where_the_program_wrote_no_spans(tmp_path_factory):
    old = _ctx(tmp_path_factory, "recorded_v5e.xplane.pb.gz", 30)
    assert _read(NAME, old) is None
    old.trace = None
    assert _read(NAME, old) is None
