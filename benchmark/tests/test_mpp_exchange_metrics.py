"""The four readers PR 33 added for the four-chip Q3 cell, on spans made by
hand: what they compute, and that each returns None, without raising, over a
program that writes no such stat (the parent of PR 33) or no span at all."""
import types

import pytest

from harness import program_spans
from harness.program_spans import ProgramSpans, Span
from layer_metrics import mpp_exchange_bytes_per_stmt, mpp_exchange_ici_pct, mpp_exchange_ms, mpp_shard_skew


def ctx_with(monkeypatch, spans, statements=2, **kw):
    monkeypatch.setattr(program_spans, "of_run", lambda ctx: spans)
    for mod in (mpp_exchange_bytes_per_stmt, mpp_shard_skew):
        monkeypatch.setattr(mod, "of_run", lambda ctx: spans)
    return types.SimpleNamespace(trace=object(), trace_window=(0.0, 10.0), statements=[{}] * statements, platform="tpu",
                                 device_kind="TPU v5 lite", mpp=[], here="", cell={"name": "x"}, **kw)


def made(**by_name):
    out = ProgramSpans()
    for name, stats in by_name.items():
        out.host["mpp." + name] = [Span(1.0 + i, 1.5 + i, st) for i, st in enumerate(stats)]
    return out


def test_bytes_a_statement_and_skew(monkeypatch):
    spans = made(fetch=[{"xchg_bytes": "3000", "xchg_rows": "7"}, {"xchg_bytes": "1000", "xchg_rows": "5"}],
                 lanes=[{"rows_valid": "1000", "rows_padded": "2048", "ndev": "4", "shard_rows_max": "400", "shard_rows_min": "0"}] * 2)
    ctx = ctx_with(monkeypatch, spans)
    assert mpp_exchange_bytes_per_stmt.read(ctx) == 2000.0
    assert mpp_shard_skew.read(ctx) == pytest.approx(1.6)  # 400 of a mean of 250


def test_a_program_without_the_stats_reads_none(monkeypatch):
    older = made(fetch=[{}], lanes=[{"rows_valid": "1000", "rows_padded": "2048"}], gather=[{"ndev": "4"}])
    for spans in (older, None):
        ctx = ctx_with(monkeypatch, spans)
        assert mpp_exchange_bytes_per_stmt.read(ctx) is None and mpp_shard_skew.read(ctx) is None
        assert mpp_exchange_ici_pct.read(ctx) is None


@pytest.mark.parametrize("name,want", [
    ("%all-gather.90 = pred[524288]", True), ("%all_to_all.41 = u32[4,1,8192]", True), ("%all-to-all.41 = u32[4,1,8192]", True),
    ("%all-reduce-start.3 = s64[]", True), ("%all-gather-done.1 = u32[8]", True), ("%collective-permute.2 = u32[8]", True),
    ("%reduce-scatter = f32[8]", True), ("%fusion.23 = s32[16777216]", False), ("%reduce-window.14 = (u32[131072,128]", False)])
def test_which_operations_are_collectives(name, want):
    assert mpp_exchange_ms.is_collective(name) is want
