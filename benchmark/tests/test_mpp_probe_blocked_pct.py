"""`mpp_probe_blocked_pct` (PR 34) on spans made by hand: the share it
computes, 0.0 over a program that writes no such stat (PR 34's parent: every
probe row an element gather), and None, without raising, where no gather ran."""
import types

import pytest

from harness.program_spans import ProgramSpans, Span
from layer_metrics import mpp_probe_blocked_pct


def ctx_with(monkeypatch, spans):
    monkeypatch.setattr(mpp_probe_blocked_pct, "of_run", lambda ctx: spans)
    return types.SimpleNamespace(trace=object(), trace_window=(0.0, 10.0), statements=[{}] * 2)


def fetches(*stats):
    out = ProgramSpans()
    out.host["mpp.fetch"] = [Span(1.0 + i, 1.5 + i, st) for i, st in enumerate(stats)]
    return out


def test_the_share_of_probe_rows_answered_by_blocks(monkeypatch):
    # Q3 at SF2: lineitem's 16.7M padded rows by blocks, orders' 4.19M (the arm's) by the element gather
    q3 = {"xchg_bytes": "0", "probe_rows": str((1 << 24) + (1 << 22)), "probe_rows_blocked": str(1 << 24)}
    assert mpp_probe_blocked_pct.read(ctx_with(monkeypatch, fetches(q3, q3))) == pytest.approx(80.0)
    none_blocked = dict(q3, probe_rows_blocked="0")
    assert mpp_probe_blocked_pct.read(ctx_with(monkeypatch, fetches(q3, none_blocked))) == pytest.approx(40.0)


def test_a_span_without_the_stats_counts_as_not_blocked(monkeypatch):
    older = {"xchg_bytes": "28115100", "xchg_rows": "7"}
    assert mpp_probe_blocked_pct.read(ctx_with(monkeypatch, fetches(older, older))) == 0.0
    newer = {"probe_rows": "1000", "probe_rows_blocked": "1000"}
    assert mpp_probe_blocked_pct.read(ctx_with(monkeypatch, fetches(older, newer))) == pytest.approx(100.0)


def test_it_reads_nothing_where_no_gather_ran(monkeypatch):
    for spans in (ProgramSpans(), None):
        assert mpp_probe_blocked_pct.read(ctx_with(monkeypatch, spans)) is None
    outside = ProgramSpans()
    outside.host["mpp.fetch"] = [Span(11.0, 11.5, {"probe_rows": "8", "probe_rows_blocked": "8"})]
    assert mpp_probe_blocked_pct.read(ctx_with(monkeypatch, outside)) is None
