"""`mpp_lane_mb` on spans made by hand: the lane bytes a statement in MB, a
span without the stat (a program from before it) counting as nothing, and
None, without raising, where no span carries it or no gather ran."""
import types

import pytest

from harness.program_spans import ProgramSpans, Span
from layer_metrics import mpp_lane_mb


def ctx_with(monkeypatch, spans, statements=2):
    monkeypatch.setattr(mpp_lane_mb, "of_run", lambda ctx: spans)
    return types.SimpleNamespace(trace=object(), trace_window=(0.0, 10.0), statements=[{}] * statements)


def lanes(*stats, at=1.0):
    out = ProgramSpans()
    out.host["mpp.lanes"] = [Span(at + i, at + i + 0.5, st) for i, st in enumerate(stats)]
    return out


def test_the_lane_bytes_a_statement_in_mb(monkeypatch):
    # Q3 at SF2 on one chip: 2^24 lineitem rows x (4 int64 lanes + 5 bool), orders' 2^22, customer's 2^19
    sf2 = 2**24 * (4 * 8 + 5) + 2**22 * (4 * 8 + 5) + 2**19 * (2 * 8 + 3)
    one = {"rows_valid": "15300000", "rows_padded": "21495808", "lane_bytes": str(sf2)}
    assert mpp_lane_mb.read(ctx_with(monkeypatch, lanes(one, one))) == pytest.approx(sf2 / 1e6)
    assert mpp_lane_mb.read(ctx_with(monkeypatch, lanes(one, one), statements=4)) == pytest.approx(sf2 / 2e6)


def test_a_span_without_the_stat_counts_as_nothing(monkeypatch):
    older = {"rows_valid": "15300000", "rows_padded": "21495808", "h2d": "0", "cache": "hit"}
    assert mpp_lane_mb.read(ctx_with(monkeypatch, lanes(older, older))) is None
    newer = dict(older, lane_bytes="4000000")
    assert mpp_lane_mb.read(ctx_with(monkeypatch, lanes(older, newer))) == pytest.approx(2.0)


def test_it_reads_nothing_where_no_gather_ran(monkeypatch):
    for spans in (ProgramSpans(), None):
        assert mpp_lane_mb.read(ctx_with(monkeypatch, spans)) is None
    outside = lanes({"lane_bytes": "8"}, at=11.0)
    assert mpp_lane_mb.read(ctx_with(monkeypatch, outside)) is None
    assert mpp_lane_mb.read(ctx_with(monkeypatch, lanes({"lane_bytes": "8"}), statements=0)) is None
