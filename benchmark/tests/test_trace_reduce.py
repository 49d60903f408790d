"""The reduction from a profiler trace to numbers: the interval arithmetic on
made-up intervals, and the whole reduction on a trace recorded on the v5e
(`recorded_v5e.xplane.pb.gz`: cell tpch_sf2.q1q6_1c, a window of about one
second, PR 25)."""

import gzip
import os

import pytest

from harness.trace_reduce import Reduced, covered, intersect, reduce_file, total, union

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = [("exec", "device_exec"), ("cop", "cop"), ("stmt", "frontend"), ("writer", "writer")]


def test_interval_arithmetic():
    assert union([(3, 4), (1, 2), (3.5, 5), (2, 2.5)]) == [(1, 2.5), (3, 5)]
    assert intersect([(0, 2), (3, 6)], [(1, 4), (5, 9)]) == [(1, 2), (3, 4), (5, 6)]
    assert total([(1, 2), (3, 4.5)]) == 2.5
    assert covered([(1, 2), (3, 5)], 1.5, 4) == 1.5
    assert covered([(1, 2), (3, 5)], 6, 7) == 0.0


def test_busy_top_ops_and_idle_by_span():
    r = Reduced()
    r.device_ops = {0: [(1, 2, "fusion.a"), (3, 4, "fusion.b"), (3.5, 5, "fusion.a")]}
    r.spans = {"stmt": [(0.5, 6)], "cop": [(0.8, 5.5)], "exec": [(0.9, 2.5)], "writer": [(5.8, 7)]}
    assert r.busy(0) == [(1, 2), (3, 5)]
    assert r.busy_s(0, 10) == 3.0 and r.busy_s(1.5, 3.5) == 1.0
    assert r.top_ops(0, 10) == [["fusion.a", 2.5], ["fusion.b", 1.0]]
    idle = dict(r.idle_by_span(0, 10, KINDS, "between-statements"))
    # idle is [0,1] [2,3] [5,10]; each label gets what its spans add beyond the inner ones
    assert idle == pytest.approx({"device_exec": 0.6, "cop": 1.1, "frontend": 0.8, "writer": 1.0, "between-statements": 3.5})
    assert sum(idle.values()) == pytest.approx(10 - 3.0)


def test_two_devices_average():
    r = Reduced()
    r.device_ops = {0: [(0, 4, "x")], 1: [(0, 2, "x")]}
    assert r.busy_s(0, 4) == 3.0


def test_nothing_to_read_is_nothing():
    r = Reduced()
    assert r.busy_s(0, 1) == 0.0 and r.top_ops(0, 1) == []
    assert dict(r.idle_by_span(0, 1, KINDS, "rest")) == {"rest": 1.0}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The v5e trace as recorded, less its `/host:metadata` plane (16 MB of
    HLO protos that the reduction never reads)."""
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(os.path.join(HERE, "recorded_v5e.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return reduce_file(str(path), "tpu")


def test_recorded_trace_reduces_to_what_its_run_printed(recorded):
    red = recorded
    assert sorted(set(red.lines_seen)) == [
        "/device:TPU:0|Async XLA Ops", "/device:TPU:0|Scalar Unit", "/device:TPU:0|TC Overlay",
        "/device:TPU:0|XLA Modules", "/device:TPU:0|XLA Ops",
    ]
    assert list(red.device_ops) == [0] and len(red.device_ops[0]) == 5490  # the XLA Ops line only
    assert {k: len(v) for k, v in red.spans.items()} == {"stmt": 30, "cop": 90, "exec": 30}
    stmt = red.spans["stmt"]
    lo, hi = stmt[0][0], max(b for _, b in stmt)
    # the run's own result line (my chip run, PR 25): busy_s 0.16926391200000024, window_s 0.30300370600000004
    assert red.busy_s(lo, hi) == pytest.approx(0.169263912, rel=1e-9)
    assert hi - lo == pytest.approx(0.303003706, rel=1e-9)
    top = red.top_ops(lo, hi, 3)
    assert [n for n, _ in top] == ["%fusion.1 = pred[1,12582912]", "%fusion.3 = (u32[1]", "%concatenate.4 = s8[23,4194304]"]
    assert top[0][1] == pytest.approx(0.03870458, rel=1e-6)
    idle = dict(red.idle_by_span(lo, hi, KINDS, "between-statements"))
    assert idle["device_exec"] == pytest.approx(0.089135138, rel=1e-6)
    assert idle["frontend"] == pytest.approx(0.042652324, rel=1e-6)
    assert sum(idle.values()) == pytest.approx((hi - lo) - red.busy_s(lo, hi), rel=1e-9)


def test_readers_on_the_recorded_trace(recorded):
    import types

    from layer_metrics import cop_host_ms, device_idle_pct, scan_roofline

    stmt = recorded.spans["stmt"]
    ctx = types.SimpleNamespace(trace=recorded, trace_window=(stmt[0][0], max(b for _, b in stmt)), platform="tpu",
                                device_kind="TPU v5 lite", here=os.path.dirname(HERE), statements=[], rows={})
    assert cop_host_ms.read(ctx) == pytest.approx(2.6117875, rel=1e-6)  # as the run printed
    assert device_idle_pct.read(ctx) == pytest.approx(44.138006, rel=1e-6)
    ctx.device_kind = "TPU v9 imaginary"
    with pytest.raises(KeyError):
        scan_roofline.read(ctx)  # a device that is not in peaks.json is an error, not a default
    ctx.trace = None
    assert cop_host_ms.read(ctx) is None and device_idle_pct.read(ctx) is None
