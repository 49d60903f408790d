"""The program's own spans and program names, read from a trace recorded on
the v5e (`recorded_v5e_program_spans.xplane.pb.gz`: cell
tpch_sf1_htap.rf1_q1q6, a window of about one second, PR 26, less its
`/host:metadata` plane), and the eight readers built on them. On PR 25's
recording, made before the program wrote spans, every reader gives None."""

import gzip
import os
import types

import pytest

from harness import program_spans, trace_reduce
from harness.trace_reduce import covered, union

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tpch_sf1_htap.rf1_q1q6"
PHASES = ["exec.bind", "exec.inputs", "exec.dispatch", "exec.fetch", "exec.decode"]
READERS = ["exec_bind_ms", "exec_inputs_ms", "exec_dispatch_ms", "exec_fetch_ms", "exec_decode_ms", "exec_cpu_ms",
           "lock_wait_ms", "delta_kernel_ms"]


def _ctx(tmp_path_factory, recording: str, statements: int):
    """A run's context as `benchmark/run.py` builds it, over a checkout whose
    trace directory holds the recording."""
    root = tmp_path_factory.mktemp("checkout")
    prof = root / "_bench_cache" / "trace" / CELL / "plugins" / "profile" / "recorded"
    prof.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, recording)) as f:
        (prof / "recorded.xplane.pb").write_bytes(f.read())
    red = trace_reduce.reduce_file(str(prof / "recorded.xplane.pb"), "tpu")
    stmt = red.spans["stmt"]
    return types.SimpleNamespace(
        trace=red, trace_window=(stmt[0][0], max(b for _, b in stmt)), cell={"name": CELL}, here=str(root / "benchmark"),
        statements=[None] * (len(stmt) if statements is None else statements), platform="tpu",
    )


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return _ctx(tmp_path_factory, "recorded_v5e_program_spans.xplane.pb.gz", None)


@pytest.fixture(scope="module")
def spans(ctx):
    return program_spans.of_run(ctx)


def test_every_span_kind_is_found(spans):
    assert set(PHASES) | {"cop.task", "device-exec", "exec.release", "statement", "plan", "execute", "lock.wait"} <= set(spans.host)
    for name, found in spans.host.items():
        assert found == sorted(found, key=lambda s: s.start) and all(s.end >= s.start for s in found), name
    task = spans.host["cop.task"][0]
    assert {"region", "queue_us", "cpu_us", "h2d", "d2h", "engine", "stmt"} <= set(task.stats)
    assert {s.stats["lock"] for s in spans.host["lock.wait"]} <= {"device_lru", "device_misc", "colcache", "kernel_cache", "memstore"}
    assert {s.stats["kernel"] for s in spans.host["exec.dispatch"]} <= {"cop_sel_agg_g0", "cop_sel_agg_g2", "cop_sel_agg_g0_d", "cop_sel_agg_g2_d"}
    assert {s.stats["cache"] for s in spans.host["plan"]} <= {"hit", "miss"}


def test_stmt_groups_the_spans_of_one_statement(ctx, spans):
    lo, hi = ctx.trace_window
    by = {k: v for k, v in spans.by_stmt(lo, hi).items() if "cop.task" in v}
    assert len(by) == len(ctx.trace.spans["stmt"])  # the reader's statements, one id each
    for stmt, found in by.items():
        tasks = found["cop.task"]
        assert len(tasks) == 23  # a task a region of lineitem at SF1
        assert len({t.stats["region"] for t in tasks}) == 23
        for p in PHASES:
            assert len(found[p]) >= len(tasks), (stmt, p)
        (s,) = found["statement"]
        assert all(s.start <= t.start and t.end <= s.end for t in tasks)  # pool threads' tasks lie inside their statement


def test_phases_lie_inside_the_benchmarks_exec_spans(ctx, spans):
    lo, hi = ctx.trace_window
    ex = union(ctx.trace.spans["exec"])
    for p in PHASES:
        for s in spans.inside(p, lo, hi):
            assert covered(ex, s.start, s.end) == pytest.approx(s.end - s.start, abs=1e-9), p


def test_phases_tile_device_exec(ctx, spans):
    lo, hi = ctx.trace_window
    phases = sum(s.end - s.start for p in PHASES for s in spans.inside(p, lo, hi))
    whole = sum(s.end - s.start for s in spans.inside("device-exec", lo, hi))
    assert 0.99 * whole < phases <= whole  # each phase ends where the next begins
    fetch = union([(s.start, s.end) for s in spans.inside("exec.fetch", lo, hi)])
    for s in spans.inside("exec.release", lo, hi):  # the device result's drop is part of the fetch
        assert covered(fetch, s.start, s.end) == pytest.approx(s.end - s.start, abs=1e-9)


def test_modules_are_named_by_family(ctx, spans):
    fams = {m.family for mods in spans.modules.values() for m in mods}
    assert fams == {"cop_sel_agg_g0", "cop_sel_agg_g2", "cop_sel_agg_g0_d", "cop_sel_agg_g2_d"}
    assert [f for f in sorted(fams) if program_spans.is_delta_family(f)] == ["cop_sel_agg_g0_d", "cop_sel_agg_g2_d"]
    assert program_spans.is_delta_family("cop_sel_agg_g2_d_b4") and not program_spans.is_delta_family("cop_topn")


def _read(name, ctx):
    import importlib

    return importlib.import_module(f"layer_metrics.{name}").read(ctx)


def test_readers_on_the_recorded_trace(ctx, spans):
    got = {name: _read(name, ctx) for name in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    lo, hi = ctx.trace_window
    n = len(ctx.statements)
    for p in PHASES:
        assert got[p.replace(".", "_") + "_ms"] == pytest.approx(sum(s.end - s.start for s in spans.inside(p, lo, hi)) * 1e3 / n)
    assert got["exec_cpu_ms"] == pytest.approx(sum(int(s.stats["cpu_us"]) for s in spans.inside("cop.task", lo, hi)) / 1e3 / n)
    # the delta programs are the two %while loops of PERF.md: most of the device's time in this cell
    busy_ms = ctx.trace.busy_s(lo, hi) * 1e3 / n
    assert 0.5 * busy_ms < got["delta_kernel_ms"] <= busy_ms
    assert got["lock_wait_ms"] > 0  # a writer commits beside the reader


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_where_the_program_wrote_no_spans(name, tmp_path_factory):
    old = _ctx(tmp_path_factory, "recorded_v5e.xplane.pb.gz", 30)
    assert program_spans.of_run(old) is None
    assert _read(name, old) is None
    old.trace = None
    assert _read(name, old) is None
