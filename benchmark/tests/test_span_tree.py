"""`harness/span_tree.py` on a table of spans made by hand: self times, the
clients' turnaround, the cut of the device's idle time, and the thirteen
readers built on them. On PR 26's recording (a program whose `tidb:statement`
says no `type`) every reader gives None."""

import importlib
import types

import pytest

from harness import span_tree, trace_reduce
from harness.program_spans import ProgramSpans, Span
from test_program_spans import _ctx

READERS = ["stmt_wire_ms", "client_turnaround_ms", "stmt_session_ms", "stmt_parse_ms", "stmt_plan_ms", "stmt_build_ms",
           "stmt_executor_ms", "stmt_rows_ms", "stmt_finish_ms", "plan_cache_hit_pct", "idle_frontend_ms", "idle_fetch_ms",
           "idle_exec_host_ms"]
WINDOW = (0.0, 10.0)


def _table(rows) -> ProgramSpans:
    out = ProgramSpans()
    for name, start, end, stats in rows:
        out.host.setdefault(name, []).append(Span(start, end, stats))
    for spans in out.host.values():
        spans.sort(key=lambda s: s.start)
    return out


def _hand_made() -> ProgramSpans:
    a, b, c = {"stmt": "c1.1"}, {"stmt": "c1.2"}, {"stmt": "c1.3"}
    w1, w2 = {"stmt": "c2.1"}, {"stmt": "c2.2"}
    return _table([
        # the client's connection, first statement: two tasks, one on a pool thread, overlapping
        ("conn.command", 1.0, 3.0, {"conn": 1, "cmd": "query", **a}),
        ("statement", 1.1, 2.6, {"type": "Select", "ast": "session", **a}),
        ("plan", 1.2, 1.3, {"cache": "hit", **a}),
        ("execute", 1.3, 2.3, a),
        ("executor.build", 1.3, 1.4, a),
        ("cop.task", 1.5, 2.1, a),
        ("cop.task", 1.6, 2.2, a),  # another thread: its time inside `execute` is covered too
        ("exec.fetch", 1.8, 2.0, a),
        ("result.rows", 2.3, 2.4, a),
        ("stmt.finish", 2.4, 2.55, a),
        ("conn.write", 2.7, 2.9, a),
        # its second: a text seen for the first time, nothing to scan
        ("conn.command", 4.0, 5.0, {"conn": 1, "cmd": "query", **b}),
        ("statement", 4.1, 4.8, {"type": "Select", "ast": "parse", **b}),
        ("parse", 4.15, 4.25, b),
        ("plan", 4.25, 4.35, {"cache": "miss", **b}),
        ("execute", 4.4, 4.6, b),
        # its third straddles the window's end: not of the window
        ("conn.command", 9.5, 10.5, {"conn": 1, "cmd": "query", **c}),
        ("statement", 9.6, 10.4, {"type": "Select", "ast": "session", **c}),
        ("execute", 9.7, 10.3, c),
        # the writer's connection: out of the sums, but the device waits under its `execute` all the same
        ("conn.command", 2.0, 2.5, {"conn": 2, "cmd": "query", **w1}),
        ("statement", 2.05, 2.45, {"type": "Begin", "ast": "parse", **w1}),
        ("conn.command", 5.9, 6.6, {"conn": 2, "cmd": "query", **w2}),
        ("statement", 5.95, 6.55, {"type": "Insert", "ast": "parse", **w2}),
        ("execute", 6.0, 6.5, w2),
    ])


BUSY = [(1.7, 1.9, "op"), (6.2, 6.4, "op"), (9.0, 11.0, "op")]


def _run(monkeypatch, table: ProgramSpans, ops=BUSY):
    red = trace_reduce.Reduced()
    red.device_ops[0] = list(ops)
    monkeypatch.setattr(span_tree.program_spans, "of_run", lambda ctx: table if table.host else None)
    return types.SimpleNamespace(trace=red, trace_window=WINDOW)


def test_self_time_takes_out_the_union_of_what_lies_inside():
    t = _hand_made()
    every = [s for s in (x for ss in t.host.values() for x in ss) if s.stats.get("stmt") == "c1.1"]
    one = {n: [s for s in every if s in t.host[n]] for n in t.host}
    assert span_tree.self_time(one["conn.command"][0], every) == pytest.approx(2.0 - 1.5 - 0.2)
    assert span_tree.self_time(one["statement"][0], every) == pytest.approx(1.5 - 0.1 - 1.0 - 0.1 - 0.15)
    assert span_tree.self_time(one["execute"][0], every) == pytest.approx(1.0 - 0.1 - 0.7)  # the two tasks cover 1.5–2.2 once
    assert span_tree.self_time(one["cop.task"][0], every) == pytest.approx(0.6 - 0.2)  # the other task is not INSIDE it
    assert span_tree.self_time(one["plan"][0], every) == pytest.approx(0.1)  # a leaf


def test_tree_counts_the_analytic_statements_of_the_clients_connections(monkeypatch):
    tree = span_tree.of_run(_run(monkeypatch, _hand_made()))
    assert tree.n == 2 and tree.conns == {"c1"}  # the third straddles the window; the writer answered none
    assert tree.self_ms("conn.command") + tree.sum_ms("conn.write") == pytest.approx((0.3 + 0.2 + 0.3) * 1e3 / 2)
    assert tree.self_ms("statement") == pytest.approx((0.15 + 0.3) * 1e3 / 2)
    assert tree.self_ms("execute") == pytest.approx((0.2 + 0.2) * 1e3 / 2)
    assert tree.covered_ms("execute") == pytest.approx(0.7 * 1e3 / 2)
    assert tree.sum_ms("plan") == pytest.approx(0.2 * 1e3 / 2)
    assert tree.sum_ms("mpp.gather") == 0.0  # a name nobody wrote
    assert tree.turnaround_ms() == pytest.approx(1.0 * 1e3 / 2)  # 3.0 → 4.0; the next command is outside the window
    assert tree.hit_pct("plan", "cache", "hit") == 50.0


def test_the_nine_and_the_tasks_tile_the_connections_timeline(monkeypatch):
    tree = span_tree.of_run(_run(monkeypatch, _hand_made()))
    nine = (tree.self_ms("conn.command") + tree.sum_ms("conn.write") + tree.turnaround_ms() + tree.self_ms("statement")
            + tree.self_ms("execute") + sum(tree.sum_ms(n) for n in ["parse", "plan", "executor.build", "result.rows", "stmt.finish"]))
    # first command's start to the last one's end, of the commands inside the window
    assert nine + tree.covered_ms("execute") == pytest.approx((5.0 - 1.0) * 1e3 / 2)


def test_idle_is_cut_by_what_any_connection_was_doing(monkeypatch):
    ctx = _run(monkeypatch, _hand_made())
    tree = span_tree.of_run(ctx)
    idle_s = (WINDOW[1] - WINDOW[0]) - ctx.trace.busy_s(*WINDOW)
    assert idle_s == pytest.approx(8.6)
    assert tree.idle["fetch"] == pytest.approx(0.1)  # 1.9–2.0: the device done, the fetch not
    assert tree.idle["exec_host"] == pytest.approx(0.4 + 0.3 + 0.2 + 0.3)  # the writer's `execute` counts: 6.0–6.2, 6.4–6.5
    assert tree.idle["frontend"] == pytest.approx(8.6 - 1.3)
    assert sum(tree.idle.values()) == pytest.approx(idle_s)
    assert sum(tree.idle_ms(k) for k in ("frontend", "fetch", "exec_host")) * tree.n == pytest.approx(idle_s * 1e3)


def test_busy_is_taken_once_a_run(monkeypatch):
    ctx = _run(monkeypatch, _hand_made())
    calls = []
    real = ctx.trace.busy
    ctx.trace.busy = lambda dev: calls.append(dev) or real(dev)
    for name in READERS:
        assert importlib.import_module(f"layer_metrics.{name}").read(ctx) is not None, name
    assert calls == [0]


@pytest.mark.parametrize("name,want", [
    ("stmt_wire_ms", 400.0), ("client_turnaround_ms", 500.0), ("stmt_session_ms", 225.0), ("stmt_parse_ms", 50.0),
    ("stmt_plan_ms", 100.0), ("stmt_build_ms", 50.0), ("stmt_executor_ms", 200.0), ("stmt_rows_ms", 50.0),
    ("stmt_finish_ms", 75.0), ("plan_cache_hit_pct", 50.0), ("idle_frontend_ms", 3650.0), ("idle_fetch_ms", 50.0),
    ("idle_exec_host_ms", 600.0),
])
def test_reader_on_the_hand_made_run(monkeypatch, name, want):
    mod = importlib.import_module(f"layer_metrics.{name}")
    assert mod.read(_run(monkeypatch, _hand_made())) == pytest.approx(want)
    assert mod.UNIT == ("%" if name.endswith("_pct") else "ms")


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_the_spans(monkeypatch, name, tmp_path_factory):
    mod = importlib.import_module(f"layer_metrics.{name}")
    assert mod.read(_run(monkeypatch, ProgramSpans())) is None  # no spans at all
    untyped = _table([("statement", 1.0, 2.0, {"stmt": "c1.1"}), ("plan", 1.1, 1.2, {"stmt": "c1.1", "cache": "hit"}),
                      ("execute", 1.2, 1.9, {"stmt": "c1.1"})])
    assert mod.read(_run(monkeypatch, untyped)) is None  # a program from before PR 37: no statement says its type
    monkeypatch.undo()
    recorded = _ctx(tmp_path_factory, "recorded_v5e_program_spans.xplane.pb.gz", None)  # PR 26's program, on the chip
    assert mod.read(recorded) is None


def test_every_reader_is_declared_for_every_cell():
    import json
    import os

    from conftest import ROOT

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert sorted(mine) == sorted(READERS)
    for name, m in mine.items():
        assert m["workloads"] == cells and m["source"] == "program_span" and m["moves"] == "stmt_per_s", name
        assert m["layer"] == ("device" if name.startswith("idle_") else "wire + session + planner")
        assert m["better"] == ("higher" if name.endswith("_pct") else "lower")
