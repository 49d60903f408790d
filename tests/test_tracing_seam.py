"""The one span seam (``utils/tracing.region``), the phases inside
``tpu_engine.execute_dag``, named lock waits and kernel family names.

What must hold (ISSUE 26, OBSERVABILITY.md "Spans on the device's clock"):
off, the seam is one shared null context and builds no Tracer; under TRACE
the five phases nest under ``device-exec``; under a ``jax.profiler`` session
every span of a statement — cop tasks on pool threads included — carries one
``stmt``; a contended lock wait is counted by name and an uncontended one
reads no clock; a cop program is named after its DAG's shape, not its
literals; EXPLAIN ANALYZE splits ``device:`` into ``phases:``."""

import ast
import glob
import re
import threading
import time

import pytest

import tidb_tpu
from tidb_tpu.utils import execdetails as _ed
from tidb_tpu.utils import lockcheck, metrics, tracing

PHASES = list(_ed.PHASES)
Q1 = "SELECT f, SUM(v), COUNT(*) FROM t WHERE k < {} GROUP BY f"
Q6 = "SELECT SUM(v) FROM t WHERE k < {}"


def _mk_db(rows=1000, split=200):
    db = tidb_tpu.open(region_split_keys=split)
    s = db.session()
    s.execute("CREATE TABLE t (id INT PRIMARY KEY, k INT, v DECIMAL(10,2), f CHAR(1))")
    for lo in range(0, rows, 100):
        s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i % 7},{i}.50,'{'AB'[i % 2]}')" for i in range(lo, lo + 100)))
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    return db, s


@pytest.fixture(scope="module")
def served():
    db, s = _mk_db()
    assert len(db.store.regions()) > 4  # several regions a statement: one batch cop task, and one more a region written since
    s.query(Q1.format(5))
    s.query(Q6.format(3))
    return db, s


# -- off ---------------------------------------------------------------------


def test_seam_off_is_one_shared_null_context_and_builds_no_tracer(served, monkeypatch):
    _, s = served

    class Boom(tracing.Tracer):
        def __init__(self, *a, **k):
            raise AssertionError("Tracer constructed with tracing off")

    monkeypatch.setattr(tracing, "Tracer", Boom)
    assert not tracing.profiling()
    a, b = tracing.region("exec.bind"), tracing.region("cop.task", region=3, label="cop.r3")
    assert a is b is tracing._NULL
    with a as span:
        assert span is None  # sites guard `span.note(...)` on this
    assert s.span("plan") is tracing._NULL
    assert s.query(Q6.format(3))
    assert s.tracer is None and tracing.current_stmt() is None  # the statement's binding is put back


# -- TRACE -------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_rows(served):
    _, s = served
    s.execute("SET tidb_distsql_scan_concurrency = 1")  # tasks one after another: a row's children are its own
    try:
        rows = s.query("TRACE " + Q1.format(5))
    finally:
        s.execute("SET tidb_distsql_scan_concurrency = 8")
    out = []  # (depth, name, start_ms, dur_ms) in the Tracer's order
    for label, start, dur in rows:
        name = label.lstrip(" └─")
        out.append(((len(label) - len(label.lstrip(" "))) // 2, name, float(start[:-2]), float(dur[:-2])))
    return out


def _children(rows, i):
    """Rows nested directly under row i: deeper by one, starting inside it."""
    depth, _, start, dur = rows[i]
    return [r for r in rows if r[0] == depth + 1 and start <= r[2] and r[2] + r[3] <= start + dur + 1e-3]


@pytest.mark.parametrize("phase", PHASES)
def test_trace_nests_each_phase_under_device_exec(traced_rows, phase):
    execs = [i for i, r in enumerate(traced_rows) if r[1] == "device-exec"]
    assert execs, traced_rows
    for i in execs:
        assert traced_rows[i - 1][1].startswith("cop.r")  # the cop span keeps its name and place
        assert any(r[1] == f"exec.{phase}" for r in _children(traced_rows, i)), (phase, traced_rows)


def test_trace_phases_sum_to_no_more_than_device_exec(traced_rows):
    for i, r in enumerate(traced_rows):
        if r[1] == "device-exec":
            kids = [k for k in _children(traced_rows, i) if k[1].startswith("exec.")]
            assert sum(k[3] for k in kids) <= r[3] + 0.05  # rows are rounded to the microsecond


# -- under a profiler session --------------------------------------------------


@pytest.fixture(scope="module")
def profiled(served, tmp_path_factory):
    """One statement of each shape run through a `jax.profiler` session on
    the CPU backend, each after a write to the table's last region (that region
    then runs as a cop task of its own on the pool, beside the batch task on
    the session's thread); the `tidb:` events of the trace, name -> [stats]."""
    import jax
    from jax.profiler import ProfileData

    _, s = served
    d = str(tmp_path_factory.mktemp("prof"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        assert tracing.profiling()
        s.execute("INSERT INTO t VALUES (5000, 1, 1.50, 'A')")
        s.query(Q1.format(5))
        s.execute("INSERT INTO t VALUES (5001, 1, 1.50, 'B')")
        s.query(Q6.format(3))
    finally:
        jax.profiler.stop_trace()
    assert not tracing.profiling()
    (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
    events: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):  # one line a host thread
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    stats = dict(ev.stats)
                    stats["_line"] = (plane.name, li)
                    events.setdefault(ev.name[len(tracing.PREFIX):], []).append(stats)
    return events


@pytest.mark.parametrize("name", ["cop.task"] + [f"exec.{p}" for p in PHASES])
def test_profiler_gets_every_span_with_the_statements_id(profiled, name):
    stmts = sorted({e["stmt"] for e in profiled["cop.task"]})  # the two reads; the writes have no cop task
    assert len(stmts) == 2 and all(isinstance(x, str) for x in stmts)
    assert set(stmts) <= {e["stmt"] for e in profiled["statement"]}
    mine = profiled[name]
    by_stmt = {x: [e for e in mine if e.get("stmt") == x] for x in stmts}
    assert sum(len(v) for v in by_stmt.values()) == len(mine)  # none without an id, none with a third
    tasks = {x: [e for e in profiled["cop.task"] if e["stmt"] == x] for x in stmts}
    for x in stmts:
        # the batch task and the written region's own; every region is in one of them
        assert sorted(int(e.get("regions", 1)) for e in tasks[x]) == [1, sum(int(e.get("regions", 1)) for e in tasks[x]) - 1]
        assert sum(int(e.get("regions", 1)) for e in tasks[x]) > 4
        assert len(by_stmt[x]) >= len(tasks[x])  # a span (bind: two) in every task


def test_profiler_spans_of_pool_threads_carry_it_too(profiled):
    tasks = profiled["cop.task"]
    session_line = profiled["statement"][0]["_line"]
    assert {e["_line"] for e in tasks} - {session_line}, "every cop task ran on the session's own thread"
    for e in tasks:
        assert e["engine"] == "tpu" and int(e["cpu_us"]) >= 0 and int(e["queue_us"]) >= 0 and "region" in e
    kernels = {e["kernel"] for e in profiled["exec.dispatch"]}
    # the lone task's single-region program, and the batch's mapped one (`_m<regions a call>`)
    assert {k.split("_m")[0] for k in kernels} == {"cop_sel_agg_g1", "cop_sel_agg_g0"} and any("_m" in k for k in kernels)
    # a dispatch span says how many programs it sent: one a padded shape of a batch, the lone task's one
    assert sorted(int(e["regions"]) for e in profiled["exec.dispatch"])[:2] == [1, 1]
    assert sum(int(e["regions"]) for e in profiled["exec.dispatch"]) == sum(int(e["programs"]) for e in tasks)
    assert sum(int(e["programs"]) for e in tasks) < sum(int(e.get("regions", 1)) for e in tasks)
    assert {e["cache"] for e in profiled["plan"]} <= {"hit", "miss"}


# -- named locks -------------------------------------------------------------

LOCKS = {"lock": threading.Lock, "rlock": threading.RLock}


def _wait_s(name: str) -> float:
    return metrics.LOCK_WAIT_SECONDS.get(lock=name)


@pytest.mark.parametrize("kind", sorted(LOCKS))
def test_lock_counts_a_contended_wait_and_nothing_uncontended(kind, monkeypatch):
    name = f"test_{kind}"
    lk = tracing.TracedLock(name, LOCKS[kind]())
    clock_reads = []
    real = time.perf_counter
    monkeypatch.setattr(tracing.time, "perf_counter", lambda: clock_reads.append(1) or real())
    for _ in range(100):
        with lk:
            assert lk.acquire(False) is (kind == "rlock")  # the RLock is re-entrant through the wrapper
            if kind == "rlock":
                lk.release()
    assert not clock_reads and _wait_s(name) == 0

    holding, done = threading.Event(), threading.Event()

    def holder():
        with lk:
            holding.set()
            time.sleep(0.05)
        done.set()

    t = threading.Thread(target=holder)
    t.start()
    assert holding.wait(5)
    assert lk.acquire(False) is False  # a failed try is not a wait
    assert _wait_s(name) == 0
    with lk:  # blocks until the holder lets go
        pass
    t.join(5)
    assert done.is_set() and not t.is_alive()
    assert 0.01 < _wait_s(name) < 5 and len(clock_reads) == 2


def test_lock_wait_is_a_span_when_the_seam_is_live():
    lk = tracing.TracedLock("test_span", threading.Lock())
    tr = tracing.Tracer()
    holding = threading.Event()

    def holder():
        with lk:
            holding.set()
            time.sleep(0.03)

    t = threading.Thread(target=holder)
    t.start()
    assert holding.wait(5)
    prev = tracing.bind(tr, "s.1")
    try:
        with lk:
            pass
    finally:
        tracing.bind(*prev)
    t.join(5)
    assert [s.name for s in tr.spans] == ["lock.wait"] and tr.spans[0].duration_s > 0.005


@pytest.mark.parametrize("kind", sorted(LOCKS))
def test_lockcheck_sees_an_inversion_through_the_wrapper(kind):
    assert lockcheck.installed()  # tests/conftest.py
    a = tracing.TracedLock("test_a", LOCKS[kind]())
    b = tracing.TracedLock("test_b", LOCKS[kind]())
    with a:
        with b:
            pass
    with pytest.raises(lockcheck.LockOrderError):
        with b:
            with a:
                pass
    assert a.acquire(False)  # the failed acquire left nothing held
    a.release()


def _served_path_locks():
    from tidb_tpu.copr import colcache, tpu_engine
    from tidb_tpu.kv import memstore
    from tidb_tpu.ops import dag_kernel

    store = memstore.MemStore()
    return {
        "device_lru": (tpu_engine._DEVICE_LRU._mu, tpu_engine, "_DeviceLRU", "_mu"),
        "device_misc": (tpu_engine._MISC_MU, tpu_engine, None, "_MISC_MU"),
        "colcache": (colcache.cache_for(store)._mu, colcache, "ColumnCache", "_mu"),
        "kernel_cache": (dag_kernel._CACHE_MU, dag_kernel, None, "_CACHE_MU"),
        "memstore": (store._mu, memstore, "MemStore", "_mu"),
    }


@pytest.mark.parametrize("name", ["device_lru", "device_misc", "colcache", "kernel_cache", "memstore"])
def test_served_path_lock_is_named_and_both_checkers_see_it(name):
    from tidb_tpu.tools.check.rules_locks import _is_lock_ctor

    lk, module, cls, attr = _served_path_locks()[name]
    assert isinstance(lk, tracing.TracedLock) and lk.name == name
    assert isinstance(lk._lock, lockcheck._CheckedLock)  # made by the patched factory: order-checked
    with lk:  # re-entrant only where it was before
        assert lk.acquire(False) is (name == "memstore")
        if name == "memstore":
            lk.release()
    # the static rule finds the creation in the AST, through the wrapper
    tree = ast.parse(open(module.__file__).read())
    scope = next(n for n in tree.body if isinstance(n, ast.ClassDef) and n.name == cls) if cls else tree
    made = [
        n for n in ast.walk(scope)
        if isinstance(n, ast.Assign) and _is_lock_ctor(n.value)
        and any((t.attr if isinstance(t, ast.Attribute) else getattr(t, "id", None)) == attr for t in n.targets)
    ]
    assert len(made) == 1, (name, made)


# -- kernel families ---------------------------------------------------------


def _families():
    from tidb_tpu.ops import dag_kernel

    with dag_kernel._CACHE_MU:
        return [k.family for k in dag_kernel._COMPILE_CACHE.values()]


@pytest.mark.parametrize("shape,texts,family", [
    ("q6", [Q6.format(2), Q6.format(4), Q6.format(6)], "cop_sel_agg_g0"),
    ("q1", [Q1.format(2), Q1.format(6)], "cop_sel_agg_g1"),
])
def test_family_names_the_shape_not_the_literals(shape, texts, family):
    from tidb_tpu.ops import dag_kernel

    _, s = _mk_db(rows=300, split=10_000)
    with dag_kernel._CACHE_MU:
        dag_kernel._COMPILE_CACHE.clear()
    for text in texts:
        s.query(text)
    fams = _families()
    assert len(fams) == len(texts)  # one program per literal ...
    assert set(fams) == {family}  # ... all of one family
    with dag_kernel._CACHE_MU:
        fn = next(iter(dag_kernel._COMPILE_CACHE.values())).fn
    assert fn.__name__ == family  # what jax names the XLA module after: jit_<family>


def test_family_marks_the_delta_variant_and_fused_blocks(monkeypatch):
    import dataclasses

    from tidb_tpu import config
    from tidb_tpu.copr import dagpb
    from tidb_tpu.ops import dag_kernel

    # a base this small is rebuilt outright as shipped; let it be delta-tracked
    monkeypatch.setattr(config, "_CURRENT", dataclasses.replace(config.current(), device_delta_min_rows=1))
    _, s = _mk_db(rows=300, split=10_000)
    s.query(Q6.format(3))  # builds the base block
    with dag_kernel._CACHE_MU:
        dag_kernel._COMPILE_CACHE.clear()
    s.execute("INSERT INTO t VALUES (5000, 1, 1.00, 'A')")
    assert s.query(Q6.format(3))
    assert _families() == ["cop_sel_agg_g0_d"]
    dag = dagpb.DAGRequest([dagpb.ExecutorPB(dagpb.TABLE_SCAN), dagpb.ExecutorPB(dagpb.SELECTION),
                            dagpb.ExecutorPB(dagpb.AGGREGATION, group_by=[{}, {}])])
    assert dag_kernel.kernel_family(dag) == "cop_sel_agg_g2"
    assert dag_kernel.kernel_family(dag, nb=4, delta_cap=8192) == "cop_sel_agg_g2_d_b4"
    assert dag_kernel.kernel_family(dag, m=48) == "cop_sel_agg_g2_m48"  # a mapped program: 48 regions a call
    assert dag_kernel.kernel_family(dagpb.DAGRequest([dagpb.ExecutorPB(dagpb.TABLE_SCAN)])) == "cop_scan"
    topn = dagpb.DAGRequest([dagpb.ExecutorPB(dagpb.TABLE_SCAN), dagpb.ExecutorPB(dagpb.TOPN)])
    assert dag_kernel.kernel_family(topn) == "cop_topn"


# -- EXPLAIN ANALYZE ---------------------------------------------------------


def test_explain_analyze_splits_device_into_phases(served):
    _, s = served
    text = "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + Q1.format(5)))
    m = re.search(r"device: ([0-9.]+)ms, phases: bind/inputs/dispatch/fetch/decode ([0-9./]+)ms", text)
    assert m, text
    parts = [float(x) for x in m.group(2).split("/")]
    assert len(parts) == len(PHASES) and all(p >= 0 for p in parts) and sum(parts) > 0
    assert sum(parts) <= float(m.group(1)) + 0.1 * len(parts)  # each part is rounded to 0.1 ms


def test_phases_ride_the_sidecars_wire_form():
    d = _ed.CopExecDetails(7)
    d.bind_ms, d.inputs_ms, d.dispatch_ms, d.fetch_ms, d.decode_ms = 1.5, 0.25, 0.5, 2.0, 0.125
    home = _ed.CopExecDetails(7)
    home.merge_pb(d.to_pb())
    home.merge_pb(d.to_pb())  # a re-split task accumulates every attempt
    summary = _ed.CopTasksSummary()
    summary.add(home)
    assert summary.phases_ms == [3.0, 0.5, 1.0, 4.0, 0.25]
    assert "phases:" not in _ed.CopTasksSummary().render()
    assert not any(k.startswith("ph") for k in _ed.CopExecDetails(1).to_pb())  # zeros stay off the wire
