"""The group-by route by rows a call (ISSUE 38): a dense group-by goes to the
int8 MXU dot (``ops/mxu_groupby``) once the rows ONE program call reduces —
``n_pad * m`` in a mapped program — reach ``dag_kernel._DOT_MIN_ROWS``, and
stays on the equality mask below. What must hold: the program holds a
``dot_general`` exactly when the route says so, whatever the region count; on
the dot, inside a mapped program, each region's partials are the ones its own
single-region program and the host engine give, row for row (NULL keys, a sum
past 2^31, padding slots, AVG, COUNT(*)); the ``tidb:exec.dispatch`` span's
``agg_*`` stats, the ``tidb_tpu_cop_agg_route_total`` counter, the sidecar and
EXPLAIN ANALYZE count the regions each route reduced."""

import glob

import jax
import numpy as np
import pytest

import tidb_tpu
from tests.test_cop_batch import Q1, _forget, _host, _requests, _summary
from tidb_tpu.copr import tpu_engine
from tidb_tpu.copr.client import CopClient
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.ops import dag_kernel
from tidb_tpu.utils import execdetails, metrics, tracing
from tidb_tpu.utils.chunk import Chunk

Q1_TEXT = Q1.format(5)
# NULL group keys (f), a sum whose span passes 2^31 (b), AVG, COUNT(*), COUNT(col)
GROUPED = "SELECT f, r, SUM(q), SUM(v), SUM(b), AVG(q), COUNT(*), COUNT(f) FROM t GROUP BY f, r ORDER BY f, r"
SCALAR = "SELECT SUM(v), COUNT(*) FROM t WHERE q < 24"


def _mk_db(rows=6000, split=1000):
    """``t``: ``rows`` rows in regions of about ``split``; ``f`` NULL in a row
    of 9, ``b`` up to ~1.8e10 (a limb lane of int64 width): 11 regions."""
    db = tidb_tpu.open(region_split_keys=split)
    s = db.session()
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, k INT, q INT, d DECIMAL(4,2), v DECIMAL(12,2), f CHAR(1), r CHAR(1), b BIGINT)")
    ids = np.arange(rows, dtype=np.int64)
    f = [None if i % 9 == 4 else "ANR"[i % 3] for i in ids]
    cols = [ids, ids % 7, ids % 50, ids % 11, ids * 150 + 25, f, np.array([b"F", b"O"])[ids % 2], ids * 3_000_017 - 7]
    for lo in range(0, rows, split // 2):
        bulk_load(db, "t", [c[lo : lo + split // 2] for c in cols])
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    return db, s


@pytest.fixture(scope="module")
def served():
    db, s = _mk_db()
    for text in (GROUPED, Q1_TEXT, SCALAR):  # every region's column-cache entry: a first read is a task a region
        s.query(text)
    return db, s


@pytest.fixture
def fresh(served):
    """No program or resolved task built under another gate survives the test
    on either side: the kernel cache's key does not hold the gate."""

    def clear():
        with dag_kernel._CACHE_MU:
            dag_kernel._COMPILE_CACHE.clear()
        _forget(served[0])

    clear()
    yield served
    clear()


def _capture(db, s, text, monkeypatch):
    """(bound DAG, agg_cap, full_scan, the stacked lanes) of the mapped call the statement sends."""
    sent, bound = [], []
    real_args, real_get = tpu_engine._call_args, tpu_engine.get_kernel
    monkeypatch.setattr(tpu_engine, "_call_args", lambda kernel, *a: sent.append((kernel, real_args(kernel, *a))) or sent[-1][1])
    monkeypatch.setattr(tpu_engine, "get_kernel", lambda dag, *a, **kw: bound.append((dag, a, kw)) or real_get(dag, *a, **kw))
    _forget(db)
    s.query(text)
    monkeypatch.undo()
    (kernel, (lanes, _, _)), = [c for c in sent if c[0].m > 1]
    dag, (_, agg_cap), kw = next(b for b in bound if b[2]["m"] == kernel.m)
    return dag, agg_cap, kw["full_scan"], lanes


def _primitives(jaxpr, out=None) -> set:
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, out)
    return out


def _program(dag, agg_cap, full_scan, lanes, n_pad, m):
    """(kernel, the primitives of its jaxpr) at ``n_pad`` rows a region and
    ``m`` regions a call: traced on shapes alone, nothing compiled or run."""
    spec = lambda a, *lead: None if a is None else jax.ShapeDtypeStruct((*lead, n_pad), a.dtype)  # noqa: E731
    handles, cols = lanes
    k = dag_kernel._build(dag, n_pad, agg_cap, full_scan=full_scan, m=m)
    if m > 1:
        stacked = (spec(handles, m), tuple((spec(d, m), spec(v, m)) for d, v in cols))
        args = (stacked, jax.ShapeDtypeStruct((m, dag_kernel.MAX_RANGES, 2), np.int64), jax.ShapeDtypeStruct((m,), np.int64))
    else:
        lanes = tuple((spec(d), jax.ShapeDtypeStruct((n_pad,), np.bool_)) for d, _ in cols)
        args = (spec(handles) or lanes[0][0], lanes, jax.ShapeDtypeStruct((dag_kernel.MAX_RANGES, 2), np.int64), jax.ShapeDtypeStruct((), np.int64))
    return k, _primitives(jax.make_jaxpr(k.fn)(*args).jaxpr)


# -- the route by rows a call -----------------------------------------------------


@pytest.mark.parametrize("n_pad,m,route", [
    (262144, 48, "dot"),  # tpch_sf2's call: 12.6M rows
    (262144, 64, "dot"),  # tpch_sf10's top rung
    (262144, 8, "dot"),  # 2^21 rows exactly
    (131072, 8, "eqmask"),  # 2^20 rows a call
    (1024, 48, "eqmask"),  # tier-1's small regions
    (262144, 1, "eqmask"),  # the single-region program of the same n_pad
    (524288, 1, "eqmask"),  # tpch_sf10's lone region
    (1 << 21, 1, "dot"),  # a single region of 2^21 rows, as before
])
def test_q1_takes_the_dot_by_rows_a_call(served, monkeypatch, n_pad, m, route):
    db, s = served
    dag, agg_cap, full_scan, lanes = _capture(db, s, Q1_TEXT, monkeypatch)
    k, prims = _program(dag, agg_cap, full_scan, lanes, n_pad, m)
    assert k.route == route == ("dot" if n_pad * m >= dag_kernel._DOT_MIN_ROWS else "eqmask")
    assert ("dot_general" in prims) == (route == "dot"), sorted(prims)


def test_a_scalar_aggregate_has_no_route(served, monkeypatch):
    db, s = served
    dag, agg_cap, full_scan, lanes = _capture(db, s, SCALAR, monkeypatch)
    k, prims = _program(dag, agg_cap, full_scan, lanes, 262144, 48)
    assert k.route is None and "dot_general" not in prims


def test_the_two_gates_are_one_helper():
    gate = dag_kernel._dot_route_ok
    assert gate(12, 48 * 262144) and gate(12, 1 << 21) and not gate(12, (1 << 21) - 1)
    assert gate(dag_kernel._DENSE_EQMASK_MAX + 1, 1)  # past the eqmask's buckets: the dot at any size


# -- exact on the dot inside a mapped program --------------------------------------


@pytest.mark.parametrize("text", [GROUPED, Q1_TEXT])
def test_mapped_dot_partials_equal_each_regions_own_and_the_hosts(fresh, monkeypatch, text):
    """The gate lowered below a mapped call's rows and above one region's: the
    mapped calls take the dot, each region alone the equality mask."""
    db, s = fresh
    monkeypatch.setattr(dag_kernel, "_DOT_MIN_ROWS", 4096)
    (req,), rows = _requests(s, text, monkeypatch)
    assert rows == _host(s, text)
    regions = list(db.store.pd.regions_in_ranges(req.ranges))
    calls = []
    real = tpu_engine._call_args
    monkeypatch.setattr(tpu_engine, "_call_args", lambda kernel, regs, *a: calls.append((kernel, len(regs))) or real(kernel, regs, *a))
    _forget(db)  # resolved anew: through _exec_single and _call_args
    (res,) = list(CopClient(db.store).send(req))
    # ten regions of one kernel key: one call of 16 slots, six of them padding (nvalid 0)
    assert any(k.route == "dot" and k.m > n for k, n in calls), [(k.route, k.m, n) for k, n in calls]
    # a region alone in its kernel key (the one whose `b` fits int32) calls the single-region program
    by_route = {}
    for k, n in calls:
        by_route[k.route] = by_route.get(k.route, 0) + n
    assert res.details.regions == len(regions) and res.details.agg_routes == by_route
    assert by_route["dot"] == sum(n for k, n in calls if k.m > 1)
    one_by_one = [tpu_engine.execute_dag(db.store, req.data, r, rg, req.start_ts) for r, rg in regions]
    assert res.chunk.rows() == Chunk.concat(one_by_one).rows()
    assert {k.route for k in dag_kernel._COMPILE_CACHE.values()} == {"dot", "eqmask"}
    host = [tpu_engine.host_execute_dag(db.store, req.data, r, rg, req.start_ts) for r, rg in regions]
    assert sorted(res.chunk.rows()) == sorted(Chunk.concat(host).rows())


def test_mapped_dot_sums_past_2_31_are_exact(fresh, monkeypatch):
    db, s = fresh
    monkeypatch.setattr(dag_kernel, "_DOT_MIN_ROWS", 4096)
    text = "SELECT r, SUM(b), COUNT(*) FROM t GROUP BY r ORDER BY r"
    rows, summary = _summary(s, text)
    assert summary.agg_routes["dot"] >= 8 and sum(summary.agg_routes.values()) == summary.regions
    ids = np.arange(6000, dtype=np.int64)
    b = ids * 3_000_017 - 7
    assert max(b) - min(b) >= 1 << 31
    assert [(r, int(x), c) for r, x, c in rows] == [("F", int(b[ids % 2 == 0].sum()), 3000), ("O", int(b[ids % 2 == 1].sum()), 3000)]


# -- span, counter, sidecar and EXPLAIN --------------------------------------------


def _routes():
    return {r: metrics.COP_AGG_ROUTE.get(route=r) for r in ("dot", "eqmask", "sort")}


def _moved(before):
    return {r: k - before[r] for r, k in _routes().items() if k != before[r]}


def _dispatch_stats(s, text, tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rows, summary = _summary(s, text)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    name = tracing.PREFIX + "exec.dispatch"
    return rows, summary, [dict(ev.stats) for plane in ProfileData.from_file(path).planes for line in plane.lines
                           for ev in line.events if ev.name == name]


@pytest.mark.parametrize("gate,route", [(4096, "dot"), (1 << 21, "eqmask")])
def test_span_counter_sidecar_and_explain_count_the_regions_a_route_reduced(fresh, monkeypatch, tmp_path, gate, route):
    db, s = fresh
    monkeypatch.setattr(dag_kernel, "_DOT_MIN_ROWS", gate)
    s.query(Q1_TEXT)
    before = _routes()
    rows, summary, dispatches = _dispatch_stats(s, Q1_TEXT, tmp_path)
    assert rows == _host(s, Q1_TEXT) and summary.num == 1 and summary.programs < summary.regions
    assert sum(int(d.get("agg_" + route, 0)) for d in dispatches) == summary.regions  # padding slots not counted
    assert not any(k.startswith("agg_") and not k.endswith(route) for d in dispatches for k in d)
    assert summary.agg_routes == {route: summary.regions} and _moved(before) == {route: summary.regions}
    text = "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + Q1_TEXT))
    assert f"agg_route: {route}×{summary.regions}" in text


def test_a_single_region_call_counts_eqmask_and_a_scalar_aggregate_none(fresh, tmp_path):
    db, s = fresh
    text = "SELECT f, COUNT(*), SUM(q) FROM t WHERE id < 10 GROUP BY f ORDER BY f"
    before = _routes()
    rows, summary, (dispatch,) = _dispatch_stats(s, text, tmp_path / "one")
    assert rows == _host(s, text) and summary.regions == summary.programs == 1
    assert int(dispatch["agg_eqmask"]) == 1 and _moved(before) == {"eqmask": 1} and summary.agg_routes == {"eqmask": 1}
    before = _routes()
    s.query(SCALAR)
    rows, summary, dispatches = _dispatch_stats(s, SCALAR, tmp_path / "scalar")
    assert rows == _host(s, SCALAR) and summary.regions > 1
    assert not any(k.startswith("agg_") for d in dispatches for k in d)
    assert _moved(before) == {} and summary.agg_routes == {}
    assert "agg_route:" not in "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + SCALAR))


def test_the_sidecar_carries_the_routes_over_the_wire():
    det = execdetails.CopExecDetails(7)
    assert "ar" not in det.to_pb()
    det.agg_routes.update(dot=45, eqmask=1)
    back = execdetails.CopExecDetails(7)
    back.merge_pb(det.to_pb())
    back.merge_pb(det.to_pb())
    assert back.agg_routes == {"dot": 90, "eqmask": 2}
    summary = execdetails.CopTasksSummary()
    summary.add(det)
    summary.add(back)
    assert summary.agg_routes == {"dot": 135, "eqmask": 3} and "agg_route: dot×135 eqmask×3" in summary.render()
