"""The batch cop task (ISSUE 27): a ``tpu`` request whose results are
order-blind partial aggregates goes to the engine as ONE task carrying every
region (``copr/client.py``, ``tpu_engine._batch_path``). What must hold: the
same partial results, row for row, as a task a region gives, and the host
engine's answer; a region that is not clean leaves the batch and runs alone;
the chaos seam fires once a region and a fault touches that region only; the
sidecar, the ``tidb:cop.task`` span and the counter agree on how many regions
a task served; a request that is not order-blind keeps a task a region.

Inside the task (ISSUE 30) the regions that share a kernel key go to the
device as ONE call of one mapped program (``dag_kernel.get_kernel``'s ``m``),
its count padded up a ladder: the same partials still, one call a padded
shape, one compiled program for 5, 6 or 7 regions, a program no larger for 48
regions than for 8; a region that leaves shrinks the group, an overflow
re-runs its region alone; sidecar, spans and counter agree on the calls."""

import dataclasses
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import tidb_tpu
from tidb_tpu import config
from tidb_tpu.copr import client as cop_client
from tidb_tpu.copr import tpu_engine
from tidb_tpu.copr.client import CopClient
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.kv.fault_injection import NShot
from tidb_tpu.kv.kv import RegionError, StoreType
from tidb_tpu.ops import dag_kernel
from tidb_tpu.utils import failpoint, metrics, tracing
from tidb_tpu.utils.chunk import Chunk

ROWS, SPLIT = 4000, 1000
Q1 = "SELECT f, r, SUM(q), SUM(v), SUM(v * (1 - d)), AVG(q), COUNT(*) FROM t WHERE k <= {} GROUP BY f, r ORDER BY f, r"
Q6 = "SELECT SUM(v * d) FROM t WHERE k >= {} AND k < {} AND d BETWEEN 0.02 AND 0.06 AND q < 24"
TOPN = "SELECT id, v FROM t WHERE k < 5 ORDER BY v DESC, id LIMIT 7"
SHAPES = {"q1": Q1.format(5), "q6": Q6.format(1, 6)}


def _mk_db(rows=ROWS, split=SPLIT, groups=None):
    """``t``: ``rows`` rows over at least 4 regions (loaded in batches, so the
    regions split as they grow), read by the ``tpu`` engine."""
    db = tidb_tpu.open(region_split_keys=split)
    s = db.session()
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, k INT, q INT, d DECIMAL(4,2), v DECIMAL(12,2), f CHAR(1), r CHAR(1), g BIGINT)")
    ids = np.arange(rows, dtype=np.int64)
    cols = [ids, ids % 7, ids % 50, ids % 11, ids * 150 + 25, np.array([b"A", b"N", b"R"])[ids % 3],
            np.array([b"F", b"O"])[ids % 2], ids % 10 if groups is None else groups(ids)]
    for lo in range(0, rows, split // 2):
        bulk_load(db, "t", [c[lo : lo + split // 2] for c in cols])
    assert len(db.store.regions()) >= 4
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    return db, s


@pytest.fixture(scope="module")
def served():
    db, s = _mk_db()
    for text in SHAPES.values():  # builds every region's column-cache entry: a first read is a task a region
        s.query(text)
    return db, s


def _host(s, text):
    s.execute("SET tidb_isolation_read_engines = 'host'")
    try:
        return s.query(text)
    finally:
        s.execute("SET tidb_isolation_read_engines = 'tpu'")


def _requests(s, text, monkeypatch):
    """The cop requests the statement sends, and what it answered."""
    sent = []
    real = CopClient.send

    def send(self, req):
        sent.append(req)
        return real(self, req)

    with monkeypatch.context() as m:
        m.setattr(CopClient, "send", send)
        rows = s.query(text)
    return sent, rows


def _summary(s, text):
    rows = s.query(text)
    return rows, s.exec_summary


def _forget(db):
    """Drop the store's resolved batch tasks (ISSUE 36): the statement after is
    resolved anew, through ``_batch_path`` and ``_exec_single``, as a first one."""
    tpu_engine.cache_for(db.store)._resolved.clear()


def _counts():
    return metrics.COP_REGIONS.get(path="batched"), metrics.COP_REGIONS.get(path="single")


def _programs():
    return metrics.COP_PROGRAMS.get(form="mapped"), metrics.COP_PROGRAMS.get(form="single")


# -- the same answers ----------------------------------------------------------


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_batch_gives_each_regions_partials_row_for_row(served, shape, monkeypatch):
    db, s = served
    (req,), rows = _requests(s, SHAPES[shape], monkeypatch)
    assert rows == _host(s, SHAPES[shape])
    assert cop_client._order_blind_partial(req, req.data)
    regions = list(db.store.pd.regions_in_ranges(req.ranges))
    assert len(regions) >= 4
    (res,) = list(CopClient(db.store).send(req))
    assert res.details.regions == len(regions) and res.details.engine == "tpu" and not res.details.degraded
    assert 1 <= res.details.programs < len(regions)  # mapped: one call a padded shape, its partials stacked
    one_by_one = [tpu_engine.execute_dag(db.store, req.data, r, rg, req.start_ts) for r, rg in regions]
    assert all(len(c) for c in one_by_one)  # a partial row (or group rows) from every region
    assert res.chunk.rows() == Chunk.concat(one_by_one).rows()
    host = [cop_client._engines()[StoreType.HOST](db.store, req.data, r, rg, req.start_ts) for r, rg in regions]
    assert sorted(res.chunk.rows()) == sorted(Chunk.concat(host).rows())


def test_regions_of_another_padded_shape_share_the_batch(monkeypatch):
    """The last region is smaller than the others: another ``n_pad``, another
    program, the same batch."""
    db, s = _mk_db(rows=9300, split=4000)  # regions of 2,000 to 3,000 rows: padded to 2,048 and to 4,096
    s.query(SHAPES["q1"])
    with dag_kernel._CACHE_MU:
        dag_kernel._COMPILE_CACHE.clear()
    rows, summary = _summary(s, SHAPES["q1"])
    assert rows == _host(s, SHAPES["q1"])
    assert summary.num == 1 and summary.regions == len(list(db.store.pd.regions_in_ranges(_requests(s, SHAPES["q1"], monkeypatch)[0][0].ranges)))
    with dag_kernel._CACHE_MU:
        pads = {key[1] for key in dag_kernel._COMPILE_CACHE}
    assert len(pads) >= 2, pads


WIDE = [
    "SELECT SUM(b), COUNT(*) FROM w WHERE b > 7 AND c < 4",
    "SELECT f, SUM(b + c), MIN(b), MAX(b) FROM w WHERE b - 3 > c GROUP BY f ORDER BY f",
    "SELECT c, SUM(b * c) FROM w GROUP BY c ORDER BY c",
]


@pytest.fixture(scope="module")
def wide():
    """``w.b`` fits int32 in the first regions and not in the later ones: the
    batch binds ONCE over the union of the regions' min/max, so its narrow-lane
    and magnitude proofs must hold for every region's device arrays, whichever
    width each was uploaded in. A mapped program stacks lanes of one width only,
    so the batch is two groups. ``w.c`` is NULL in one row of 13 of the later
    regions and in none of the first: stacked with its validity in one group,
    without it in the other."""
    db = tidb_tpu.open(region_split_keys=1000)
    s = db.session()
    s.execute("CREATE TABLE w (id BIGINT PRIMARY KEY, b BIGINT, c INT, f CHAR(1))")
    ids = np.arange(4000, dtype=np.int64)
    c = [None if i % 13 == 0 and i >= 1500 else int(i % 5) for i in ids]  # NULLs: a group of their own, rows no comparison keeps
    cols = [ids, np.where(ids < 1500, ids, ids * 3_000_000_000), c, np.array([b"A", b"B"])[ids % 2]]
    for lo in range(0, 4000, 500):
        bulk_load(db, "w", [c[lo : lo + 500] for c in cols])
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    return db, s


@pytest.mark.parametrize("text", WIDE)
def test_one_bind_over_regions_of_different_widths(wide, text):
    _, s = wide
    s.query(text)
    rows, summary = _summary(s, text)
    assert summary.num == 1 and summary.regions >= 4 and summary.engines == {"tpu": 1}
    assert rows == _host(s, text)


@pytest.mark.parametrize("text", WIDE)
def test_mapped_partials_of_both_lane_widths_equal_each_regions_own(wide, text, monkeypatch):
    """Two groups (``b`` as int32, ``b`` as int64), a call each; the stacked
    results, unstacked, are the rows a call a region gives, in region order.
    Only the second group's ``c`` holds NULLs and is stacked with its validity."""
    db, s = wide
    s.query(text)
    (req,), rows = _requests(s, text, monkeypatch)
    regions = list(db.store.pd.regions_in_ranges(req.ranges))
    (res,) = list(CopClient(db.store).send(req))
    assert res.details.regions == len(regions) >= 4 and res.details.programs == 2
    entries = [tpu_engine.cache_for(db.store).head(r, req.data.executors[0].table_id, req.start_ts) for r, _ in regions]
    c_id = next(c.column_id for c in req.data.executors[0].columns if not c.is_handle and not entries[-1].all_valid(c.column_id))
    assert {e.all_valid(c_id) for e in entries} == {True, False}
    one_by_one = [tpu_engine.execute_dag(db.store, req.data, r, rg, req.start_ts) for r, rg in regions]
    assert res.chunk.rows() == Chunk.concat(one_by_one).rows()
    assert rows == _host(s, text)


# -- one program a padded shape ---------------------------------------------------


def _mapped_keys():
    """Kernel-cache keys of mapped programs: (n_pad, agg_cap, m)."""
    with dag_kernel._CACHE_MU:
        return sorted((key[1], key[2], key[6]) for key in dag_kernel._COMPILE_CACHE if key[6] > 1)


def test_five_six_and_seven_regions_share_one_program_and_padding_adds_no_row():
    """Regions of 1,000 rows, bulk-loaded one at a time so none is left half
    full: each count of them is padded to the ladder's first step."""
    db = tidb_tpu.open(region_split_keys=1000)
    s = db.session()
    s.execute("CREATE TABLE p (id BIGINT PRIMARY KEY, k INT, v BIGINT)")
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    text = "SELECT k, COUNT(*), SUM(v) FROM p GROUP BY k ORDER BY k"
    ids = np.arange(6000, dtype=np.int64)

    def load(lo):
        bulk_load(db, "p", [ids[lo : lo + 1000], ids[lo : lo + 1000] % 3, ids[lo : lo + 1000] % 100])  # every load the same bounds: one bound DAG

    for lo in range(0, 3000, 1000):
        load(lo)
    with dag_kernel._CACHE_MU:
        dag_kernel._COMPILE_CACHE.clear()
    seen = []
    for lo in (3000, 4000, 5000):
        load(lo)
        s.query(text)  # the new region's first read: a task of its own
        rows, summary = _summary(s, text)
        assert rows == _host(s, text) and sum(r[1] for r in rows) == lo + 1000  # a padding slot counts no row
        assert summary.num == 1 and summary.programs == 1
        seen.append(summary.regions)
        assert _mapped_keys() == [(1024, 1024, tpu_engine._MAP_STEP)]  # ONE entry, whatever the count
    assert seen == [5, 6, 7]


def test_mixed_padded_shapes_are_one_call_a_shape(monkeypatch):
    db, s = _mk_db(rows=9300, split=4000)  # regions of 2,000 to 3,000 rows: padded to 2,048 and to 4,096
    text = SHAPES["q6"]
    s.query(text)
    with dag_kernel._CACHE_MU:
        dag_kernel._COMPILE_CACHE.clear()
    rows, summary = _summary(s, text)
    assert rows == _host(s, text) and summary.num == 1
    with dag_kernel._CACHE_MU:
        shapes = {(key[1], key[4]) for key in dag_kernel._COMPILE_CACHE}  # (n_pad, full_scan)
    assert len(shapes) >= 2 and summary.programs == len(shapes) < summary.regions


def _eqns(jaxpr) -> int:
    import jax

    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _eqns(sub)
    return n


def test_mapped_program_is_no_larger_for_48_regions_than_for_8(served, monkeypatch):
    """Compile cost must not grow with the region count: the body is traced
    once, whatever ``m`` is."""
    import jax

    db, s = served
    sent = []
    real = tpu_engine._call_args
    monkeypatch.setattr(tpu_engine, "_call_args", lambda kernel, *a: sent.append((kernel, real(kernel, *a))) or sent[-1][1])
    bound = []
    real_get = tpu_engine.get_kernel
    monkeypatch.setattr(tpu_engine, "get_kernel", lambda dag, *a, **kw: bound.append((dag, a, kw)) or real_get(dag, *a, **kw))
    for shape in sorted(SHAPES):
        del sent[:], bound[:]
        _forget(db)
        s.query(SHAPES[shape])
        (kernel, (slots, _, _)), = [c for c in sent if c[0].m > 1]
        dag, (n_pad, agg_cap), kw = next(b for b in bound if b[2]["m"] == kernel.m)
        sizes = {}
        for m in (8, 48):
            k = dag_kernel._build(dag, n_pad, agg_cap, full_scan=kw["full_scan"], m=m)
            args = (slots[:1] * m, np.zeros((m, dag_kernel.MAX_RANGES, 2), np.int64), np.zeros(m, np.int64))
            sizes[m] = _eqns(jax.make_jaxpr(k.fn)(*args).jaxpr)
        handles, cols = slots[0]
        cols = tuple((d, d != d if v is None else v) for d, v in cols)  # the single-region program takes every validity
        single = _eqns(jax.make_jaxpr(dag_kernel._build(dag, n_pad, agg_cap, full_scan=kw["full_scan"]).fn)(
            cols[0][0] if handles is None else handles, cols, np.zeros((dag_kernel.MAX_RANGES, 2), np.int64), np.int64(0)).jaxpr)
        assert abs(sizes[48] - sizes[8]) < 0.1 * sizes[8], sizes
        assert sizes[48] < 1.5 * single + 40, (sizes, single)  # the body once, the stacking, the packing: not a body a region


# -- a region that is not clean leaves ------------------------------------------


def test_region_with_a_pending_delta_leaves_and_the_answer_holds_the_write(monkeypatch):
    # a base this small is rebuilt outright as shipped; let it be delta-tracked
    monkeypatch.setattr(config, "_CURRENT", dataclasses.replace(config.current(), device_delta_min_rows=1))
    db, s = _mk_db()
    text = SHAPES["q6"]
    s.query(text)
    before, summary = _summary(s, text)
    assert summary.num == 1 and summary.delta_rows == 0
    n = summary.regions
    s.execute(f"INSERT INTO t VALUES ({ROWS + 5}, 3, 1, 0.04, 1000.00, 'A', 'F', 1)")  # acknowledged: autocommit
    after, summary = _summary(s, text)
    assert after == _host(s, text)
    assert after[0][0] - before[0][0] == 40  # 1000.00 * 0.04: the answer holds the write
    assert summary.num == 2 and summary.regions == n  # the batch, and the written region alone
    assert summary.delta_rows == 1 and summary.engines == {"tpu": 2} and not summary.degraded
    # the group shrank by the region that left: still one mapped call, and the `_d` program of the region alone
    assert summary.programs == 2 and _mapped_keys() and all(m == tpu_engine._MAP_STEP for _, _, m in _mapped_keys())


def test_first_read_builds_each_region_in_a_task_of_its_own():
    db, s = _mk_db()
    rows, summary = _summary(s, SHAPES["q6"])
    assert summary.num == summary.regions >= 4  # nothing cached: every region left the batch, which served none
    again, summary = _summary(s, SHAPES["q6"])
    assert again == rows and summary.num == 1 and summary.regions >= 4


# -- faults keep their grain ------------------------------------------------------


def _die():
    raise RuntimeError("chaos: TPU device lost mid-task")


def test_engine_fault_degrades_that_region_alone(served):
    db, s = served
    text = SHAPES["q1"]
    want, clean = _summary(s, text)
    victim = sorted(r.region_id for r in db.store.regions())[-2]
    degraded = metrics.COP_DEGRADED.get(reason="embedded")
    shot = NShot(lambda rid, st: _die(), n_times=1, match=lambda rid, st: rid == victim and st == StoreType.TPU)
    with failpoint.enabled("cop_task_engine", shot):
        got, summary = _summary(s, text)
    assert shot.fired == 1 and got == want
    assert metrics.COP_DEGRADED.get(reason="embedded") == degraded + 1
    assert summary.num == 2 and summary.regions == clean.regions
    assert summary.engines == {"tpu": 1, "host": 1}  # the others stayed on the device, in the batch
    assert 1 <= summary.programs <= clean.programs  # the group shrank by one region; the host engine sends no program
    assert summary.degraded == {"embedded:RuntimeError": 1}
    assert any("degraded to host" in str(w) for w in s.query("SHOW WARNINGS"))


def test_region_error_resplits_that_region_alone(served):
    db, s = served
    text = SHAPES["q6"]
    want, clean = _summary(s, text)
    victim = sorted(r.region_id for r in db.store.regions())[1]
    backoffs = metrics.BACKOFF_TOTAL.get(config="regionMiss")

    def miss(rid, st):
        raise RegionError(rid, f"region {rid} epoch changed (chaos)")

    shot = NShot(miss, n_times=1, match=lambda rid, st: rid == victim)
    with failpoint.enabled("cop_task_engine", shot):
        got, summary = _summary(s, text)
    assert shot.fired == 1 and got == want
    assert metrics.BACKOFF_TOTAL.get(config="regionMiss") == backoffs + 1
    assert summary.num == 2 and summary.regions == clean.regions and summary.resplits == 1
    assert summary.engines == {"tpu": 2} and not summary.degraded


def test_seam_fires_once_a_region(served):
    db, s = served
    seen = []
    with failpoint.enabled("cop_task_engine", lambda rid, st: seen.append((rid, st))):
        _, summary = _summary(s, SHAPES["q6"])
    assert len(seen) == len(set(seen)) == summary.regions and {st for _, st in seen} == {StoreType.TPU}


def test_batch_that_fails_as_a_whole_falls_back_to_a_task_a_region(served, monkeypatch):
    db, s = served
    text = SHAPES["q1"]
    want, clean = _summary(s, text)
    real = tpu_engine._exec_single

    def broken(ph, store, dag, bound, scan, cache, parts, warn=None, keep=None):
        if len(parts) > 1:
            raise RuntimeError("chaos: the device dropped the batch")
        return real(ph, store, dag, bound, scan, cache, parts, warn, keep)

    monkeypatch.setattr(tpu_engine, "_exec_single", broken)
    _forget(db)  # a batch that fails on its resolved task's way: tests/test_cop_resolved.py
    got, summary = _summary(s, text)
    assert got == want
    assert summary.num == summary.regions == clean.regions  # no batch result; every region its own task
    assert summary.engines == {"tpu": clean.regions} and not summary.degraded


def test_agg_cap_overflow_reruns_that_region_alone():
    """A group a row; one region (4,500 rows as the load leaves them, the
    others 3,000 to 3,750) holds more groups than the first cap, 4,096: its
    program runs again at a cap that holds them, the others' results stand."""
    db, s = _mk_db(rows=24000, split=6000, groups=lambda ids: ids)
    text = "SELECT g, COUNT(*), SUM(q) FROM t GROUP BY g ORDER BY g"
    s.query(text)
    with dag_kernel._CACHE_MU:
        dag_kernel._COMPILE_CACHE.clear()
    rows, summary = _summary(s, text)
    assert len(rows) == 24000 and rows == _host(s, text)
    assert summary.num == 1 and summary.regions >= 4 and summary.engines == {"tpu": 1}
    with dag_kernel._CACHE_MU:
        caps = sorted(key[2] for key in dag_kernel._COMPILE_CACHE)
    assert caps[0] == 4096 and caps[-1] > 4096, caps
    assert sum(1 for c in caps if c > 4096) == 1  # one region's shape, once
    with dag_kernel._CACHE_MU:
        (grown,) = [key for key in dag_kernel._COMPILE_CACHE if key[2] > 4096]
    assert grown[6] == 1  # the re-run is that region's alone: the single-region program
    assert any(m > 1 and cap == 4096 for _, cap, m in _mapped_keys())  # it overflowed inside a mapped group
    assert summary.programs < summary.regions


# -- it says when it engages --------------------------------------------------------


def test_sidecar_span_and_counter_agree(served, tmp_path):
    import jax
    from jax.profiler import ProfileData

    db, s = served
    batched, single = _counts()
    programs = _programs()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, summary = _summary(s, SHAPES["q1"])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    spans.setdefault(ev.name[len(tracing.PREFIX):], []).append(dict(ev.stats))
    (task,) = spans["cop.task"]
    assert summary.num == 1 and summary.regions >= 4
    assert int(task["regions"]) == summary.regions
    # `regions` on the dispatch span is the programs sent: one a padded shape, not one a region
    assert [int(d["regions"]) for d in spans["exec.dispatch"]] == [summary.programs] == [int(task["programs"])]
    assert 1 <= summary.programs < summary.regions
    assert all(d["kernel"].startswith("cop_sel_agg_g2") for d in spans["exec.dispatch"])
    assert len(spans["exec.fetch"]) == len(spans["exec.decode"]) == 1  # fetched once, decoded once
    assert _counts() == (batched + summary.regions, single)
    assert sum(_programs()) == sum(programs) + summary.programs and _programs()[0] > programs[0]
    text = "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + SHAPES["q1"]))
    assert f"cop_task: {{num: 1," in text and f"regions: {summary.regions}, programs: {summary.programs}," in text


# -- what never batches ---------------------------------------------------------------


def test_one_region_request_takes_the_old_path(served):
    db, s = served
    batched, single = _counts()
    rows, summary = _summary(s, "SELECT SUM(v) FROM t WHERE id < 10")
    assert rows[0][0] * 100 == sum(i * 150 + 25 for i in range(10))
    assert summary.num == summary.regions == summary.programs == 1
    assert _counts() == (batched, single + 1)


def test_topn_request_takes_the_old_path(served, monkeypatch):
    db, s = served
    batched, single = _counts()
    (req,), rows = _requests(s, TOPN, monkeypatch)
    summary = s.exec_summary
    assert not cop_client._order_blind_partial(req, req.data)
    assert rows == _host(s, TOPN)
    assert summary.num == summary.regions >= 4  # a task a region
    assert _counts()[0] == batched


def test_host_engine_takes_the_old_path(served):
    db, s = served
    batched, _ = _counts()
    s.execute("SET tidb_isolation_read_engines = 'host'")
    try:
        _, summary = _summary(s, SHAPES["q6"])
    finally:
        s.execute("SET tidb_isolation_read_engines = 'tpu'")
    assert summary.num == summary.regions >= 4 and summary.engines == {"host": summary.num}
    assert _counts()[0] == batched


# -- the big task (ISSUE 35): more regions of one shape than one call holds -----------


@pytest.mark.parametrize("k,n_pad,counts", [
    (240, 262144, [64, 64, 64, 48]),  # tpch_sf10's lineitem: three calls at the ladder's top and a smaller rung
    (241, 262144, [64, 64, 64, 56]),
    (65, 262144, [64, 1]),  # a rest of one takes the single-region program
    (66, 262144, [64, 8]), (67, 262144, [64, 8]), (71, 262144, [64, 8]), (72, 262144, [64, 8]), (73, 262144, [64, 16]),
    (46, 262144, [48]), (64, 262144, [64]), (1, 262144, [1]), (2, 262144, [8]),
    (5, 524288, [8]), (33, 524288, [32, 1]),  # 2^24 padded rows a call: 32 regions of 524,288
    (3, 1 << 22, [1, 1, 1]),  # too large for a step of 8 to fit
])
def test_map_counts_by_value(k, n_pad, counts):
    assert tpu_engine._map_counts(k, n_pad) == counts
    assert 0 <= sum(counts) - k < tpu_engine._MAP_STEP  # every region has a slot, and padding is a rest's


@pytest.fixture(scope="module")
def many():
    """More than 64 clean regions of one padded shape."""
    db, s = _mk_db(rows=34_600, split=1000)
    for text in SHAPES.values():
        s.query(text)
    return db, s


def _dispatches(s, text, tmp_path):
    """(rows, the summary, the stats of the statement's ``tidb:exec.dispatch`` spans)."""
    import jax
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


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_more_regions_than_a_call_holds_are_still_one_task(many, shape, monkeypatch, tmp_path):
    db, s = many
    cache = tpu_engine.cache_for(db.store)
    tid = db.catalog.table("test", "t").id
    by_shape: dict = {}
    for r in db.store.regions():
        entry = cache.head(r, tid, db.store.current_ts())
        if entry is not None:
            by_shape[tpu_engine.bucket_size(entry.n)] = by_shape.get(tpu_engine.bucket_size(entry.n), 0) + 1
    n_pad = max(by_shape, key=by_shape.get)
    assert by_shape[n_pad] > 64, by_shape
    monkeypatch.setattr(tpu_engine, "_MAP_ROWS", 64 * n_pad)  # the ladder's top: 64 regions a call, as 2^24 rows are 64 of 262,144
    want = [c for n_pad, k in sorted(by_shape.items()) for c in tpu_engine._map_counts(k, n_pad)]
    assert want.count(64) == 1 and len(want) >= 2
    _forget(db)  # the calls kept were made under another ladder
    rows, summary, (dispatch,) = _dispatches(s, SHAPES[shape], tmp_path)
    assert rows == _host(s, SHAPES[shape])
    assert summary.num == 1 and summary.regions == sum(by_shape.values()) > 64  # ONE task, every region in it
    assert summary.programs == int(dispatch["regions"]) == len(want)
    assert int(dispatch["pad_slots"]) == sum(want) - summary.regions > 0  # a rest of 2-7 is padded to 8
    assert {m for pad, _, m in _mapped_keys() if pad == n_pad} >= set(c for c in want if c > 1)  # a program a rung


def test_rehearsal_of_the_sf10_cell_is_correct_and_prints_its_metrics(tmp_path):
    """`tpch_sf10.q1q6_1c` as the driver runs it, on the CPU at SF 0.02."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", "tpch_sf10.q1q6_1c", "--seed", "2147483951",
           "--seconds", "3", "--trace", "1", "--platform", "cpu", "--scale", "0.02"]
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=1", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600, cwd=root, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] >= 4 and line["rehearsal"] is True, line
    assert {k: c["value"] for k, c in line["checks"].items()} == {"answers_wrong": 0, "statements_failed": 0, "not_on_device": 0}
    m = line["metrics"]
    for name in ("frontend_ms", "cop_host_ms", "h2d_bytes_per_stmt", "colcache_merges", "cop_regions_per_task", "cop_programs_per_task",
                 "exec_bind_ms", "exec_dispatch_ms", "load_rows_per_s"):
        assert name in m, sorted(m)
    assert m["load_rows_per_s"]["unit"] == "rows/s" and m["load_rows_per_s"]["value"] > 10_000  # 1.53M rows: no rate is claimed here
    assert m["compiles_in_window"]["value"] == 0 and m["cop_regions_per_task"]["value"] >= 1
    assert "scan_roofline" not in m  # no chip, no share
    assert "generated {'customer': 3000, 'orders': 30000, 'lineitem': " in p.stderr


def test_a_program_that_loads_row_at_a_time_is_refused_the_sf10_cell_at_once():
    """The cell's generator ends the run of a program without the array loader
    (every commit before PR 35) before a table is made, exit code 4: such a
    run cannot end inside the check's limit, and one stopped there refuses a PR."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import runpy, sys, tidb_tpu.utils.chunk as chunk; del chunk.Dictionary.encode_many; "
            "sys.argv = ['benchmark/run.py', '--workload', 'tpch_sf10.q1q6_1c', '--seed', '2147483951', '--seconds', '3', '--platform', 'cpu', '--scale', '0.02']; "
            "runpy.run_path('benchmark/run.py', run_name='__main__')")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd=root,
                       env=dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert p.returncode == 4, p.stderr[-3000:]
    assert "loads row at a time" in p.stderr and "generated" not in p.stderr and p.stdout.strip() == ""
