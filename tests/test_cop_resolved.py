"""The resolved batch task (ISSUE 36): a batch cop task whose regions are clean
and unchanged since the task before it re-sends the program calls that task
derived (``colcache.ResolvedTask``, ``tpu_engine._batch_path`` /
``_run_resolved``) and derives nothing. What must hold: a hit answers row for
row what a miss and the host engine answer; everything that changes what the
task would derive — a write, a split, a merge, a compacted dictionary, a
dropped table, an older snapshot, a faulted region, a failed batch — is a miss
(or ``stale``) answered right by the old path; an overflow's re-run is kept in
the form that answered; tasks read one resolved task at once; the tasks hold
no array the device LRU let go of; every serve is counted for the heatmap;
span, counter, sidecar and EXPLAIN say the same; the store stays collectable."""

import dataclasses
import gc
import glob
import threading
import weakref

import numpy as np
import pytest

from tests.test_cop_batch import Q1, Q6, ROWS, _forget, _host, _mk_db, _requests, _summary
from tidb_tpu import config
from tidb_tpu.copr import tpu_engine
from tidb_tpu.copr.client import CopClient
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.kv import tablecodec
from tidb_tpu.kv.fault_injection import NShot
from tidb_tpu.kv.kv import StoreType
from tidb_tpu.utils import execdetails, failpoint, metrics, tracing

TEXTS = {
    "q1": [Q1.format(k) for k in (2, 5, 6)],
    "q6": [Q6.format(lo, hi) for lo, hi in ((1, 6), (0, 3), (2, 7))],
}
Q6_TEXT, Q1_TEXT = TEXTS["q6"][0], TEXTS["q1"][1]


def _cache(db):
    return tpu_engine.cache_for(db.store)


def _tid(db):
    return db.catalog.table("test", "t").id


def _how(s, text):
    """(rows, how the statement's batch task met its resolved task, the summary)."""
    rows, summary = _summary(s, text)
    (how,) = summary.resolved or {None: 1}
    return rows, how, summary


def _settle(s, text):
    """Run ``text`` until its batch task hits: at most a first read (a task a
    region), a miss, a hit."""
    for _ in range(4):
        rows, how, _ = _how(s, text)
        if how == "hit":
            return rows
    raise AssertionError(f"no hit after four runs: {how}")


def _resolved_counts():
    return {how: metrics.COP_TASK_RESOLVED.get(how=how) for how in ("hit", "miss", "stale")}


def _moved(before):
    return {how: n - before[how] for how, n in _resolved_counts().items() if n != before[how]}


@pytest.fixture(scope="module")
def served():
    db, s = _mk_db()
    for texts in TEXTS.values():
        for text in texts:
            s.query(text)  # the first read builds every region's entry, a task a region
    return db, s


@pytest.fixture
def fresh():
    return _mk_db()


# -- a hit answers what a miss answers ----------------------------------------------


@pytest.mark.parametrize("shape,i", [(shape, i) for shape in sorted(TEXTS) for i in range(3)])
def test_a_hit_answers_row_for_row_what_a_miss_and_the_host_answer(served, shape, i, monkeypatch):
    db, s = served
    text = TEXTS[shape][i]
    _forget(db)
    (req,), _ = _requests(s, text, monkeypatch)  # the miss that resolves it
    assert s.exec_summary.resolved == {"miss": 1}
    before = _resolved_counts()
    (hit,) = list(CopClient(db.store).send(req))
    assert hit.details.resolved == "hit" and _moved(before) == {"hit": 1}
    _forget(db)
    (miss,) = list(CopClient(db.store).send(req))
    assert miss.details.resolved == "miss"
    assert hit.chunk.rows() == miss.chunk.rows() and len(hit.chunk)  # the partials, region after region
    for key in ("regions", "programs", "dev_cache_hits", "dev_cache_misses", "h2d_bytes", "d2h_bytes", "engine", "degraded"):
        assert getattr(hit.details, key) == getattr(miss.details, key), key
    assert hit.details.regions >= 4 and hit.details.dev_cache_misses == 0
    rows, how, _ = _how(s, text)
    assert how == "hit" and rows == _host(s, text)


def test_a_templates_parameter_sets_are_a_task_each_over_the_same_arrays(served):
    db, s = served
    _forget(db)
    for text in TEXTS["q6"]:
        assert _how(s, text)[1] == "miss"
    tasks = list(_cache(db)._resolved.values())
    assert len(tasks) == 3 and all(_how(s, text)[1] == "hit" for text in TEXTS["q6"])
    assert len({tuple(t.keys) for t in tasks}) == 1  # the same device arrays
    assert len({id(t.calls[0][0]) for t in tasks}) == 3  # a program a literal (the cop path bakes them in)


def test_the_kept_tasks_are_bounded_by_count(served, monkeypatch):
    from tidb_tpu.copr import colcache

    db, s = served
    monkeypatch.setattr(colcache, "RESOLVED_TASKS", 2)
    _forget(db)
    for text in TEXTS["q6"]:
        s.query(text)
    assert len(_cache(db)._resolved) == 2
    assert _how(s, TEXTS["q6"][0])[1] == "miss"  # the least recently used went
    assert _how(s, TEXTS["q6"][2])[1] == "hit"


# -- every way out is a miss, and the answer after it is right -------------------------


def test_a_committed_write_takes_its_region_out_and_is_in_the_answer(monkeypatch):
    # a base this small is rebuilt outright as shipped; let it be delta-tracked
    monkeypatch.setattr(config, "_CURRENT", dataclasses.replace(config.current(), device_delta_min_rows=1))
    db, s = _mk_db()
    before = _settle(s, Q6_TEXT)
    n = s.exec_summary.regions
    s.execute(f"INSERT INTO t VALUES ({ROWS + 5}, 3, 1, 0.04, 1000.00, 'A', 'F', 1)")  # acknowledged: autocommit
    after, how, summary = _how(s, Q6_TEXT)
    assert how == "stale" and after == _host(s, Q6_TEXT)
    assert after[0][0] - before[0][0] == 40  # 1000.00 * 0.04: read-your-acknowledged-writes
    assert summary.num == 2 and summary.regions == n and summary.delta_rows == 1
    # the written region is not among the kept: it leaves again, the others hit
    again, how, summary = _how(s, Q6_TEXT)
    assert how == "hit" and again == after and summary.num == 2 and summary.delta_rows == 1
    (task,) = _cache(db)._resolved.values()
    assert len(task.kept) == n - 1 and len(task.left) == 1
    # a merge gives the region a head again: it belongs in the batch, so the task is resolved anew
    assert _cache(db).merge_pending(threshold=1) == 1
    merged, how, summary = _how(s, Q6_TEXT)
    assert how == "stale" and merged == after and not summary.delta_rows
    assert not _cache(db)._resolved  # the compactor's entry has its slots still to decode: the region left WITH a head, nothing is kept
    assert _how(s, Q6_TEXT)[:2] == (after, "miss")
    rows, how, summary = _how(s, Q6_TEXT)
    assert (rows, how) == (after, "hit") and summary.num == 1 and summary.regions == n


def test_a_write_to_a_base_rebuilt_outright_is_in_the_answer(fresh):
    db, s = fresh
    before = _settle(s, Q6_TEXT)
    s.execute(f"INSERT INTO t VALUES ({ROWS + 5}, 3, 1, 0.04, 1000.00, 'A', 'F', 1)")
    after, how, _ = _how(s, Q6_TEXT)
    assert how == "stale" and after == _host(s, Q6_TEXT) and after[0][0] - before[0][0] == 40
    assert _settle(s, Q6_TEXT) == after


def test_a_split_is_a_miss(fresh):
    db, s = fresh
    want = _settle(s, Q1_TEXT)
    n = s.exec_summary.regions
    db.store.split_region(tablecodec.record_key(_tid(db), ROWS // 2 + 137))
    rows, how, _ = _how(s, Q1_TEXT)
    assert how == "miss" and rows == want == _host(s, Q1_TEXT)  # other regions, other ranges: another key
    assert _settle(s, Q1_TEXT) == want and s.exec_summary.regions == n + 1


def test_a_merge_that_replaced_an_entry_is_stale(fresh):
    db, s = fresh
    want = _settle(s, Q1_TEXT)
    cache, tid = _cache(db), _tid(db)
    region = db.store.regions()[1]
    old = cache.head(region, tid, db.store.current_ts())
    assert cache.merge_now(region, tid, None, (), db.store.current_ts()) is old  # a head is left alone
    assert _how(s, Q1_TEXT)[1] == "hit"
    new = cache._merge((region.region_id, tid), region, tid, None, (), db.store.current_ts(), old)
    assert new is not old and cache.head(region, tid, db.store.current_ts()) is new
    rows, how, _ = _how(s, Q1_TEXT)
    assert how in ("stale", None) and rows == want  # the new entry has a slot to decode: the region may take every other with it
    assert _settle(s, Q1_TEXT) == want


def test_a_compacted_dictionary_is_stale(fresh):
    db, s = fresh
    s.execute(f"INSERT INTO t VALUES ({ROWS + 5}, 3, 1, 0.04, 1000.00, 'B', 'F', 1)")  # 'B' after 'A', 'N', 'R': codes out of order
    want = _settle(s, Q1_TEXT)
    cache, tid = _cache(db), _tid(db)
    slot = [c.name for c in db.catalog.table("test", "t").columns].index("f")
    epoch = cache.epoch
    cache.ensure_sorted_dict(tid, slot)
    assert cache.epoch == epoch + 1
    rows, how, _ = _how(s, Q1_TEXT)
    assert how == "stale" and rows == want == _host(s, Q1_TEXT)  # the kept arrays hold the codes of before
    assert _settle(s, Q1_TEXT) == want


def test_invalidate_table_drops_the_tables_tasks(fresh):
    db, s = fresh
    want = _settle(s, Q6_TEXT)
    cache = _cache(db)
    assert len(cache._resolved) == 1
    cache.invalidate_table(_tid(db))
    assert not cache._resolved
    before = _resolved_counts()
    rows, summary = _summary(s, Q6_TEXT)
    assert _moved(before) == {"miss": 1} and rows == want
    assert summary.num == summary.regions and not summary.resolved  # nothing cached: a task a region, the batch served none
    assert _settle(s, Q6_TEXT) == want


def test_a_snapshot_older_than_an_entry_is_stale_and_reads_its_own_time(fresh):
    db, s = fresh
    old = db.session()
    old.execute("SET tidb_isolation_read_engines = 'tpu'")
    before = _settle(s, Q6_TEXT)
    old.execute("BEGIN")
    assert _how(old, Q6_TEXT)[::2][0] == before and old.exec_summary.resolved == {"hit": 1}
    s.execute(f"INSERT INTO t VALUES ({ROWS + 5}, 3, 1, 0.04, 1000.00, 'A', 'F', 1)")
    after = _settle(s, Q6_TEXT)  # the written region's entry is rebuilt at a time after the snapshot's
    assert after[0][0] - before[0][0] == 40
    rows, how, summary = _how(old, Q6_TEXT)
    assert how == "stale" and rows == before  # snapshot isolation: not the write, not the newer entry
    assert summary.num == 2  # the region whose entry is too new for it left
    old.execute("COMMIT")
    assert _settle(s, Q6_TEXT) == after == _host(s, Q6_TEXT)


def _die():
    raise RuntimeError("chaos: TPU device lost mid-task")


def test_a_region_faulted_at_the_seam_is_another_batch(served):
    db, s = served
    want = _settle(s, Q1_TEXT)
    victim = sorted(r.region_id for r in db.store.regions())[-2]
    shot = NShot(lambda rid, st: _die(), n_times=1, match=lambda rid, st: rid == victim and st == StoreType.TPU)
    with failpoint.enabled("cop_task_engine", shot):
        rows, how, summary = _how(s, Q1_TEXT)
    assert shot.fired == 1 and rows == want and how == "miss"  # the batch the engine sees lacks the region
    assert summary.engines == {"tpu": 1, "host": 1}
    assert _how(s, Q1_TEXT) [:2] == (want, "hit")  # the whole batch's task is as it was


def test_a_batch_that_fails_on_its_resolved_tasks_way_falls_back_and_drops_it(served, monkeypatch):
    db, s = served
    want = _settle(s, Q1_TEXT)
    n = s.exec_summary.regions
    assert len(_cache(db)._resolved) >= 1
    real = tpu_engine._run_all
    failed = []

    def broken(ph, calls):
        if not failed and sum(len(live) for _, _, live in calls) > 1:
            failed.append(len(calls))
            raise RuntimeError("chaos: the device dropped the batch")
        return real(ph, calls)

    monkeypatch.setattr(tpu_engine, "_run_all", broken)
    held = set(_cache(db)._resolved)
    before = _resolved_counts()
    rows, summary = _summary(s, Q1_TEXT)
    assert failed and rows == want and _moved(before) == {"hit": 1}  # it was a hit until it was sent
    assert summary.num == summary.regions == n  # the batch served none; every region a task of its own
    assert summary.engines == {"tpu": n} and not summary.degraded
    assert len(held - set(_cache(db)._resolved)) == 1  # dropped before the regions left
    assert _how(s, Q1_TEXT)[:2] == (want, "miss")
    assert _how(s, Q1_TEXT)[:2] == (want, "hit")


def test_an_overflows_rerun_is_kept_in_the_form_that_answered():
    """A group a row; one region holds more groups than the first cap: the
    resolved task is the mapped call it overflowed in AND the re-run at the cap
    that held it, sent together; the re-run's result stands."""
    db, s = _mk_db(rows=24000, split=6000, groups=lambda ids: ids)
    text = "SELECT g, COUNT(*), SUM(q) FROM t GROUP BY g ORDER BY g"
    s.query(text)
    first, how, miss = _how(s, text)
    assert how == "miss" and len(first) == 24000 and first == _host(s, text)
    second, how, hit = _how(s, text)
    assert how == "hit" and second == first
    assert hit.programs == miss.programs and hit.regions == miss.regions >= 4
    (task,) = _cache(db)._resolved.values()
    caps = sorted(kernel.agg_cap for kernel, _, _ in task.calls)
    assert caps[0] == 4096 and caps[-1] > 4096 and sum(1 for c in caps if c > 4096) == 1
    assert len(task.answered) == len(task.kept) + 1  # one region answered twice


def test_four_threads_read_one_resolved_task(served):
    db, s = served
    want = _settle(s, Q1_TEXT)
    (key,) = [k for k, t in _cache(db)._resolved.items() if any(kernel.kind != "rows" for kernel, _, _ in t.calls)][-1:]
    task = _cache(db)._resolved[key]
    sessions = [db.session() for _ in range(4)]
    for c in sessions:
        c.execute("SET tidb_isolation_read_engines = 'tpu'")
    before = _resolved_counts()
    got, errors = [], []
    start = threading.Barrier(4)

    def client(c):
        try:
            start.wait()
            for _ in range(6):
                got.append((c.query(Q1_TEXT), dict(c.exec_summary.resolved)))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,)) for c in sessions]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors and len(got) == 24
    assert all(rows == want and how == {"hit": 1} for rows, how in got)
    assert _moved(before) == {"hit": 24}
    assert _cache(db)._resolved[key] is task  # published once, read by all


# -- HBM stays the LRU's ---------------------------------------------------------------


def test_a_use_of_a_task_is_a_use_of_its_arrays_in_one_step():
    class Holder:
        def __init__(self, keys):
            self.keys = keys

    lru = tpu_engine._DeviceLRU(300)
    a, b, c, d = ((9, 1, 1, s, "s", 0, 0, 64) for s in range(4))
    for k in (a, b, c):
        lru.put(k, (k,), 100)
    holder = Holder([a])
    lru.touch(holder)
    assert list(lru._entries) == [a, b, c]  # nothing moved yet: one step, whatever it holds
    lru.put(d, (d,), 100)
    assert lru.holds([a, c, d]) and not lru.holds([b])  # a was as recent as the task's use
    gone = Holder([c])
    lru.touch(gone)
    del gone
    gc.collect()
    lru.put(b, (b,), 100)  # a holder that died pins nothing and moves nothing: c is the oldest still
    assert lru.holds([a, d, b]) and not lru.holds([c])


def test_resolved_tasks_hold_nothing_the_lru_evicted(monkeypatch):
    db, s = _mk_db()
    big = tpu_engine._DeviceLRU(1 << 40)
    monkeypatch.setattr(tpu_engine, "_DEVICE_LRU", big)
    want = _settle(s, Q6_TEXT)
    table_bytes = big.total
    cache = _cache(db)

    def holds_only_what_the_lru_holds(lru):
        return all(lru.holds(t.keys) for t in cache._resolved.values())

    # a budget that holds the table: the task is kept, and holds what the LRU holds
    fits = tpu_engine._DeviceLRU(table_bytes + table_bytes // 4)
    monkeypatch.setattr(tpu_engine, "_DEVICE_LRU", fits)
    _forget(db)
    assert _settle(s, Q6_TEXT) == want and len(cache._resolved) == 1 and holds_only_what_the_lru_holds(fits)
    # another table's arrays push the first one's out: its task goes with them
    s.execute("CREATE TABLE u (id BIGINT PRIMARY KEY, k INT, q INT, d DECIMAL(4,2), v DECIMAL(12,2))")
    ids = np.arange(ROWS, dtype=np.int64)
    for lo in range(0, ROWS, 500):
        bulk_load(db, "u", [c[lo : lo + 500] for c in (ids, ids % 7, ids % 50, ids % 11, ids * 150 + 25)])
    other = Q6_TEXT.replace("FROM t", "FROM u")
    assert _settle(s, other) == want
    assert fits.total <= fits.budget + table_bytes // 8
    assert holds_only_what_the_lru_holds(fits)
    assert all(any(ekey[1] != _tid(db) for _, ekey, _ in t.kept) for t in cache._resolved.values())  # t's went
    assert _how(s, Q6_TEXT)[0] == want
    # a budget too small for one task's arrays: nothing is kept, everything is answered
    small = tpu_engine._DeviceLRU(table_bytes // 3)
    monkeypatch.setattr(tpu_engine, "_DEVICE_LRU", small)
    _forget(db)
    for _ in range(3):
        rows, how, _ = _how(s, Q6_TEXT)
        assert rows == want and how == "miss" and not cache._resolved
    assert small.total <= small.budget + table_bytes // 8


# -- what a hit still owes -----------------------------------------------------------------


def test_every_serve_is_counted_for_the_heatmap(served, monkeypatch):
    db, s = served
    _forget(db)
    noted = []
    real = db.store.note_region_read
    monkeypatch.setattr(db.store, "note_region_read", lambda *a: noted.append(a) or real(*a))
    _, how, summary = _how(s, Q1_TEXT)
    miss, noted[:] = list(noted), []
    _, how2, _ = _how(s, Q1_TEXT)
    assert (how, how2) == ("miss", "hit")
    assert noted == miss and len(noted) == summary.regions and all(keys > 0 and nbytes > 0 for _, _, keys, nbytes in noted)


def test_span_counter_sidecar_and_explain_agree(served, tmp_path):
    import jax
    from jax.profiler import ProfileData

    db, s = served
    _settle(s, Q1_TEXT)
    before = _resolved_counts()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, summary = _summary(s, Q1_TEXT)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    spans.setdefault(ev.name[len(tracing.PREFIX):], []).append(dict(ev.stats))
    assert len(spans["cop.task"]) == 1
    assert [b["resolved"] for b in spans["exec.bind"]] == ["hit"]  # a hit binds once and says so there
    for phase in ("inputs", "dispatch", "fetch", "decode"):
        assert len(spans["exec." + phase]) == 1, phase  # the five phases tile a hit too
    assert summary.resolved == {"hit": 1} and _moved(before) == {"hit": 1}
    assert [int(d["regions"]) for d in spans["exec.dispatch"]] == [summary.programs]
    text = "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + Q1_TEXT))
    assert f"programs: {summary.programs}, resolved: hit," in text
    _forget(db)
    text = "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + Q1_TEXT))
    assert "resolved: miss," in text


def test_the_sidecar_carries_it_over_the_wire():
    det = execdetails.CopExecDetails(7)
    assert "rv" not in det.to_pb()
    det.resolved = "stale"
    back = execdetails.CopExecDetails(7)
    back.merge_pb(det.to_pb())
    assert det.to_pb()["rv"] == "stale" and back.resolved == "stale"
    summary = execdetails.CopTasksSummary()
    for how in ("hit", "hit", "miss", ""):
        d = execdetails.CopExecDetails(1)
        d.resolved = how
        summary.add(d)
    assert summary.resolved == {"hit": 2, "miss": 1} and "resolved: hit×2 miss}" in summary.render()


def test_a_task_that_is_not_a_batch_says_nothing(served):
    db, s = served
    before = _resolved_counts()
    _, summary = _summary(s, "SELECT SUM(v) FROM t WHERE id < 10")  # one region: the old path
    assert summary.num == 1 and not summary.resolved and not _moved(before)
    assert "resolved" not in summary.render()


def test_a_closed_store_is_collectable():
    db, s = _mk_db()
    _settle(s, Q6_TEXT)
    _settle(s, Q1_TEXT)
    cache = _cache(db)
    assert len(cache._resolved) == 2
    store, kept, task = weakref.ref(db.store), weakref.ref(cache), weakref.ref(next(iter(cache._resolved.values())))
    del db, s, cache
    gc.collect()
    assert store() is None and kept() is None and task() is None
