"""A mapped cop call's lanes kept stacked on the device: the regions of a batch
task that one mapped program answers are read from ``(m, n_pad)`` arrays the
device LRU keeps beside the regions' own (``tpu_engine._lane_stack``), built
once by one jitted stack and keyed by the member arrays' own LRU keys. What
must hold: a mapped call over kept stacks answers lane for lane what the
single-region program answers region by region, at any count, with and without
NULLs; a write to a member gives it new keys, so the next task builds a new
stack and the old one is never read again (and leaves HBM); a stack's bytes
count in the LRU's budget and its eviction un-resolves the tasks that hold it;
threads reading one resolved task share one stack; the ``inputs`` span and
the ``tidb_tpu_cop_lane_stacks_total`` counter say how many mapped calls read
kept stacks and how many built one."""

import dataclasses
import glob
import sys
import threading

import jax
import numpy as np
import pytest

import tidb_tpu
from tidb_tpu import config
from tests.test_cop_batch import _forget, _host, _requests
from tests.test_cop_resolved import _how, _settle
from tidb_tpu.copr import tpu_engine
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.kv import tablecodec
from tidb_tpu.utils import metrics, tracing
from tidb_tpu.utils.chunk import Chunk

ROWS, SPLIT = 4000, 100
# `n` is NULL in a row of 7: its validity is a lane of its own, stacked too
NULLS = "SELECT r, SUM(n), COUNT(n), COUNT(*), SUM(q) FROM t GROUP BY r"
NO_NULLS = "SELECT SUM(v * d), COUNT(*) FROM t WHERE q < 24"


def _mk_db():
    """``t``: ``ROWS`` rows in regions of 50–100 (one padded shape, 1,024)."""
    db = tidb_tpu.open(region_split_keys=SPLIT)
    s = db.session()
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, q INT, d DECIMAL(4,2), v DECIMAL(12,2), n INT, r CHAR(1))")
    ids = np.arange(ROWS, dtype=np.int64)
    n = [None if i % 7 == 3 else int(i % 13) for i in ids]
    cols = [ids, ids % 50, ids % 11, ids * 150 + 25, n, np.array([b"A", b"N", b"R"])[ids % 3]]
    for lo in range(0, ROWS, SPLIT // 2):
        bulk_load(db, "t", [c[lo : lo + SPLIT // 2] for c in cols])
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    for text in (NULLS, NO_NULLS):  # every region's column-cache entry: a first read is a task a region
        s.query(text)
    return db, s


@pytest.fixture(scope="module")
def served():
    return _mk_db()


@pytest.fixture
def lru(monkeypatch):
    """A device LRU of this test's own: what it holds was put by the test."""
    lru = tpu_engine._DeviceLRU(1 << 40)
    monkeypatch.setattr(tpu_engine, "_DEVICE_LRU", lru)
    return lru


def _stacks(lru) -> dict:
    """The lane stacks ``lru`` keeps: key → (array, the bytes it counts)."""
    with lru._mu:
        return {k: v for k, v in lru._entries.items() if k[0] == tpu_engine._STACKED}


def _lane_counts():
    return {how: metrics.COP_LANE_STACKS.get(how=how) for how in ("kept", "built")}


def _moved(before):
    return {how: n - before[how] for how, n in _lane_counts().items() if n != before[how]}


def _mapped_calls():
    return metrics.COP_PROGRAMS.get(form="mapped")


# -- the same answers -------------------------------------------------------------------


@pytest.mark.parametrize("k,m", [(2, 8), (8, 8), (46, 48)])
@pytest.mark.parametrize("text", [NULLS, NO_NULLS], ids=["nulls", "no_nulls"])
def test_a_mapped_call_over_kept_stacks_answers_as_each_region_alone(served, lru, monkeypatch, text, k, m):
    db, s = served
    (req,), rows = _requests(s, text, monkeypatch)
    assert rows == _host(s, text)
    regions = list(db.store.pd.regions_in_ranges(req.ranges))
    assert len(regions) >= 48
    batch = regions[:k]
    alone = Chunk.concat([tpu_engine.execute_dag(db.store, req.data, r, rg, req.start_ts) for r, rg in batch]).rows()
    sent = []
    real = tpu_engine._call_args
    monkeypatch.setattr(tpu_engine, "_call_args", lambda kernel, *a: sent.append((kernel, real(kernel, *a))) or sent[-1][1])
    for how in ("built", "kept"):  # resolved anew both times: the first builds the stacks, the second reads them kept
        _forget(db)
        before, left = _lane_counts(), []
        got = tpu_engine.execute_dag(db.store, req.data, None, None, req.start_ts, batch=batch, leave=left.extend)
        assert not left and got.rows() == alone  # the partials, region after region
        assert _moved(before) == {how: 1}
    (kernel, (lanes, ranges, nvalid)), _ = [c for c in sent if c[0].m > 1]
    assert kernel.m == m and ranges.shape[0] == m and list(np.asarray(nvalid) > 0) == [True] * k + [False] * (m - k)
    _, cols = lanes
    assert all(a.shape == (m, 1024) for a in jax.tree.leaves(lanes))
    stacks = _stacks(lru)
    assert all(any(a is v for v, _ in stacks.values()) for a in jax.tree.leaves(lanes))  # every lane a kept stack
    with_validity = [v is not None for _, v in cols]
    assert any(with_validity) == (text == NULLS)  # only a lane with a NULL in it carries its validity
    # the padding slots hold the first region's rows again
    first = jax.tree.leaves(lanes)[0]
    assert all(np.array_equal(first[0], first[j]) for j in range(k, m))


def _eqns(jaxpr) -> list:
    """Every equation of ``jaxpr`` and of the jaxprs inside it (the jitted program's own, vmap's body)."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _eqns(sub)
    return out


@pytest.mark.parametrize("text", [NULLS, NO_NULLS], ids=["nulls", "no_nulls"])
def test_the_mapped_program_copies_nothing_into_a_stack(served, monkeypatch, text):
    """Traced on the stacked shapes: no value of ``m * n_pad`` rows (the old
    concatenate of the regions' arrays) and no barrier around one."""
    db, s = served
    sent, bound = [], []
    real_args, real_get = tpu_engine._call_args, tpu_engine.get_kernel
    monkeypatch.setattr(tpu_engine, "_call_args", lambda kernel, *a: sent.append((kernel, real_args(kernel, *a))) or sent[-1][1])
    monkeypatch.setattr(tpu_engine, "get_kernel", lambda dag, *a, **kw: bound.append((dag, a, kw)) or real_get(dag, *a, **kw))
    _forget(db)
    s.query(text)
    monkeypatch.undo()
    (kernel, args), = [c for c in sent if c[0].m > 1]
    m, n_pad = kernel.m, 1024
    eqns = _eqns(jax.make_jaxpr(kernel.fn)(*args).jaxpr)
    assert len(eqns) > 10 and not any(e.primitive.name == "optimization_barrier" for e in eqns)
    shapes = [tuple(v.aval.shape) for e in eqns for v in e.outvars]
    assert not any(m * n_pad in shape for shape in shapes), sorted(set(shapes))


# -- a write gives a member new keys ------------------------------------------------------


def test_a_write_to_a_member_builds_a_new_stack_and_never_reads_the_old(lru, monkeypatch):
    # a base this small is rebuilt outright as shipped; let it be delta-tracked, then merged
    monkeypatch.setattr(config, "_CURRENT", dataclasses.replace(config.current(), device_delta_min_rows=1))
    db, s = _mk_db()
    text = NO_NULLS
    _settle(s, text)
    tid = db.catalog.table("test", "t").id
    written = db.store.region_for_key(tablecodec.record_key(tid, 1037))
    old = {mk for sk in _stacks(lru) for mk in sk[2] if mk[1] == written.region_id}
    assert old  # the written region is a member of a kept stack
    read = []
    real = tpu_engine._lane_stack
    monkeypatch.setattr(tpu_engine, "_lane_stack", lambda part, members, arrays: read.append(members) or real(part, members, arrays))
    s.execute("UPDATE t SET q = 1, v = v + 1 WHERE id = 1037")  # acknowledged: autocommit
    want = _host(s, text)
    before = _lane_counts()
    # the written region leaves the batch with its delta: the others are a group of new members
    assert _how(s, text)[:2] == (want, "stale") and _moved(before) == {"built": 1}
    assert _how(s, text)[:2] == (want, "hit") and _moved(before) == {"built": 1, "kept": 1}
    assert read and not any(mk in old for members in read for mk in members)
    # merged, it comes back with new keys: a new stack is built, the old ones left HBM with the arrays they copy
    assert tpu_engine.cache_for(db.store).merge_pending(threshold=1) == 1
    assert _settle(s, text) == want
    assert _moved(before)["built"] == 2
    assert not any(mk in old for members in read for mk in members)  # the old stacks were never read
    assert not any(mk in old for sk in _stacks(lru) for mk in sk[2])
    new = {mk for sk in _stacks(lru) for mk in sk[2] if mk[1] == written.region_id}
    assert new and any(set(members) & new for members in read)  # the new one holds the write


# -- HBM stays the LRU's -------------------------------------------------------------------


def test_a_stacks_bytes_count_in_the_budget_and_its_eviction_unresolves_its_tasks(served, lru):
    db, s = served
    _forget(db)
    want = _settle(s, NULLS)
    cache = tpu_engine.cache_for(db.store)
    (task,) = cache._resolved.values()
    stacks = _stacks(lru)
    assert stacks and set(stacks) <= set(task.keys) and lru.holds(task.keys)
    for key, (stack, nbytes) in stacks.items():
        assert nbytes == stack.nbytes == len(key[2]) * 1024 * stack.dtype.itemsize
    with lru._mu:
        arrays = sum(nb for k, (_, nb) in lru._entries.items() if k[0] != tpu_engine._STACKED)
    assert lru.total == arrays + sum(nb for _, nb in stacks.values())
    # the LRU lets go of one stack, and only it: the task that holds it goes with it
    victim = next(iter(stacks))
    lru.get(victim)  # the task's last use is applied to the order first
    with lru._mu:
        lru._entries.move_to_end(victim, last=False)
    lru.budget = lru.total
    lru.put(("filler", 0, 0), None, 1)
    assert victim not in lru._entries and not cache._resolved
    assert lru.holds([k for k in task.keys if k[0] != tpu_engine._STACKED])
    lru.budget = 1 << 40
    before = _lane_counts()
    rows, how, _ = _how(s, NULLS)
    assert rows == want and how == "miss" and _moved(before) == {"built": 1}
    assert _how(s, NULLS)[1] == "hit"


def test_a_superseded_member_takes_its_stacks_with_it_resident_or_not(monkeypatch):
    lru = tpu_engine._DeviceLRU(1 << 30)
    unresolved = []
    monkeypatch.setattr(tpu_engine, "_unresolve_evicted", unresolved.extend)
    a, b = ((9, r, 1, 5, "s", 0, 0, 64) for r in (1, 2))
    for k in (a, b):
        lru.put(k, (k,), 100)
    both = (tpu_engine._STACKED, "d", (a, b, a))
    only_b = (tpu_engine._STACKED, "d", (b, b))
    lru.put(both, ("ab",), 300)
    lru.put(only_b, ("bb",), 200)
    assert lru.put(both, ("again",), 300) == ("ab",) and lru.total == 700  # what two tasks built at once is kept once
    lru.budget = 700
    lru.put((9, 3, 1, 5, "s", 0, 0, 64), ("c",), 100)  # the budget lets go of a's arrays, the oldest: its stack stays good
    assert lru.get(a) is None and lru.get(both) == ("ab",) and lru.total == 700
    lru.budget = 1 << 30
    lru.put((9, 1, 1, 5, "s", 1, 0, 64), ("a1",), 100)  # region 1 written: version 1
    lru.evict_superseded((9, 1, 1, 5, "s"), (1, 0))
    assert lru.get(both) is None and lru.get(only_b) == ("bb",) and lru.total == 500
    assert unresolved == [a, both]
    monkeypatch.undo()  # the real one expands a stack into its members' regions
    gone = []

    class Cache:
        def unresolve_regions(self, regions):
            gone.append(regions)

    class Store:
        nonce = 9

    monkeypatch.setattr(tpu_engine, "caches", lambda: [(Store(), Cache())])
    tpu_engine._unresolve_evicted([both])
    assert gone == [{(1, 1), (2, 1)}]


# -- threads -----------------------------------------------------------------------------------


def test_four_threads_reading_one_resolved_task_share_one_stack(served, lru):
    db, s = served
    _forget(db)
    want = _host(s, NULLS)
    sessions = [db.session() for _ in range(4)]
    for sess in sessions:
        sess.execute("SET tidb_isolation_read_engines = 'tpu'")
    got, errors = [], []
    start = threading.Barrier(4)
    before, calls = _lane_counts(), _mapped_calls()

    def read(sess):
        try:
            start.wait(timeout=60)
            for _ in range(5):
                got.append(sess.query(NULLS))
        except Exception as e:  # noqa: BLE001 — reported below, with the thread's answers
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=read, args=(sess,)) for sess in sessions]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    assert len(got) == 20 and all(rows == want for rows in got)
    moved = _moved(before)
    assert sum(moved.values()) == _mapped_calls() - calls and moved.get("built", 0) <= 4
    # one stack a lane, whoever built it: the published task reads the very arrays the LRU holds
    stacks = _stacks(lru)
    cache = tpu_engine.cache_for(db.store)
    (task,) = cache._resolved.values()
    lanes = [a for kernel, args, _ in task.calls if kernel.m > 1 for a in jax.tree.leaves(args[0])]
    assert lanes and all(any(a is v for v, _ in stacks.values()) for a in lanes)
    assert lru.total == sum(nb for _, nb in lru._entries.values())


# -- what the span and the counter say -------------------------------------------------------


def _inputs(s, text, tmp_path):
    """(rows, the stats of the statement's ``tidb:exec.inputs`` spans that speak of stacks)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rows = s.query(text)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    name = tracing.PREFIX + "exec.inputs"
    spans = [dict(ev.stats) for plane in ProfileData.from_file(path).planes for line in plane.lines
             for ev in line.events if ev.name == name]
    return rows, [sp for sp in spans if any(k.startswith("stacks_") for k in sp)]


def test_the_inputs_span_and_the_counter_count_kept_and_built(served, lru, tmp_path):
    db, s = served
    want = _host(s, NO_NULLS)
    seen = []
    for i, forget in enumerate((True, False, True)):  # built (a miss, no stack yet); kept (a hit); kept (a miss)
        if forget:
            _forget(db)
        before, calls = _lane_counts(), _mapped_calls()
        rows, spans = _inputs(s, NO_NULLS, tmp_path / str(i))
        n = _mapped_calls() - calls
        assert rows == want and n >= 1
        kept = sum(int(sp.get("stacks_kept", 0)) for sp in spans)
        built = sum(int(sp.get("stacks_built", 0)) for sp in spans)
        assert _moved(before) == {how: k for how, k in (("kept", kept), ("built", built)) if k}
        seen.append((kept, built, n))
    (n,) = {n for _, _, n in seen}
    assert seen == [(0, n, n), (n, 0, n), (n, 0, n)]
