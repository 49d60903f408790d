"""A read beside a writer EXTENDS the cached delta overlay by what was
committed since (colcache._extend_delta) instead of point-reading every touched
row again. The extended overlay must be, field for field, what the rebuild
(``_build_delta`` + ``_decode_delta_slots``) gives at the same ``read_ts``:
that rebuild is the reference of every test here.
"""

import dataclasses

import numpy as np
import pytest

import tidb_tpu
from tidb_tpu import config as _config
from tidb_tpu.copr import colcache
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.kv import tablecodec
from tidb_tpu.kv.memstore import OP_PUT, Mutation
from tidb_tpu.kv.rowcodec import RowSchema, encode_row
from tidb_tpu.utils import metrics as _m

N = 600
CAP = 512
SLOTS = [1, 2, 3]  # g VARCHAR, v BIGINT, f DOUBLE; id is the handle
STRINGS = ["aa", "bb", "cc", "dd", "ee", "zz"]


class Ctx:
    def __init__(self, db):
        self.db = db
        self.store = db.store
        self.t = db.catalog.table("test", "d")
        self.tid = self.t.id
        self.schema = RowSchema(self.t.storage_schema)
        self.cache = colcache.cache_for(db.store)
        self.s = db.session()

    @property
    def region(self):
        region, _ = next(iter(self.store.pd.regions_in_ranges([tablecodec.record_range(self.tid)])))
        return region

    def split(self, read_ts=None, slots=SLOTS):
        ts = self.store.current_ts() if read_ts is None else read_ts
        return self.cache.get_split(self.region, self.tid, self.schema, slots, ts)

    def rebuilt(self, like, slots=SLOTS):
        """The reference: every row of ``like`` read from the store again."""
        d = self.cache._build_delta(self.region, self.tid, like.handles, like.built_ts, like.data_version, True)
        self.cache._decode_delta_slots(d, self.tid, self.schema, slots)
        return d

    def cached(self):
        return self.cache._deltas.get((self.region.region_id, self.tid))


@pytest.fixture()
def ctx():
    old = _config.current()
    _config.set_current(
        dataclasses.replace(old, device_delta_cap=CAP, device_delta_merge_rows=1 << 20, device_delta_min_rows=1)
    )
    db = tidb_tpu.open(region_split_keys=1 << 62)
    db.execute("CREATE TABLE d (id BIGINT PRIMARY KEY, g VARCHAR(2), v BIGINT, f DOUBLE)")
    rng = np.random.default_rng(7)
    bulk_load(
        db,
        "d",
        [
            np.arange(N, dtype=np.int64),
            np.array([b"aa", b"bb", b"cc"], dtype="S2")[rng.integers(0, 3, N)],
            rng.integers(0, 100, N).astype(np.int64),
            rng.random(N),
        ],
    )
    c = Ctx(db)
    base, delta = c.split()  # the base is built BEFORE the first write
    assert delta is None and base.n == N
    yield c
    _config.set_current(old)


def _hows():
    return {h: _m.DELTA_OVERLAY.get(how=h) for h in ("reused", "extended", "rebuilt")}


def _moved(before):
    return {h: n - before[h] for h, n in _hows().items() if n != before[h]}


def assert_same(got, want, slots=SLOTS):
    assert np.array_equal(got.handles, want.handles)
    assert np.array_equal(got.tomb, want.tomb)
    assert got.complete == want.complete and got.n_put == want.n_put
    assert np.array_equal(got._put_rows, want._put_rows)
    for s in slots:
        gd, gv = got.cols[s]
        wd, wv = want.cols[s]
        assert gd.dtype == wd.dtype and np.array_equal(gd, wd), s
        assert np.array_equal(gv, wv), s
        assert got.minmax(s) == want.minmax(s), s


def _value(rng, kind):
    if rng.random() < 0.15:
        return "NULL"
    if kind == "g":
        return f"'{STRINGS[rng.integers(0, len(STRINGS))]}'"
    if kind == "v":
        return str(int(rng.integers(-50, 150)))
    return repr(round(float(rng.random()), 6))


@pytest.mark.parametrize("seed", [11, 12, 13, 14, 15, 16])
def test_extended_overlay_equals_the_rebuilt_one_over_random_dml(ctx, seed):
    rng = np.random.default_rng(seed)
    live = set(range(N))
    touched: list[int] = []
    next_id = 10_000
    extended = tombstones = 0
    for step in range(40):
        for _ in range(int(rng.integers(1, 4))):  # 1-3 commits between two reads
            op = rng.choice(["insert", "update", "delete", "again", "reinsert", "range"])
            if op == "insert":
                ids = list(range(next_id, next_id + int(rng.integers(1, 4))))
                next_id += len(ids)
                rows = ",".join(f"({i},{_value(rng, 'g')},{_value(rng, 'v')},{_value(rng, 'f')})" for i in ids)
                ctx.s.execute(f"INSERT INTO d VALUES {rows}")
                live.update(ids)
                touched += ids
            elif op == "reinsert" and (dead := [h for h in touched if h not in live]):
                h = dead[int(rng.integers(0, len(dead)))]
                ctx.s.execute(f"INSERT INTO d VALUES ({h},{_value(rng, 'g')},{_value(rng, 'v')},{_value(rng, 'f')})")
                live.add(h)
            elif op == "range":
                lo = int(rng.integers(0, N - 5))
                ctx.s.execute(f"UPDATE d SET v = {_value(rng, 'v')} WHERE id BETWEEN {lo} AND {lo + 3}")
                touched += [h for h in range(lo, lo + 4) if h in live]
            else:
                pool = [h for h in touched if h in live] if op == "again" and touched else sorted(live)
                if not pool:
                    continue
                h = pool[int(rng.integers(0, len(pool)))]
                if op == "delete":
                    ctx.s.execute(f"DELETE FROM d WHERE id = {h}")
                    live.discard(h)
                else:
                    col = ["g", "v", "f"][int(rng.integers(0, 3))]
                    ctx.s.execute(f"UPDATE d SET {col} = {_value(rng, col)} WHERE id = {h}")
                touched.append(h)
        before = _hows()
        slots = SLOTS if step else [2]  # the first overlay holds one lane: the others are decoded from the carried rows
        _base, delta = ctx.split(slots=slots)
        assert delta is not None and delta.complete
        assert_same(delta, ctx.rebuilt(delta, slots), slots)
        how = _moved(before)
        assert sum(how.values()) == 1, how
        extended += how.get("extended", 0)
        tombstones += int(delta.tomb.sum())
        assert ctx.cached() is delta
    # the first read has nothing to start from and a buffer half dead is compacted by a rebuild: the rest extend
    assert extended >= 30, extended
    # (an UPDATE that changes nothing writes nothing)
    assert set(delta.handles.tolist()) <= set(touched) and delta.n > 60 and tombstones and not delta.tomb.all()
    assert ctx.s.query("SELECT COUNT(*) FROM d")[0][0] == len(live)


def test_a_read_below_the_cached_overlay_rebuilds_and_leaves_it_alone(ctx):
    ctx.s.execute("UPDATE d SET v = 1000 WHERE id = 3")
    ts_between = ctx.store.current_ts()
    ctx.s.execute("UPDATE d SET v = 2000 WHERE id = 3")
    ctx.s.execute("INSERT INTO d VALUES (9001, 'aa', 5, 0.5)")
    _b, newest = ctx.split()
    kept = (newest.handles.copy(), newest.tomb.copy(), {s: (d.copy(), v.copy()) for s, (d, v) in newest.cols.items()})
    before = _hows()
    _b, older = ctx.split(read_ts=ts_between)
    assert _moved(before) == {"rebuilt": 1}
    assert older is not newest and older.built_ts == ts_between
    assert list(older.handles) == [3] and older.cols[2][0][0] == 1000
    assert not older.complete  # two commits lie above it: it is nobody's start
    assert ctx.cached() is newest
    assert np.array_equal(newest.handles, kept[0]) and np.array_equal(newest.tomb, kept[1])
    for s, (d, v) in kept[2].items():
        assert np.array_equal(newest.cols[s][0], d) and np.array_equal(newest.cols[s][1], v)
    before = _hows()
    _b, again = ctx.split()
    assert again is newest and _moved(before) == {"reused": 1}


def _row(ctx, g, v, f):
    return encode_row(ctx.schema, [0, g, v, f])


def _prewrite_two(ctx, h_primary, h_secondary, v):
    """A transaction over two rows whose primary is committed and whose
    secondary is still locked: the secondary's key."""
    store = ctx.store
    kp, ks = tablecodec.record_key(ctx.tid, h_primary), tablecodec.record_key(ctx.tid, h_secondary)
    start_ts = store.tso.ts()
    muts = [Mutation(OP_PUT, kp, _row(ctx, b"pp", v, 0.25)), Mutation(OP_PUT, ks, _row(ctx, b"ss", v, 0.75))]
    store.prewrite(muts, kp, start_ts)
    commit_ts = store.tso.ts()
    store.commit([kp], start_ts, commit_ts)
    return ks


@pytest.mark.parametrize("held", [True, False], ids=["a_row_the_overlay_holds", "a_row_committed_since"])
def test_a_key_locked_at_read_time_is_resolved_then_read(ctx, held):
    ctx.s.execute("UPDATE d SET v = 1 WHERE id IN (7, 8)")
    _b, first = ctx.split()
    assert list(first.handles) == [7, 8]
    if not held:
        ctx.s.execute("UPDATE d SET v = 2 WHERE id = 8")  # in the log since `first`: read again whatever its lock says
    ks = _prewrite_two(ctx, 7, 8, 77)
    assert ks in ctx.store._locks
    before = _hows()
    _b, second = ctx.split()
    assert _moved(before) == {"extended": 1}
    assert ks not in ctx.store._locks  # rolled forward by the read, as the rebuild's get_many does
    assert list(second.cols[2][0]) == [77, 77]
    _b, third = ctx.split()  # the roll-forward is a commit of its own in the log
    assert_same(third, ctx.rebuilt(third))
    assert list(third.cols[2][0]) == [77, 77] and list(first.cols[2][0]) == [1, 1]


def test_a_commit_stamped_below_the_overlay_but_applied_after_it_is_not_missed(ctx):
    ctx.s.execute("UPDATE d SET v = 1 WHERE id = 7")
    store = ctx.store
    ka, kb = tablecodec.record_key(ctx.tid, 9500), tablecodec.record_key(ctx.tid, 9501)
    start_ts = store.tso.ts()
    store.prewrite([Mutation(OP_PUT, ka, _row(ctx, b"nn", 5, 0.5)), Mutation(OP_PUT, kb, _row(ctx, b"oo", 6, 0.5))], ka, start_ts)
    commit_ts = store.tso.ts()  # decided at this stamp; applied to neither row yet
    _b, first = ctx.split()  # neither row is in the log, so the read does not meet their locks
    assert first.built_ts > commit_ts and first.complete and list(first.handles) == [7]
    store.commit([ka, kb], start_ts, commit_ts)  # applied now, stamped below first.built_ts
    before = _hows()
    _b, second = ctx.split()
    assert _moved(before) == {"extended": 1}
    assert list(second.handles) == [7, 9500, 9501]
    assert_same(second, ctx.rebuilt(second))
    assert list(second.cols[2][0]) == [1, 5, 6]


def test_a_merge_between_two_reads_means_a_rebuild_over_the_new_base(ctx):
    ctx.s.execute("UPDATE d SET v = 1 WHERE id < 4")
    _b0, first = ctx.split()
    assert ctx.cache.merge_pending(threshold=1) == 1 and ctx.cached() is None
    ctx.s.execute("UPDATE d SET v = 2 WHERE id = 2")
    before = _hows()
    base, second = ctx.split()
    assert _moved(before) == {"rebuilt": 1}
    assert base is not _b0 and second._base is base and list(second.handles) == [2]
    assert_same(second, ctx.rebuilt(second))
    # an overlay left over from the old base is nobody's start either
    ctx.cache._deltas[(ctx.region.region_id, ctx.tid)] = first
    ctx.s.execute("UPDATE d SET v = 3 WHERE id = 5")
    before = _hows()
    _b, third = ctx.split()
    assert _moved(before) == {"rebuilt": 1} and list(third.handles) == [2, 5]
    assert_same(third, ctx.rebuilt(third))


def test_a_statement_holding_the_old_overlay_reads_it_unchanged(ctx):
    ctx.s.execute("UPDATE d SET v = 1, g = 'dd' WHERE id IN (10, 11, 12)")
    _b, held = ctx.split()
    frozen = dataclasses.replace(
        held, handles=held.handles.copy(), tomb=held.tomb.copy(), cols={s: (d.copy(), v.copy()) for s, (d, v) in held.cols.items()}
    )
    buf, starts, put_rows = held._buf, held._starts.copy(), held._put_rows.copy()
    ctx.s.execute("UPDATE d SET v = 2 WHERE id = 11")  # in place
    ctx.s.execute("DELETE FROM d WHERE id = 12")  # a tombstone where a row was
    ctx.s.execute("INSERT INTO d VALUES (5, 'ee', 9, 0.5) ON DUPLICATE KEY UPDATE v = 9")
    ctx.s.execute("INSERT INTO d VALUES (9000, 'ee', 9, 0.5)")  # appended
    _b, newer = ctx.split()
    assert newer is not held and list(newer.handles) == [5, 10, 11, 12, 9000]
    assert_same(newer, ctx.rebuilt(newer))
    assert_same(held, frozen)
    assert held._buf is buf and np.array_equal(held._starts, starts) and np.array_equal(held._put_rows, put_rows)
    for s in SLOTS:
        assert not np.shares_memory(newer.cols[s][0], held.cols[s][0])
    # and the held one still decodes a lane it had not decoded, from its own rows
    only_v = dataclasses.replace(held, cols={2: held.cols[2]}, _minmax={})
    ctx.cache._decode_delta_slots(only_v, ctx.tid, ctx.schema, [1, 3])
    assert_same(only_v, frozen)


def test_string_lanes_carry_their_dictionary_codes(ctx):
    ctx.s.execute("UPDATE d SET g = 'zz' WHERE id = 1")  # a string the dictionary has not seen
    ctx.s.execute("UPDATE d SET g = NULL WHERE id = 2")
    _b, first = ctx.split()
    dic = ctx.cache.dictionary(ctx.tid, 1)
    zz = dic.encode(b"zz")
    assert list(first.cols[1][0]) == [zz, 0] and list(first.cols[1][1]) == [True, False]
    ctx.s.execute("INSERT INTO d VALUES (8000, 'yy', 1, 0.5), (8001, 'zz', 1, 0.5)")
    before = _hows()
    _b, second = ctx.split()
    assert _moved(before) == {"extended": 1}
    assert list(second.cols[1][0]) == [zz, 0, dic.encode(b"yy"), zz]
    assert_same(second, ctx.rebuilt(second))
    # a compacted dictionary remaps the cached overlay's codes; the next one starts from the remapped ones
    ctx.cache.ensure_sorted_dict(ctx.tid, 1)
    ctx.s.execute("UPDATE d SET g = 'ab' WHERE id = 3")
    before = _hows()
    _b, third = ctx.split()
    assert _moved(before) == {"extended": 1}
    assert_same(third, ctx.rebuilt(third))
    values = dic.values_array()
    assert [values[c] for c in third.cols[1][0][third.cols[1][1]]] == [b"zz", b"ab", b"yy", b"zz"]


def test_a_dictionary_compacted_while_codes_are_carried_means_a_rebuild(ctx, monkeypatch):
    ctx.s.execute("UPDATE d SET g = 'zz' WHERE id = 1")
    ctx.s.execute("UPDATE d SET g = 'ab' WHERE id = 2")  # codes out of order: the dictionary is not sorted
    _b, first = ctx.split()
    ctx.s.execute("UPDATE d SET g = 'ee' WHERE id = 3")
    real = ctx.cache._extend_delta

    def raced(*a, **k):
        new = real(*a, **k)
        ctx.cache.ensure_sorted_dict(ctx.tid, 1)  # lands after the old codes were copied, before the overlay is installed
        return new

    monkeypatch.setattr(ctx.cache, "_extend_delta", raced)
    before = _hows()
    _b, second = ctx.split()
    assert _moved(before) == {"rebuilt": 1}
    assert ctx.cached() is second
    assert_same(second, ctx.rebuilt(second))
    values = ctx.cache.dictionary(ctx.tid, 1).values_array()
    assert [values[c] for c in second.cols[1][0]] == [b"zz", b"ab", b"ee"]


def test_a_buffer_with_more_dead_rows_than_rows_is_compacted_by_a_rebuild(ctx):
    ctx.s.execute("UPDATE d SET v = 0 WHERE id IN (30, 31)")
    ctx.split()
    hows = []
    for i in range(1, 9):
        ctx.s.execute(f"UPDATE d SET v = {i} WHERE id = 30")
        before = _hows()
        _b, d = ctx.split()
        hows += list(_moved(before))
        assert_same(d, ctx.rebuilt(d))
        assert d._dead <= d.n and len(d._buf) <= (d.n + d._dead) * 64
    assert hows.count("rebuilt") >= 2 and hows.count("extended") >= 4, hows


def test_counter_sidecar_and_explain_say_how_the_overlay_was_obtained(ctx):
    s = ctx.s
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    q = "SELECT COUNT(*), SUM(v) FROM d"
    s.execute("UPDATE d SET v = v + 1 WHERE id < 20")
    before = _hows()
    txt = "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + q))
    assert "delta_rows: 20, delta_read: 20" in txt, txt
    assert _moved(before) == {"rebuilt": 1}
    s.execute("INSERT INTO d VALUES (7000, 'aa', 1, 0.5), (7001, 'aa', 1, 0.5)")
    s.execute("UPDATE d SET v = 0 WHERE id = 3")
    before = _hows()
    txt = "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + q))
    assert "delta_rows: 22, delta_read: 3" in txt, txt
    assert _moved(before) == {"extended": 1}
    before = _hows()
    txt = "\n".join(str(r) for r in s.query("EXPLAIN ANALYZE " + q))
    assert "delta_rows: 22, delta_read: 0" in txt, txt
    assert _moved(before) == {"reused": 1}
    assert 'tidb_tpu_delta_overlay_total{how="extended"}' in _m.REGISTRY.render()
    host = s.query(q)
    s.execute("SET tidb_isolation_read_engines = 'host'")
    assert s.query(q) == host


def test_the_bind_span_says_how_many_rows_it_read_beside_how_many_it_holds(ctx, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    from tidb_tpu.utils import tracing

    s = ctx.s
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    q = "SELECT COUNT(*), SUM(v) FROM d"
    s.execute("UPDATE d SET v = v + 1 WHERE id < 20")
    s.query(q)  # compiled outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        s.execute("INSERT INTO d VALUES (7000, 'aa', 1, 0.5), (7001, 'aa', 1, 0.5)")
        s.query(q)
        s.query(q)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    binds = [
        dict(ev.stats)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name == tracing.PREFIX + "exec.bind"
    ]
    through_a_delta = [(int(b["delta_rows"]), int(b["delta_read"])) for b in binds if "delta_rows" in b]
    assert through_a_delta == [(22, 2), (22, 0)]  # extended by the two rows committed since, then reused


def test_readers_beside_writers_extend_one_cached_overlay_and_stay_exact(ctx):
    """More threads than cores over one region's overlay: two writers (single
    rows, and statements of several rows, whose secondaries commit after their
    primary) and four readers at their own snapshots. Every overlay a reader
    was given must equal the rebuild at ITS read_ts, whatever the others
    installed meanwhile."""
    import sys
    import threading
    import time

    stop = threading.Event()
    errors: list = []
    seen: list = []

    def writer(k):
        try:
            s = ctx.db.session()
            i = 0
            while not stop.is_set():
                i += 1
                if i % 3 == 0:
                    s.execute(f"INSERT INTO d VALUES ({20_000 + k * 10_000 + i}, 'aa', {i}, 0.5)")
                elif i % 3 == 1:
                    s.execute(f"UPDATE d SET v = {i} WHERE id BETWEEN {k * 50} AND {k * 50 + 4}")
                else:
                    s.execute(f"DELETE FROM d WHERE id = {100 + k * 200 + i % 150}")
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                _b, delta = ctx.split()
                if delta is not None:
                    seen.append(delta)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    before = _hows()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    threads = [threading.Thread(target=writer, args=(k,)) for k in range(2)] + [threading.Thread(target=reader) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    assert len(seen) > 20 and _moved(before).get("extended", 0) > 10
    for delta in seen[:: max(1, len(seen) // 60)] + seen[-3:]:
        want = ctx.rebuilt(delta)
        want.complete = delta.complete  # a commit beside the read makes it incomplete; the rows are judged all the same
        assert_same(delta, want)
