"""The bulk load without a Python step a row (ISSUE 35): ``Dictionary.encode_many``
and ``_ChangeLog.note_many`` must leave EXACTLY what the row-at-a-time loops
left. The plain reference is those loops, kept HERE: ``_ref_encode`` is a
straight transcription of ``Dictionary.encode`` as the loader called it (once
a distinct string of the batch, in sorted order) and ``_ref_note`` of
``_ChangeLog.note`` (once a handle). Every scenario loads two stores, one
through the program and one with the reference put in the program's place,
and compares all of their state: the dictionaries' values in order and their
flags, every block's code lanes and validity, every (region, table) change
log, and the answers of both engines."""

import numpy as np
import pytest

import tidb_tpu
from tidb_tpu.copr.colcache import cache_for
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.kv import memstore
from tidb_tpu.utils import chunk, metrics
from tidb_tpu.utils.chunk import Dictionary


# -- the per-row reference -------------------------------------------------------


def _ref_encode(dic, value: bytes) -> int:
    code = dic._index.get(value)
    if code is not None:
        return code
    code = len(dic._values)
    dic._values.append(value)
    dic._index[value] = code
    if dic.sorted and code > 0 and dic._values[code - 1] > value:
        dic.sorted = False
    if code > 0:
        dic.ci_sorted = False
    return code


def _ref_encode_batch(dic, values: np.ndarray) -> np.ndarray:
    uniq, inv = np.unique(values, return_inverse=True)
    code_of = np.fromiter((_ref_encode(dic, bytes(u)) for u in uniq), dtype=np.int32, count=len(uniq))
    return code_of[inv.reshape(-1)].astype(np.int32, copy=False)


def _ref_note(log, ts: int, handle: int, op: str) -> None:
    log.lo = handle if log.lo is None else min(log.lo, handle)
    log.hi = handle if log.hi is None else max(log.hi, handle)
    if log.lost:
        log.lost_max_ts = max(log.lost_max_ts, ts)
        return
    if len(log.items) >= memstore._CHANGE_ITEMS_CAP:
        log.items.clear()
        log.lost = True
        log.lost_max_ts = ts
        return
    log.items.append((ts, handle, op))


def _ref_note_batch(log, ts: int, handles: np.ndarray, op: str) -> None:
    for h in handles:
        _ref_note(log, ts, int(h), op)


# -- what a load leaves ------------------------------------------------------------


def _state(db):
    store = db.store
    stamps = {0}
    for blocks in store._stable.values():
        stamps.update(b.commit_ts for b in blocks)
    for log in store._changes.values():
        stamps.update(ts for ts, _, _ in log.items)
        stamps.add(log.lost_max_ts)
    nth = {ts: i for i, ts in enumerate(sorted(stamps))}  # a store's clock is the wall's: compare the order
    dicts = {key: (list(d._values), d.sorted, d.ci_sorted, dict(d._index)) for key, d in cache_for(store)._dicts.items()}
    blocks = {
        tid: [(b.handles.tolist(), nth[b.commit_ts], {pos: (str(d.dtype), d.tolist(), v.tolist()) for pos, (d, v) in sorted(b.cols.items())})
              for b in bs]
        for tid, bs in store._stable.items()
    }
    logs = {
        key: ([(nth[ts], h, op, type(h)) for ts, h, op in log.items], log.lo, log.hi, log.lost, nth[log.lost_max_ts])
        for key, log in store._changes.items()
    }
    regions = [(r.region_id, r.start, r.end) for r in store.regions()]
    return {"dicts": dicts, "blocks": blocks, "logs": logs, "regions": regions}


def _answers(db, texts):
    s = db.session()
    out = []
    for engine in ("host", "tpu"):
        s.execute(f"SET tidb_isolation_read_engines = '{engine}'")
        out.append([s.query(t) for t in texts])
    assert out[0] == out[1]
    return out[0]


def _both(scenario, monkeypatch, **open_kw):
    """(the program's state and answers, the reference's)."""
    got = []
    for reference in (False, True):
        with monkeypatch.context() as m:
            if reference:
                m.setattr(Dictionary, "encode_many", _ref_encode_batch)
                m.setattr(memstore._ChangeLog, "note_many", _ref_note_batch)
            db = tidb_tpu.open(**open_kw)
            texts = scenario(db)
            got.append((_state(db), _answers(db, texts)))
    return got


# -- the scenarios --------------------------------------------------------------------

WORDS = np.array([f"w{i:04d}".encode() for i in range(300)], dtype="S9")
T = "CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT, s VARCHAR(20), f CHAR(1))"
TEXTS = ["SELECT s, COUNT(*), SUM(v) FROM t GROUP BY s ORDER BY s", "SELECT f, COUNT(*), MIN(id), MAX(id) FROM t GROUP BY f ORDER BY f",
         "SELECT COUNT(*), SUM(v) FROM t WHERE s >= 'w0100'"]


def _load(db, ids, strings, flags=None, table="t"):
    ids = np.asarray(ids, dtype=np.int64)
    flags = np.array([b"A", b"N", b"R"])[ids % 3] if flags is None else flags
    bulk_load(db, table, [ids, ids * 7 % 1000, strings, flags])


def repeats_across_batches(db):
    db.execute(T)
    rng = np.random.default_rng(7)
    for lo in range(0, 6000, 1500):
        _load(db, np.arange(lo, lo + 1500), WORDS[rng.integers(0, 120, 1500)])
    return TEXTS


def only_new_strings(db):
    db.execute(T)
    for lo in range(0, 4000, 1000):
        ids = np.arange(lo, lo + 1000)
        _load(db, ids, np.array([f"name#{i:09d}".encode() for i in ids], dtype="S20"))
    return TEXTS


def strings_out_of_order(db):
    db.execute(T)
    _load(db, np.arange(0, 500), WORDS[200 + np.arange(500) % 50])
    assert cache_for(db.store).dictionary(db.catalog.table("test", "t").id, 2).sorted
    _load(db, np.arange(500, 1000), WORDS[np.arange(500) % 40])  # sort before every known one
    assert not cache_for(db.store).dictionary(db.catalog.table("test", "t").id, 2).sorted
    _load(db, np.arange(1000, 1500), WORDS[(np.arange(500) * 13) % 300].astype("S12"))  # known and new mixed, a wider array
    return TEXTS


def nulls_in_an_object_column(db):
    db.execute(T)
    for lo in range(0, 900, 300):
        ids = np.arange(lo, lo + 300)
        strings = [None if i % 5 == 0 else f"o{i % 37}" for i in ids]
        bulk_load(db, "t", [ids, ids * 7 % 1000, strings, [None if i % 11 == 0 else "AB"[i % 2] for i in ids]])
    _load(db, np.arange(900, 1200), np.array([f"o{i % 41}".encode() for i in range(300)], dtype="S4"))  # an array after the lists
    return TEXTS + ["SELECT COUNT(*), COUNT(s), COUNT(f) FROM t"]


def across_a_region_split(db):
    db.execute(T)
    rng = np.random.default_rng(11)
    for lo in range(0, 5400, 600):  # region_split_keys 1000: regions split as they grow, batches straddle them
        _load(db, np.arange(lo, lo + 600), WORDS[rng.integers(0, 300, 600)])
    _load(db, np.arange(20000, 19400, -1), WORDS[rng.integers(0, 300, 600)])  # handles in descending order
    assert len(db.store.regions()) >= 5
    return TEXTS


def past_the_change_log_cap(db):
    db.execute(T)
    cap = memstore._CHANGE_ITEMS_CAP
    at = 0
    for n in (cap - 10, 10, 1, 500, cap + 1, 20):  # fills it exactly; the handle that finds it full; lost; a span; lost still
        _load(db, np.arange(at, at + n), WORDS[np.arange(n) % 7])
        at += n
    (log,) = [lg for (_, tid), lg in db.store._changes.items() if tid == db.catalog.table("test", "t").id]
    assert log.lost and not log.items and (log.lo, log.hi) == (0, at - 1)
    return TEXTS[:2]


def the_cap_is_met_exactly(db):
    db.execute(T)
    cap = memstore._CHANGE_ITEMS_CAP
    _load(db, np.arange(0, cap - 5), WORDS[np.arange(cap - 5) % 3])
    _load(db, np.arange(cap - 5, cap), WORDS[:5])
    (log,) = db.store._changes.values()
    assert not log.lost and len(log.items) == cap
    return TEXTS[:2]


def a_partitioned_table(db):
    db.execute("CREATE TABLE t (id BIGINT, v BIGINT, s VARCHAR(20), f CHAR(1)) PARTITION BY HASH (id) PARTITIONS 3")
    rng = np.random.default_rng(3)
    for lo in range(0, 3000, 750):
        _load(db, np.arange(lo, lo + 750), WORDS[rng.integers(0, 200, 750)])
    return TEXTS


SCENARIOS = {
    "repeats_across_batches": (repeats_across_batches, {}),
    "only_new_strings": (only_new_strings, {}),
    "strings_out_of_order": (strings_out_of_order, {}),
    "nulls_in_an_object_column": (nulls_in_an_object_column, {}),
    "across_a_region_split": (across_a_region_split, {"region_split_keys": 1000}),
    "past_the_change_log_cap": (past_the_change_log_cap, {"region_split_keys": 10_000_000}),
    "the_cap_is_met_exactly": (the_cap_is_met_exactly, {"region_split_keys": 10_000_000}),
    "a_partitioned_table": (a_partitioned_table, {}),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_the_load_leaves_what_the_row_at_a_time_loops_left(name, monkeypatch):
    scenario, open_kw = SCENARIOS[name]
    (state, answers), (ref_state, ref_answers) = _both(scenario, monkeypatch, **open_kw)
    for part in ("regions", "dicts", "blocks", "logs"):
        assert state[part] == ref_state[part], part
    assert state["dicts"] and state["blocks"] and state["logs"]
    assert answers == ref_answers and any(answers[0])


# -- the dictionary alone -------------------------------------------------------------


def _same(dic, ref):
    assert (dic._values, dic._index, dic.sorted, dic.ci_sorted) == (ref._values, ref._index, ref.sorted, ref.ci_sorted)
    assert all(type(v) is bytes for v in dic._values)


def _batches(seed, n=6, rows=400, pool=150, width=9):
    rng = np.random.default_rng(seed)
    return [WORDS[rng.integers(0, pool, rows)].astype(f"S{width}") for _ in range(n)]


def _plain(dic, ref, monkeypatch):
    for b in _batches(1):
        assert dic.encode_many(b).tolist() == _ref_encode_batch(ref, b).tolist()


def _every_hash_collides(dic, ref, monkeypatch):
    monkeypatch.setattr(chunk, "_hash_rows", lambda a: np.zeros(len(a), dtype=np.uint64))
    _plain(dic, ref, monkeypatch)


def _half_the_hashes_collide(dic, ref, monkeypatch):
    real = chunk._hash_rows
    monkeypatch.setattr(chunk, "_hash_rows", lambda a: real(a) >> np.uint64(58))
    _plain(dic, ref, monkeypatch)


def _singles_between_batches(dic, ref, monkeypatch):
    for k, b in enumerate(_batches(2)):
        assert dic.encode_many(b).tolist() == _ref_encode_batch(ref, b).tolist()
        assert dic.encode(f"single{k}") == _ref_encode(ref, f"single{k}".encode())
        assert dic.encode_many(np.array([f"single{k}".encode()], dtype="S12")).tolist() == [ref._index[f"single{k}".encode()]]


def _compacted_between_batches(dic, ref, monkeypatch):
    for k, b in enumerate(_batches(3, pool=300)):
        assert dic.encode_many(b).tolist() == _ref_encode_batch(ref, b).tolist()
        if k % 2:
            assert dic.compact().tolist() == ref.compact().tolist()
            assert dic.sorted


def _widths_that_change(dic, ref, monkeypatch):
    for k, b in enumerate(_batches(4)):
        b = b.astype(f"S{9 + 7 * (k % 3)}")
        assert dic.encode_many(b).tolist() == _ref_encode_batch(ref, b).tolist()
    wide = np.array([b"w0001" + b"x" * 60, b"w0001", b"", b"w0001" + b"x" * 59], dtype="S70")
    assert dic.encode_many(wide).tolist() == _ref_encode_batch(ref, wide).tolist()
    assert dic.encode_many(wide.astype("S80")).tolist() == [ref._index[bytes(v)] for v in wide]


def _values_no_array_can_hold(dic, ref, monkeypatch):
    odd = np.array([b"a\x00", b"a", b"a\x00\x00", b"\x00", b"", b"b\x00c"], dtype=object)  # from the object path: NULs at the end
    assert dic.encode_many(odd).tolist() == _ref_encode_batch(ref, odd).tolist()
    fixed = np.array([b"a", b"", b"b\x00c", b"b"], dtype="S4")
    for _ in range(3):  # before and after the index holds them
        assert dic.encode_many(fixed).tolist() == _ref_encode_batch(ref, fixed).tolist()
    assert [dic._values[c] for c in dic.encode_many(fixed)] == [b"a", b"", b"b\x00c", b"b"]


def _an_empty_batch(dic, ref, monkeypatch):
    assert dic.encode_many(np.empty(0, dtype="S5")).tolist() == []
    assert dic.encode_many(np.empty(0, dtype=object)).tolist() == []
    _plain(dic, ref, monkeypatch)


def _one_long_column(dic, ref, monkeypatch):
    for b in _batches(5, n=4, rows=30_000, pool=300):  # more rows than the index has values: re-indexed at once
        assert np.array_equal(dic.encode_many(b), _ref_encode_batch(ref, b))
    _, n, keys, codes, vals = dic._np
    assert n == len(dic) == len(keys) == len(codes) == len(vals)


DICTIONARY = [_plain, _every_hash_collides, _half_the_hashes_collide, _singles_between_batches, _compacted_between_batches,
              _widths_that_change, _values_no_array_can_hold, _an_empty_batch, _one_long_column]


@pytest.mark.parametrize("case", DICTIONARY, ids=lambda f: f.__name__.strip("_"))
def test_encode_many_is_encode_over_the_sorted_distinct_values(case, monkeypatch):
    dic, ref = Dictionary(), Dictionary()
    case(dic, ref, monkeypatch)
    _same(dic, ref)
    assert len(dic) > 0


def test_the_index_catches_up_with_values_it_has_not_seen():
    dic = Dictionary()
    first = dic.encode_many(WORDS[:100])
    assert first.tolist() == list(range(100)) and dic._np[1] == 0  # built before the batch: it held nothing yet
    assert dic.encode_many(WORDS[:100]).tolist() == first.tolist() and dic._np[1] == 100
    before = dic._np
    assert dic.encode_many(WORDS[50:100]).tolist() == first[50:].tolist() and dic._np is before  # nothing new: left as it is


def test_threads_that_share_a_dictionary_lose_no_value():
    """Cop and partition workers share a table's dictionaries: batches and
    single values from more threads than cores, the interpreter switching
    often. Every code a thread was given still decodes to its value, and the
    dictionary holds each value once."""
    import sys
    import threading

    dic = Dictionary()
    wrong: list = []

    def work(k):
        rng = np.random.default_rng(k)
        for i in range(40):
            batch = np.array([f"v{j:05d}".encode() for j in rng.integers(0, 3000, 200)], dtype="S8")
            codes = dic.encode_many(batch)
            single = f"t{k}.{i}".encode()
            c = dic.encode(single)
            if [dic._values[int(x)] for x in codes] != batch.tolist() or dic._values[c] != single:
                wrong.append((k, i))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not wrong
    assert len(set(dic._values)) == len(dic._values) == len(dic._index)
    assert all(dic._index[v] == i for i, v in enumerate(dic._values))


# -- it says what it did ------------------------------------------------------------------


def test_the_loader_counts_rows_and_seconds_and_writes_its_span(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    from tidb_tpu.utils import tracing

    rows = metrics.BULK_LOAD_ROWS.get(table="t")
    seconds = {p: metrics.BULK_LOAD_SECONDS.get(phase=p) for p in ("encode", "ingest")}
    db = tidb_tpu.open(region_split_keys=1000)
    db.execute(T)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for lo in range(0, 1800, 600):
            _load(db, 2 * np.arange(lo, lo + 600), WORDS[np.arange(lo, lo + 600) % 250])
        _load(db, 2 * np.arange(0, 1800, 3) + 1, WORDS[np.arange(600) % 300])  # between the rows of every region
    finally:
        jax.profiler.stop_trace()
    assert metrics.BULK_LOAD_ROWS.get(table="t") == rows + 2400
    assert all(metrics.BULK_LOAD_SECONDS.get(phase=p) > seconds[p] for p in seconds)
    page = metrics.REGISTRY.render()
    assert 'tidb_tpu_bulk_load_rows_total{table="t"}' in page and 'tidb_tpu_bulk_load_seconds_total{phase="encode"}' in page
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans = [dict(ev.stats) for plane in ProfileData.from_file(path).planes for line in plane.lines for ev in line.events
             if ev.name == tracing.PREFIX + "load.ingest"]
    assert [(s["table"], int(s["rows"]), int(s["strings"])) for s in spans] == [("t", 600, 2)] * 4
    assert [int(s["dict_new"]) for s in spans] == [250 + 3, 0, 0, 50]
    assert [int(s["regions"]) for s in spans[:3]] == [1, 1, 1]  # an ascending load lands in the last region
    assert int(spans[3]["regions"]) == len(db.store.regions()) >= 3
