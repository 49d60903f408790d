"""TPC-H Q3 through ONE MPP gather at the shapes of the spec's scale step 10
(`benchmark/configs/tpch_sf10_mpp.json`), on the CPU at SF 0.01: a rehearsal
of the cell `tpch_sf10_mpp.q3_1c`; the device bytes of the lanes a program
reads (`lane_bytes` on `tidb:mpp.lanes`, the pool's gauge); `o_orderkey`s
whose domain passes 2^25 with few rows, so that `orders`' direct-address
table and presence bitmap are 2^26 codes as at SF10; the first group slots
sized by the probe's rows (SF10's ~115k groups against 2^16 slots); and the
lanes made a column at a time from the regions' own arrays, byte for byte
what the joined chunk gave."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SF = 0.01
CELL = "tpch_sf10_mpp.q3_1c"


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "tpch_sf10_mpp.json")) as f:
        return json.load(f)


def _tables(shift_from=None):
    """(config, template q3, tables) at SF 0.01 from the configuration's own
    generator; ``shift_from``: every order key from that one on moves up by
    2^25, in `orders` and `lineitem` alike (both stay in key order)."""
    import importlib

    sys.path.insert(0, BENCH)
    try:
        cfg = _config()
        cfg["scale_factor"] = SF
        gen = importlib.import_module(f"generators.{cfg['generator']}")
        tables = gen.generate(13, cfg)
        from harness.traffic import Template

        tpl = Template("q3", 13, 0)
        columns = gen.COLUMNS
    finally:
        sys.path.remove(BENCH)
    if shift_from is not None:
        for table, key in (("orders", "o_orderkey"), ("lineitem", "l_orderkey")):
            col = tables[table][columns[table].index(key)]
            col[col >= shift_from] += 1 << 25
    return cfg, tpl, tables, columns


def _open(cfg, tables):
    import tidb_tpu
    from tidb_tpu.executor.load import bulk_load

    db = tidb_tpu.open(region_split_keys=int(cfg["store"]["region_split_keys"]))
    batch = int(cfg["store"]["load_batch_rows"])  # as the benchmark loads: regions split as they grow
    for name in cfg["load_order"]:
        db.execute(cfg["tables"][name]["ddl"])
        for lo in range(0, len(tables[name][0]), batch):
            bulk_load(db, name, [c[lo : lo + batch] for c in tables[name]])
    return db


def _session(db, cfg):
    s = db.session()
    for sql in cfg["session"]:
        s.execute(sql)
    return s


@pytest.fixture(scope="module")
def tpch():
    cfg, tpl, tables, columns = _tables()
    cols = {t: dict(zip(columns[t], tables[t])) for t in tpl.tables}
    return _open(cfg, tables), cfg, tpl, cols


class _Annotation:
    """Stands in for `jax.profiler.TraceAnnotation`: the `tidb:` spans the
    program opens while a profiler session is live, with their stats."""

    seen: list = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, dict(meta)
        _Annotation.seen.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **meta):
        self.meta.update(meta)

    @staticmethod
    def is_enabled():
        return True


@pytest.fixture
def spans(monkeypatch):
    """name -> [stats] of every `tidb:` span opened from here on."""
    from tidb_tpu.utils import tracing

    _Annotation.seen = []
    monkeypatch.setattr(tracing, "_annotation", _Annotation)

    def of(name):
        return [a.meta for a in _Annotation.seen if a.name == tracing.PREFIX + name]

    return of


def test_rehearsal_of_the_cell_is_correct_and_prints_its_metrics(tmp_path):
    """`tpch_sf10_mpp.q3_1c` as the benchmark runs it, on the CPU at SF 0.01:
    `correct`, every check 0, and every `mpp_*` metric its entries in
    `BENCHMARK.json` list it for, `mpp_lane_mb` among them."""
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483891",
           "--seconds", "2", "--trace", "1", "--platform", "cpu", "--scale", str(SF)]
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=1", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] >= 4, line
    assert {k: c["value"] for k, c in line["checks"].items()} == {"answers_wrong": 0, "statements_failed": 0, "not_on_device": 0}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"] if CELL in m.get("workloads", ())]
    mpp = [n for n in listed if n.startswith("mpp_")]
    assert "mpp_lane_mb" in mpp and "mpp_exchange_ms" not in mpp
    m = line["metrics"]
    # every one but `mpp_kernel_ms`, which reads the TPU's `XLA Modules` line: no such line here
    assert set(mpp) - set(m) == {"mpp_kernel_ms"}, sorted(m)
    assert set(listed) - set(m) == {"mpp_kernel_ms"}, sorted(set(listed) - set(m))
    assert m["compiles_in_window"]["value"] == 0 and 1.0 <= m["mpp_padded_ratio"]["value"] <= 2.0
    # lineitem's 2^16 padded rows x (4 int64 lanes + 5 bool), orders' 2^14 alike, customer's 2^11 x (int64 +
    # the segment's int32 dictionary codes + 3 bool)
    assert m["mpp_lane_mb"]["value"] == pytest.approx(((1 << 16) * 37 + (1 << 14) * 37 + (1 << 11) * 15) / 1e6)


@pytest.mark.parametrize("ndev", [1, 4])
def test_lane_bytes_are_the_bytes_the_gather_put_on_the_device(tpch, spans, monkeypatch, ndev):
    """A cold gather puts every lane it reads: `lane_bytes` is what those
    device arrays hold, `h2d` alike; a warm one puts nothing and reads the
    same bytes from the pool, which the gauge `tidb_tpu_mpp_lane_bytes` holds."""
    import jax

    from tidb_tpu.parallel import gather
    from tidb_tpu.parallel import mesh as mesh_mod
    from tidb_tpu.utils import metrics

    db, cfg, tpl, _ = tpch
    put = []
    real_put = jax.device_put

    def recording_put(x, *a, **kw):
        out = real_put(x, *a, **kw)
        if isinstance(x, np.ndarray):
            put.append(out)
        return out

    monkeypatch.setattr(mesh_mod, "FORCE_NDEV", ndev)
    monkeypatch.setattr(jax, "device_put", recording_put)
    gather._MPP_DEV_CACHE.clear()
    s = _session(db, cfg)
    s.query(tpl.texts[0])
    s.query(tpl.texts[1])
    cold, warm = spans("mpp.lanes")
    lane_bytes = int(cold["lane_bytes"])
    assert lane_bytes == sum(int(a.nbytes) for a in put) == int(cold["h2d"]) > 0
    # 4 + 4 + 2 columns (data + validity) and a live lane a table, every row padded: lineitem's ~60k to 2^16 a shard's
    assert len(put) == 2 * 10 + 3 and {int(a.shape[0]) % ndev for a in put} == {0}
    assert int(warm["lane_bytes"]) == lane_bytes and int(warm["h2d"]) == 0 and warm["cache"] == "hit"
    assert metrics.MPP_LANE_BYTES.get() == lane_bytes == gather._pool_bytes()
    assert f"tidb_tpu_mpp_lane_bytes {lane_bytes:g}" in metrics.REGISTRY.render()


@pytest.mark.parametrize("ndev", [1, 4])
def test_q3_over_order_keys_whose_domain_passes_2_25(monkeypatch, ndev):
    """The last ~third of the orders keyed 2^25 higher (the cut on a block
    boundary of `lineitem`'s rows): 15,000 orders whose keys span 2^26 codes
    once bounded, as SF10's 15M do. On one chip `orders`' direct-address table
    and its presence bitmap are sized by that domain and `lineitem`'s rows
    still go by blocks; on one chip and on four every parameter set's answer
    is the plain reference's."""
    from tidb_tpu.parallel import gather
    from tidb_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(mesh_mod, "FORCE_NDEV", ndev)

    _, _, tables, columns = _tables()
    lkey = tables["lineitem"][columns["lineitem"].index("l_orderkey")]
    # an order boundary at a multiple of the probe's block (128 rows), two thirds in
    at = next(i for i in range(2 * len(lkey) // 3 // 128 * 128, len(lkey), 128) if lkey[i] != lkey[i - 1])
    cfg, tpl, tables, columns = _tables(shift_from=int(lkey[at]))
    okey = tables["orders"][columns["orders"].index("o_orderkey")]
    assert len(okey) == 15_000 and (1 << 25) < okey.max() < (1 << 26)
    cols = {t: dict(zip(columns[t], tables[t])) for t in tpl.tables}
    s = _session(_open(cfg, tables), cfg)
    for drawn, text in zip(tpl.drawn, tpl.texts):
        rows = [tuple(str(c) for c in r) for r in s.query(text)]
        assert len(rows) == 10 and tpl.ref.same(rows, tpl.ref.state(tpl.ref_columns(cols), drawn)), (drawn, rows)
        (d,) = s.mpp_details
        assert d.ndev == ndev and (ndev > 1 or d.probe == "blocked,blocked"), d.render()
    if ndev == 1:  # the program was built for 2^26 codes: orders' key bounds widen to [0, 2^26 - 1]
        fn, *_ = list(gather._MPP_FN_CACHE.values())[-1]
        assert "s32[67108864]" in fn.as_text()


@pytest.mark.parametrize("rows,slots", [(59_957, 1 << 16), (12_000_000, 1 << 16), (60_000_000, 234_375)])
def test_the_first_group_slots_grow_with_the_probe_rows(rows, slots):
    from tidb_tpu.parallel import gather

    assert gather.first_group_slots(rows) == slots


def test_q3_at_sf10_s_rows_starts_with_room_for_its_groups(tpch, spans, monkeypatch):
    """With no statistics (a bulk load), Q3's first program at SF2's 12M
    `lineitem` rows keeps 2^16 group slots for its ~23k groups (the accepted
    cells' program), and at SF10's 60M gets 2^18 for its 109k-119k, where
    2^16 overflowed into a second, bigger program every statement. The
    program span says which it ran with."""
    from tidb_tpu.parallel import gather
    from tidb_tpu.parallel import mesh as mesh_mod

    db, cfg, tpl, _ = tpch
    asked = []
    initial = gather.MPPGatherExec._initial_group_cap
    monkeypatch.setattr(gather.MPPGatherExec, "_initial_group_cap", lambda self, n: asked.append((self, n)) or initial(self, n))
    monkeypatch.setattr(mesh_mod, "FORCE_NDEV", 1)
    _session(db, cfg).query(tpl.texts[0])
    ((ex, n),) = asked
    assert 59_000 < n < 61_000  # the probe's rows: lineitem's at SF 0.01
    assert [initial(ex, rows) for rows in (n, 12_000_000, 60_000_000)] == [1 << 16, 1 << 16, 1 << 18]
    assert [st["group_cap"] for st in spans("mpp.program")] == [1 << 16]


def _joined_then_dealt(cols, n, ndev):
    """The lanes as the gather made them before they were made a column at a
    time: every column's regions joined into one array, then dealt over the
    shards, then masked — (lanes, bounds, what each shard holds, rows)."""
    from tidb_tpu.ops.window_core import widen_bounds
    from tidb_tpu.parallel.gather import _pow2

    q = (n + ndev - 1) // ndev
    per = _pow2(max(q, 8))
    cuts = [(min(sh * q, n), min((sh + 1) * q, n)) for sh in range(ndev)]

    def deal(a):
        out = np.zeros(per * ndev, dtype=a.dtype)
        for sh, (lo, hi) in enumerate(cuts):
            out[sh * per : sh * per + hi - lo] = a[lo:hi]
        return out

    lanes, bounds, by_shard = [], [], []
    for parts in cols:
        data, valid = np.concatenate([d for d, _ in parts]), np.concatenate([v for _, v in parts])
        v = deal(valid)
        lanes += [np.where(v, deal(data), 0), v]
        if np.issubdtype(data.dtype, np.floating):
            bounds.append(None)
            by_shard.append(None)
            continue
        lv = data[valid]
        bounds.append((int(lv.min()), int(lv.max())) if lv.size else (0, 0))
        if ndev == 1:
            by_shard.append(None)
            continue
        lows, highs, ordered = [], [], True
        for lo, hi in cuts:
            part, ok = data[lo:hi], valid[lo:hi]
            if not ok.all():
                part, ordered = part[ok], False
            lows.append(int(part.min()) if part.size else 0)
            highs.append(int(part.max()) if part.size else -1)
            ordered = ordered and bool((part[1:] >= part[:-1]).all())
        by_shard.append((lows, highs, ordered))
    return lanes + [deal(np.ones(n, dtype=bool))], widen_bounds(bounds), by_shard, [hi - lo for lo, hi in cuts]


@pytest.mark.parametrize("ndev", [1, 2, 4, 8])
@pytest.mark.parametrize("sizes", [(0,), (5,), (250, 0, 7, 1024, 3), (4096,) * 9 + (17,)])
def test_lanes_made_a_column_at_a_time_are_the_joined_chunk_s_byte_for_byte(ndev, sizes):
    """Regions' arrays of every kind a lane holds (int64 keys in order,
    int32 dictionary codes, float64, bool, a part all NULL, parts without a
    NULL, empty parts), dealt over 1 to 8 shards: the same bytes, bounds and
    per-shard ranges as joining the regions first gave."""
    from tidb_tpu.parallel.gather import _dealt_lanes, _Parts

    rng = np.random.default_rng(sum(sizes) * 8 + ndev)
    n = sum(sizes)
    at = np.cumsum((0,) + sizes)
    keys = np.sort(rng.integers(-50, 10**9, n))

    def column(make, nulls):
        out = []
        for k, m in enumerate(sizes):
            valid = np.ones(m, bool) if k % 2 == 0 or not nulls else rng.random(m) < 0.7
            if k == 3 and nulls:
                valid[:] = False
            out.append((make(k, m), valid))
        return out

    cols = [
        column(lambda k, m: keys[at[k] : at[k] + m], nulls=False),
        column(lambda k, m: keys[at[k] : at[k] + m], nulls=True),
        column(lambda k, m: rng.integers(0, 5, m).astype(np.int32), nulls=True),
        column(lambda k, m: rng.normal(size=m), nulls=True),
        column(lambda k, m: rng.random(m) < 0.5, nulls=True),
    ]
    lanes, bounds, by_shard, rows = _joined_then_dealt(cols, n, ndev)
    got_n, got_rows, live, columns = _dealt_lanes(_Parts(n, cols), ndev)
    assert (got_n, got_rows, len(columns)) == (n, rows, len(cols))
    assert live.dtype == lanes[-1].dtype and live.tobytes() == lanes[-1].tobytes()
    for i, make in enumerate(columns):
        data, valid, b, held = make()
        for got, want in ((data, lanes[2 * i]), (valid, lanes[2 * i + 1])):
            assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), i
        assert (b, held) == (bounds[i], by_shard[i]), i


def test_many_regions_give_the_joined_chunk_s_lanes_and_the_reference_s_answers(monkeypatch):
    """`tpch_sf10_mpp`'s tables at SF 0.01 in regions split at 5,000 keys
    (20 and more of `lineitem`'s), on four shards: every lane the gather made from the
    regions' arrays is the joined chunk's, byte for byte, what went on the
    device is what was made, and every answer is the plain reference's."""
    from tidb_tpu.parallel import gather
    from tidb_tpu.parallel import mesh as mesh_mod

    cfg, tpl, tables, columns = _tables()
    cfg["store"].update(region_split_keys=5000, load_batch_rows=1000)
    db = _open(cfg, tables)
    made = []  # (parts, ndev, {column: what was made})
    real = gather._dealt_lanes

    def recording(chunk, ndev):
        n, rows, live, columns = real(chunk, ndev)
        mine = {}
        made.append((chunk, ndev, mine))
        return n, rows, live, [lambda i=i, make=make: mine.setdefault(i, make()) for i, make in enumerate(columns)]

    monkeypatch.setattr(gather, "_dealt_lanes", recording)
    monkeypatch.setattr(mesh_mod, "FORCE_NDEV", 4)
    gather._MPP_DEV_CACHE.clear()
    s = _session(db, cfg)
    cols = {t: dict(zip(columns[t], tables[t])) for t in tpl.tables}
    for drawn, text in list(zip(tpl.drawn, tpl.texts))[:2]:
        rows = [tuple(str(c) for c in r) for r in s.query(text)]
        assert tpl.ref.same(rows, tpl.ref.state(tpl.ref_columns(cols), drawn)), (drawn, rows)
    assert len(made) == 3 and max(len(chunk.cols[0]) for chunk, _, _ in made) >= 20  # lineitem's regions
    for chunk, ndev, mine in made:
        lanes, bounds, by_shard, _ = _joined_then_dealt(chunk.cols, chunk.n, ndev)
        assert sorted(mine) == list(range(len(chunk.cols)))
        for i, (data, valid, b, held) in mine.items():
            assert data.tobytes() == lanes[2 * i].tobytes() and valid.tobytes() == lanes[2 * i + 1].tobytes()
            assert (b, held) == (bounds[i], by_shard[i])
    pooled = [np.asarray(a) for ent in gather._MPP_DEV_CACHE.values() for col in ent["cols"].values() for a in col[:2]]
    host = [a for _, _, mine in made for data, valid, _, _ in mine.values() for a in (data, valid)]
    assert sorted(a.tobytes() for a in pooled) == sorted(a.tobytes() for a in host)
