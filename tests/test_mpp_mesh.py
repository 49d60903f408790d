"""TPC-H Q3 on a mesh of FOUR shards (PR 33, the deployment `tpch_sf2_mpp4`):
each way a join's rows can meet — left in place (`local`: the tables lie in
key order and are dealt over the shards in that order), the build side
replicated (`broadcast`), both sides repartitioned by key (`hash`, reached as
a user reaches it, through `tidb_broadcast_join_threshold_count`) — answers as
the benchmark's plain reference does for every parameter set; what the served
path's statistics make the planner choose; the shards' shares add up to the
one-device answer; a shard of padding alone and a capacity that overflows once
still answer exactly; every collective lies under `mpp.exchange`; and the
cell `tpch_sf2_mpp4.q3_1c` rehearses `correct`. SF 0.01, four of the forced
host devices."""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SF = 0.01
NDEV = 4
SESSION = ["SET tidb_isolation_read_engines = 'tpu'", "SET tidb_allow_mpp = 1", "SET tidb_enforce_mpp = 1"]
# plan -> (lineitem as loaded or shuffled, the session's extra setting, what the gather then runs join by join)
PLANS = {
    "local": ("ordered", None, "local,broadcast"),
    "broadcast": ("shuffled", None, "broadcast,broadcast"),
    "hash": ("shuffled", "SET tidb_broadcast_join_threshold_count = 0", "hash,hash"),
}


@pytest.fixture(scope="module", autouse=True)
def four_shards():
    from tidb_tpu.parallel import mesh as mesh_mod

    mesh_mod.FORCE_NDEV = NDEV
    yield
    mesh_mod.FORCE_NDEV = None


@pytest.fixture(scope="module")
def tpch():
    """({"ordered", "shuffled": db}, template q3, the reference's columns): the
    cell's generator, DDL, load order and session at SF 0.01; `shuffled` holds
    the same rows with `lineitem` loaded in no order, so that no shard's probe
    rows span a narrow key range and the planner's exchange stands."""
    sys.path.insert(0, BENCH)
    try:
        gen = importlib.import_module("generators.tpch")
        from harness.traffic import Template

        with open(os.path.join(BENCH, "configs", "tpch_sf2_mpp4.json")) as f:
            cfg = json.load(f)
        assert cfg["session"] == SESSION
        cfg["scale_factor"] = SF
        tables = gen.generate(11, cfg)
        tpl = Template("q3", 11, 0)
    finally:
        sys.path.remove(BENCH)
    import tidb_tpu
    from tidb_tpu.executor.load import bulk_load

    dbs = {}
    for name in ("ordered", "shuffled"):
        db = tidb_tpu.open(region_split_keys=int(cfg["store"]["region_split_keys"]))
        for t in cfg["load_order"]:
            db.execute(cfg["tables"][t]["ddl"])
            cols = tables[t]
            if name == "shuffled" and t == "lineitem":
                perm = np.random.default_rng(5).permutation(len(cols[0]))
                cols = [c[perm] for c in cols]
            bulk_load(db, t, cols)
        dbs[name] = db
    cols = {t: dict(zip(gen.COLUMNS[t], tables[t])) for t in tpl.tables}
    return dbs, tpl, cols


def _session(db, extra=None):
    s = db.session()
    for sql in SESSION + ([extra] if extra else []):
        s.execute(sql)
    return s


@pytest.fixture(scope="module")
def answers(tpch):
    """plan -> [(drawn, rows, the gather's details)] for the 8 parameter sets."""
    dbs, tpl, _ = tpch
    out = {}
    for plan, (data, extra, _) in PLANS.items():
        s = _session(dbs[data], extra)
        out[plan] = [(drawn, [tuple(str(c) for c in r) for r in s.query(text)], list(s.mpp_details)) for drawn, text in zip(tpl.drawn, tpl.texts)]
    return out


@pytest.mark.parametrize("plan", list(PLANS))
@pytest.mark.parametrize("k", range(8))
def test_q3_agrees_with_the_plain_reference_under_every_exchange(tpch, answers, plan, k):
    _, tpl, cols = tpch
    drawn, rows, details = answers[plan][k]
    assert len(rows) == 10
    assert tpl.ref.same(rows, tpl.ref.state(tpl.ref_columns(cols), drawn)), (plan, drawn, rows)
    (d,) = details  # ONE gather, on exactly four devices, no second attempt
    assert d.ndev == NDEV and d.retries == 0 and d.exchange == PLANS[plan][2]
    # (PR 34) `lineitem` in key order is probed by blocks; shuffled, some block spans more than two rows of
    # the bitmap and the whole lane takes the element gather (a hash exchange sorts a shard's rows by
    # destination, not by key). The program sees which, and answers the same
    assert d.probe == ("blocked,blocked" if plan == "local" else "gather,blocked"), d.render()


@pytest.mark.parametrize("plan", list(PLANS))
def test_one_program_a_plan_and_what_it_moves(answers, plan):
    """Eight parameter sets, one program; the bytes its collectives move are
    the compiled program's, so every statement of a plan reports the same;
    rows left in place move least, two repartitioned sides most."""
    details = [d for _, _, ds in answers[plan] for d in ds]
    assert [d.compiles for d in details] == [1] + [0] * 7
    moved = {tuple(sorted(d.xchg_bytes.items())) for d in details}
    assert len(moved) == 1 and sum(details[0].xchg_bytes.values()) > 0
    kinds = {k for k, v in details[0].xchg_bytes.items() if v}
    assert kinds == {"groups"} | set(PLANS[plan][2].split(",")), kinds
    assert all(d.xchg_rows > 0 for d in details)


def test_rows_in_place_move_less_than_any_planned_exchange(answers):
    local, bcast, hashed = (sum(answers[p][0][2][0].xchg_bytes.values()) for p in ("local", "broadcast", "hash"))
    assert local < bcast and local < hashed, (local, bcast, hashed)
    # in place only slivers of `orders` travel: far fewer valid rows than a replicated or a repartitioned table
    assert answers["local"][0][2][0].xchg_rows * 4 < min(answers[p][0][2][0].xchg_rows for p in ("broadcast", "hash"))


@pytest.mark.parametrize("plan", list(PLANS))
def test_every_collective_of_the_program_lies_under_the_exchange_scope(tpch, answers, plan):
    from tidb_tpu.parallel import gather, mpp

    dbs, tpl, _ = tpch
    data, extra, ran = PLANS[plan]
    with gather._MPP_CACHE_MU:
        gather._MPP_FN_CACHE.clear()
    s = _session(dbs[data], extra)
    s.query(tpl.texts[0])
    ((fn, _, moved),) = gather._MPP_FN_CACHE.values()
    assert s.mpp_details[-1].exchange == ran
    found = mpp.compiled_collectives(fn.as_text())
    assert len(found) >= 5
    for op, nbytes, name in found:
        assert mpp.EXCHANGE_SCOPE + "/" in name and name.split(mpp.EXCHANGE_SCOPE + "/")[1].split("/")[0] in mpp.EXCHANGE_KINDS, (op, name)
    assert moved == mpp.compiled_exchange_bytes(fn.as_text(), NDEV) == s.mpp_details[-1].xchg_bytes


def test_the_served_path_holds_no_statistics_and_plans_both_folds_as_broadcast(tpch):
    """What `bulk_load` leaves the planner at any scale, SF2's included: no
    row count at all (no ANALYZE ran), so `_choose_exchange`'s no-statistics
    rule answers: both build sides broadcast. The gather may then leave rows
    in place (EXPLAIN ANALYZE says what ran)."""
    dbs, tpl, _ = tpch
    db = dbs["ordered"]
    for t in ("customer", "orders", "lineitem"):
        assert db.stats.get(db.catalog.try_table("test", t).id) is None
    s = _session(db)
    text = "\n".join(r[0] for r in s.query("EXPLAIN " + tpl.first_text))
    assert "broadcast,broadcast join exchange" in text, text
    ran = "\n".join(str(r[0]) for r in s.query("EXPLAIN ANALYZE " + tpl.first_text))
    assert "ndev: 4" in ran and "exchange: local,broadcast" in ran and "xchg_bytes: " in ran and "xchg_rows: " in ran, ran


# SF2's row counts, post-selection as Q3's conditions leave them (about half of `orders` and of `lineitem`
# pass their dates, a fifth of `customer` its segment), against the shipped threshold of 100,000 rows on 4 devices
SF2_CHOICES = [
    ("no statistics: lineitem -> orders", None, None, "broadcast"),
    ("no statistics: orders -> customer", None, None, "broadcast"),
    ("analyzed: lineitem 6.5M -> orders 1.46M", 6_500_000, 1_460_000, "hash"),
    ("analyzed: orders 1.46M -> customer 60k", 1_460_000, 60_000, "broadcast"),
    ("build analyzed alone: orders 1.46M", None, 1_460_000, "hash"),
    ("probe analyzed alone: lineitem 6.5M", 6_500_000, None, "broadcast"),
]


@pytest.mark.parametrize("what,l_rows,r_rows,want", SF2_CHOICES, ids=[c[0] for c in SF2_CHOICES])
def test_the_exchange_sf2s_row_counts_choose(what, l_rows, r_rows, want):
    from tidb_tpu.parallel.gather import _choose_exchange

    assert _choose_exchange(l_rows, r_rows, NDEV, 100_000) == want
    assert _choose_exchange(l_rows, r_rows, NDEV, 0) == "hash"  # threshold 0: never broadcast


def test_the_shares_add_up_to_the_one_device_answer(tpch, monkeypatch):
    """The four shards' partial sums a group, each group counted on the
    shard(s) that hold it, are the one-device groups: rows in place leave a
    group on one shard, or astride a cut on two, and nothing merges them on
    the mesh."""
    from tidb_tpu.parallel import gather
    from tidb_tpu.parallel import mesh as mesh_mod

    dbs, tpl, _ = tpch
    seen = []
    real = gather.MPPGatherExec._merge

    def merge(self, outs, agg):
        seen.append([np.asarray(o) for o in outs])
        return real(self, outs, agg)

    monkeypatch.setattr(gather.MPPGatherExec, "_merge", merge)

    def groups(ndev):
        monkeypatch.setattr(mesh_mod, "FORCE_NDEV", ndev)
        s = _session(dbs["ordered"])
        s.query(tpl.texts[3])
        (d,) = s.mpp_details
        outs = seen.pop()
        cnt = outs[8]  # 6 key lanes, revenue and its count of rows, then the groups' row counts
        per = len(cnt) // ndev
        held: dict = {}
        for i in np.flatnonzero(cnt > 0):
            held.setdefault(int(outs[0][i]), []).append((int(i) // per, int(outs[6][i]), int(cnt[i])))
        return d, held

    one, whole = groups(1)
    four, shares = groups(NDEV)
    assert one.ndev == 1 and four.ndev == NDEV and four.exchange == "local,broadcast"
    assert set(shares) == set(whole) and len(whole) > 100
    for key, parts in shares.items():
        assert len(parts) <= 2 and len({sh for sh, _, _ in parts}) == len(parts)
        assert (sum(p[1] for p in parts), sum(p[2] for p in parts)) == whole[key][0][1:]
    assert len({sh for parts in shares.values() for sh, _, _ in parts}) == NDEV  # every shard holds a share


@pytest.fixture(scope="module")
def star():
    """A dimension of 4,000 keys in key order and its fact table in the same
    order, 5 rows a key in the first and third thousand and 3 in the others:
    dealt over four shards the cuts do not line up, and the second and the
    fourth fact shard reach 200 rows back into the shard before's dimension rows."""
    import tidb_tpu
    from tidb_tpu.executor.load import bulk_load

    db = tidb_tpu.open()
    db.execute("CREATE TABLE dim (id BIGINT PRIMARY KEY, grp BIGINT)")
    db.execute("CREATE TABLE fact (id BIGINT, v BIGINT)")
    ids = np.arange(4000, dtype=np.int64)
    bulk_load(db, "dim", [ids, ids % 7])
    fk = np.repeat(ids, np.where(ids // 1000 % 2 == 0, 5, 3))
    bulk_load(db, "fact", [fk, (fk * 31 + np.arange(len(fk))) % 1000])
    want = {}
    for k, v in zip(fk.tolist(), ((fk * 31 + np.arange(len(fk))) % 1000).tolist()):
        if k % 7 != 3:
            want[k] = want.get(k, 0) + v
    return db, want


STAR_SQL = "SELECT fact.id, SUM(v) FROM fact JOIN dim ON fact.id = dim.id WHERE grp <> 3 GROUP BY fact.id"


def _star_answer(s):
    return {int(r[0]): int(r[1]) for r in s.query(STAR_SQL)}


def test_a_sliver_capacity_that_overflows_grows_once_and_answers_exactly(star, monkeypatch):
    from tidb_tpu.parallel import gather

    db, want = star
    s = db.session()
    s.execute("SET tidb_enforce_mpp = 1")
    assert _star_answer(s) == want
    (d,) = s.mpp_details
    assert d.exchange == "local" and d.compiles == 1 and d.ndev == NDEV
    real = gather._in_place

    def understated(*a):
        return [dict(v, halo=1) if v else v for v in real(*a)]  # 64 rows a pair, the floor: up to 200 are needed

    monkeypatch.setattr(gather, "_in_place", understated)
    s = db.session()
    s.execute("SET tidb_enforce_mpp = 1")
    assert _star_answer(s) == want
    (d,) = s.mpp_details
    assert d.exchange == "local" and d.compiles == 2 and d.retries == 0  # the first program dropped rows and said so: one bigger one


def test_rows_in_place_reduce_as_runs_without_the_sort(star, monkeypatch):
    """A shard's slivers sit behind its own build rows, so its probe rows' build
    SLOTS step back at a cut though their keys never do: the runs are told by
    key code, and every shard reduces in place (`_slot_partial`'s sort branch,
    4.19M rows with their value lanes at SF2, is not taken)."""
    import jax
    import jax.numpy as jnp

    from tidb_tpu.parallel import gather, mpp

    db, want = star
    seen = []
    real = mpp._slot_partial

    def spy(jax_, jnp_, slot, mask, vals, cap):
        live = mask & (slot >= 0)
        s = jnp.where(live, slot, -1)
        prev = jnp.concatenate([jnp.full(1, -1, s.dtype), jax.lax.cummax(s)[:-1]])
        jax.debug.callback(lambda ok: seen.append(bool(ok)), jnp.all(~live | (s >= prev)))
        return real(jax_, jnp_, slot, mask, vals, cap)

    monkeypatch.setattr(mpp, "_slot_partial", spy)
    with gather._MPP_CACHE_MU:
        gather._MPP_FN_CACHE.clear()
    s = db.session()
    s.execute("SET tidb_enforce_mpp = 1")
    assert _star_answer(s) == want and s.mpp_details[-1].exchange == "local"
    jax.effects_barrier()
    assert seen == [True] * NDEV, seen
    with gather._MPP_CACHE_MU:
        gather._MPP_FN_CACHE.clear()  # the spied program is no one else's


def test_a_hash_capacity_that_overflows_grows_once_and_answers_exactly():
    """Every key a multiple of 4: `code % 4` sends all rows to shard 0, four
    times the even share the capacity is sized for (with a quarter over)."""
    import tidb_tpu
    from tidb_tpu.executor.load import bulk_load

    db = tidb_tpu.open()
    db.execute("CREATE TABLE d4 (id BIGINT PRIMARY KEY, grp BIGINT)")
    db.execute("CREATE TABLE f4 (id BIGINT, v BIGINT)")
    ids = np.arange(0, 8000, 4, dtype=np.int64)
    bulk_load(db, "d4", [ids, ids % 5])
    fk = np.random.default_rng(3).permutation(np.repeat(ids, 3))
    bulk_load(db, "f4", [fk, fk % 11])
    s = db.session()
    s.execute("SET tidb_enforce_mpp = 1")
    s.execute("SET tidb_broadcast_join_threshold_count = 0")
    got = {int(r[0]): int(r[1]) for r in s.query("SELECT grp, SUM(v) FROM f4 JOIN d4 ON f4.id = d4.id GROUP BY grp")}
    want: dict = {}
    for k in fk.tolist():
        want[k % 5] = want.get(k % 5, 0) + k % 11
    assert got == want
    (d,) = s.mpp_details
    assert d.exchange == "hash" and d.ndev == NDEV and d.compiles == 2 and d.retries == 0


def test_a_shard_that_holds_padding_alone_answers_exactly():
    """Five dimension rows dealt over four shards are 2, 2, 1 and none: the
    fourth shard holds no valid build row, and of a fact table of 3 rows two
    shards hold none."""
    import tidb_tpu

    db = tidb_tpu.open()
    db.execute("CREATE TABLE d5 (id BIGINT PRIMARY KEY, grp BIGINT)")
    db.execute("CREATE TABLE f5 (id BIGINT, v BIGINT)")
    db.execute("INSERT INTO d5 VALUES (1, 10), (2, 20), (3, 10), (4, 20), (5, 30)")
    db.execute("INSERT INTO f5 VALUES (5, 7), (1, 1), (5, 2)")
    s = db.session()
    s.execute("SET tidb_enforce_mpp = 1")
    for extra in (None, "SET tidb_broadcast_join_threshold_count = 0"):
        if extra:
            s.execute(extra)
        rows = s.query("SELECT grp, SUM(v), COUNT(*) FROM f5 JOIN d5 ON f5.id = d5.id GROUP BY grp ORDER BY grp")
        assert [tuple(int(c) for c in r) for r in rows] == [(10, 1, 1), (30, 9, 2)]
        assert s.mpp_details[-1].ndev == NDEV


def test_rows_are_dealt_evenly_and_the_spans_say_what_ran(tpch, tmp_path):
    """12.0M rows padded to 4 x 4.19M and laid end to end left the fourth
    shard empty; dealt evenly the fullest shard holds the mean, to a row a
    table. One statement under a `jax.profiler` session: the `tidb:mpp.*` spans' stats."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from tidb_tpu.utils import tracing

    dbs, tpl, _ = tpch
    s = _session(dbs["ordered"])
    s.query(tpl.texts[0])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        s.query(tpl.texts[1])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX + "mpp."):
                    stats[ev.name[len(tracing.PREFIX):]] = dict(ev.stats)
    lanes = {k: int(v) for k, v in stats["mpp.lanes"].items() if k in ("ndev", "shard_rows_max", "shard_rows_min", "rows_valid")}
    assert lanes["ndev"] == NDEV and 0 <= lanes["shard_rows_max"] - lanes["shard_rows_min"] <= 3 * NDEV
    assert lanes["shard_rows_max"] <= lanes["rows_valid"] / NDEV + 3
    assert stats["mpp.program"]["exchange"] == "local+broadcast" and int(stats["mpp.gather"]["ndev"]) == NDEV
    d = s.mpp_details[-1]
    assert int(stats["mpp.fetch"]["xchg_bytes"]) == sum(d.xchg_bytes.values()) and int(stats["mpp.fetch"]["xchg_rows"]) == d.xchg_rows


def test_the_counter_on_metrics_grows_by_what_a_gather_moved(tpch):
    from tidb_tpu.utils import metrics

    dbs, tpl, _ = tpch
    before = {k: metrics.MPP_EXCHANGE_BYTES.get(kind=k) for k in ("hash", "broadcast", "local", "groups")}
    s = _session(dbs["ordered"])
    s.query(tpl.texts[1])
    moved = s.mpp_details[-1].xchg_bytes
    for k, v0 in before.items():
        assert metrics.MPP_EXCHANGE_BYTES.get(kind=k) - v0 == moved.get(k, 0)
    assert 'tidb_tpu_mpp_exchange_bytes_total{kind="local"}' in metrics.REGISTRY.render()


def test_rehearsal_of_the_four_chip_cell_is_correct_and_prints_its_metrics(tmp_path):
    """`tpch_sf2_mpp4.q3_1c` as the driver runs it, on four forced host devices at SF 0.01."""
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "tpch_sf2_mpp4.q3_1c", "--seed", "2147483777",
           "--seconds", "2", "--trace", "1", "--platform", "cpu", "--scale", str(SF)]
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] >= 4 and line["device"]["count"] == 4, line
    assert {k: c["value"] for k, c in line["checks"].items()} == {"answers_wrong": 0, "statements_failed": 0, "not_on_device": 0}
    assert "ndev [4]" in p.stderr and "0 programs built, 0 re-planned" in p.stderr, p.stderr[-2000:]
    m = line["metrics"]
    # every per-layer metric of the cell but those that read the TPU's own lines (kernel and collective time, the shares of a chip's peaks)
    for name in ("mpp_gather_p50_ms", "mpp_lanes_ms", "mpp_dispatch_ms", "mpp_fetch_ms", "mpp_merge_ms", "mpp_padded_ratio",
                 "mpp_exchange_bytes_per_stmt", "mpp_shard_skew", "device_idle_pct", "compiles_in_window"):
        assert name in m, sorted(m)
    for name in ("mpp_kernel_ms", "mpp_exchange_ms", "mpp_exchange_ici_pct", "scan_roofline"):
        assert name not in m, name
    assert m["compiles_in_window"]["value"] == 0 and 1.0 <= m["mpp_shard_skew"]["value"] < 1.01
    assert m["mpp_exchange_bytes_per_stmt"]["value"] > 0 and 1.0 <= m["mpp_padded_ratio"]["value"] <= 2.0
