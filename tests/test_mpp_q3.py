"""TPC-H Q3 as `benchmark/queries/q3.json` writes it, through ONE MPP gather
(PR 29): the plan (selections pushed through nested joins, the join order),
the answers against the benchmark's plain reference for every parameter set on
1 and on 4 devices, one fragment program for all of them with no host callback
in it, the spans that tile `MPPGatherExec.execute`, and a rehearsal of the cell
`tpch_sf2_mpp.q3_1c` itself. One database a module, SF 0.01."""

import glob
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SF = 0.01
SESSION = ["SET tidb_isolation_read_engines = 'tpu'", "SET tidb_allow_mpp = 1", "SET tidb_enforce_mpp = 1"]


@pytest.fixture(scope="module")
def tpch():
    """(db, template q3, the reference's columns): the benchmark's generator,
    DDL, load order and session settings, at SF 0.01."""
    sys.path.insert(0, BENCH)
    try:
        gen = importlib.import_module("generators.tpch")
        from harness.traffic import Template

        with open(os.path.join(BENCH, "configs", "tpch_sf2_mpp.json")) as f:
            cfg = json.load(f)
        assert cfg["session"] == SESSION
        cfg["scale_factor"] = SF
        tables = gen.generate(11, cfg)
        tpl = Template("q3", 11, 0)
    finally:
        sys.path.remove(BENCH)
    import tidb_tpu
    from tidb_tpu.executor.load import bulk_load

    db = tidb_tpu.open(region_split_keys=int(cfg["store"]["region_split_keys"]))
    for name in cfg["load_order"]:
        db.execute(cfg["tables"][name]["ddl"])
        bulk_load(db, name, tables[name])
    cols = {t: dict(zip(gen.COLUMNS[t], tables[t])) for t in tpl.tables}
    return db, tpl, cols


@pytest.fixture
def session(tpch):
    s = tpch[0].session()
    for sql in SESSION:
        s.execute(sql)
    return s


def _join_on(sql: str) -> str:
    """The same statement with its joins spelled JOIN ... ON."""
    out = sql.replace("FROM customer, orders, lineitem WHERE", "FROM customer JOIN orders ON c_custkey = o_custkey JOIN lineitem ON l_orderkey = o_orderkey WHERE")
    out = out.replace("AND c_custkey = o_custkey AND l_orderkey = o_orderkey ", "")
    assert out != sql and "customer, orders" not in out
    return out


@pytest.mark.parametrize("spelling", ["comma", "join_on"])
def test_q3_plans_as_one_gather_with_lineitem_probing_two_unique_builds(tpch, session, spelling):
    _, tpl, _ = tpch
    sql = tpl.first_text if spelling == "comma" else _join_on(tpl.first_text)
    text = "\n".join(r[0] for r in session.query("EXPLAIN " + sql))
    assert text.count("PhysMPPGather") == 1, text
    for absent in ("cross", "PhysSelection", "PhysHashJoin", "PhysFinalAgg"):
        assert absent not in text, text
    # lineitem probes; orders and customer are build sides, each on its primary key
    assert "[mpp] lineitem: Scan -> Selection -> Join -> Join -> PartialAgg" in text, text
    assert "lookup orders(unique), customer(unique, in orders)" in text, text
    # each filter sits in its reader
    for reader in ("lineitem: Scan -> Selection(gt(l_shipdate", "orders: Scan -> Selection(lt(o_orderdate", "customer: Scan -> Selection(eq(c_mktsegment"):
        assert reader in text, text


@pytest.fixture(scope="module")
def answers(tpch):
    """Every parameter set's answer on 1 and on 4 forced host devices, as
    text cells, with what the gathers recorded: {ndev: [(drawn, rows, details)]}."""
    from tidb_tpu.parallel import mesh as mesh_mod

    db, tpl, _ = tpch
    out = {}
    for nd in (1, 4):
        mesh_mod.FORCE_NDEV = nd
        try:
            s = db.session()
            for sql in SESSION:
                s.execute(sql)
            runs = []
            for drawn, text in zip(tpl.drawn, tpl.texts):
                rows = [tuple(str(c) for c in r) for r in s.query(text)]
                runs.append((drawn, rows, list(s.mpp_details)))
            out[nd] = runs
        finally:
            mesh_mod.FORCE_NDEV = None
    return out


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("k", range(8))
def test_q3_agrees_with_the_plain_reference(tpch, answers, ndev, k):
    _, tpl, cols = tpch
    drawn, rows, details = answers[ndev][k]
    assert len(rows) == 10
    assert tpl.ref.same(rows, tpl.ref.state(tpl.ref_columns(cols), drawn)), (drawn, rows)
    assert len(details) == 1 and details[0].ndev == ndev and details[0].retries == 0


@pytest.mark.parametrize("ndev", [1, 4])
def test_eight_parameter_sets_are_one_fragment_program(answers, ndev):
    built = [d.compiles for _, _, ds in answers[ndev] for d in ds]
    assert built[0] == 1 and sum(built) == 1, built  # the first statement builds it, the other seven find it


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("k", range(8))
def test_every_probe_row_of_q3_is_answered_by_blocks(answers, ndev, k):
    """(PR 34) `lineitem` lies in `l_orderkey` order, so every block of its
    probe rows reads two adjacent rows of `orders`' presence bitmap: the
    program sees it in its data, under every parameter set, on one shard and
    on four (the bitmap a shard's own table's, slivers in). The arm (`orders`
    probing `customer` by `o_custkey`, in no order) is blocked here too: SF
    0.01's 1,500 customer codes lie in ONE row of the bitmap; SF2's 300,000
    span 73, and the arm takes the element gather (on the chip: 4 of 5 probe
    rows blocked; a shuffled `lineitem` here: `tests/test_mpp_mesh.py`)."""
    (d,) = answers[ndev][k][2]
    assert d.probe == "blocked,blocked", d.render()


def test_explain_analyze_and_the_metrics_page_say_how_the_probes_were_answered(tpch, session):
    from tidb_tpu.utils import metrics

    _, tpl, _ = tpch
    before = {how: metrics.MPP_PROBE_ROWS.get(how=how) for how in ("blocked", "gather")}
    ran = "\n".join(str(r[0]) for r in session.query("EXPLAIN ANALYZE " + tpl.first_text))
    assert "mpp_task: {" in ran and "probe: blocked,blocked" in ran, ran
    blocked, gathered = (metrics.MPP_PROBE_ROWS.get(how=how) - before[how] for how in ("blocked", "gather"))
    # the padded lanes of `lineitem` (~60k rows) and of `orders` (15k), whatever the mesh the suite runs on
    assert 75_000 <= blocked <= 2 * (65_536 + 16_384) and gathered == 0, (blocked, gathered)
    page = metrics.REGISTRY.render()
    assert 'tidb_tpu_mpp_probe_rows_total{how="blocked"}' in page and 'tidb_tpu_mpp_probe_rows_total{how="gather"}' in page


def test_the_shipped_fragment_program_holds_no_host_callback(tpch, session, monkeypatch):
    """Probes off as shipped: nothing in the program calls back into Python, so
    its executable can persist in the compile cache; probes on, something does."""
    from tidb_tpu.parallel import gather

    _, tpl, _ = tpch

    def newest_program_text():
        session.query(tpl.first_text)
        fn, *_ = list(gather._MPP_FN_CACHE.values())[-1]
        return fn.as_text()

    assert gather.PROBES_ENABLED is False
    assert "callback" not in newest_program_text().lower()
    monkeypatch.setattr(gather, "PROBES_ENABLED", True)
    assert "callback" in newest_program_text().lower()


PHASES = ("lanes", "program", "dispatch", "fetch", "merge")


@pytest.fixture(scope="module")
def profiled(tpch, tmp_path_factory):
    """Two Q3 statements under a `jax.profiler` session: the `tidb:` events,
    name -> [(start ns, duration ns, stats)]."""
    import jax
    from jax.profiler import ProfileData

    from tidb_tpu.utils import tracing

    db, tpl, _ = tpch
    s = db.session()
    for sql in SESSION:
        s.execute(sql)
    s.query(tpl.texts[0])  # built outside the session, as a cell's warm-up does
    d = str(tmp_path_factory.mktemp("prof"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        s.query(tpl.texts[1])
        s.query(tpl.texts[2])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    events.setdefault(ev.name[len(tracing.PREFIX):], []).append((ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return events


def test_the_phases_tile_the_gather(profiled):
    gathers = profiled["mpp.gather"]
    assert len(gathers) == 2
    for start, dur, stats in gathers:
        inside = [(n, d) for n in PHASES for a, d, _ in profiled.get("mpp." + n, []) if start <= a and a + d <= start + dur]
        assert {n for n, _ in inside} == set(PHASES), inside
        assert sum(d for _, d in inside) >= 0.95 * dur, (inside, dur)


@pytest.mark.parametrize("name", ["gather"] + list(PHASES))
def test_every_mpp_span_carries_the_statements_id(profiled, name):
    stmts = {st["stmt"] for _, _, st in profiled["statement"]}
    mine = profiled["mpp." + name]
    assert len(mine) >= 2 and all(st.get("stmt") in stmts for _, _, st in mine), mine
    assert len({st["stmt"] for _, _, st in mine}) == 2


def test_what_the_spans_say(profiled):
    for _, _, st in profiled["mpp.gather"]:
        assert int(st["ndev"]) >= 1 and int(st["readers"]) == 3 and int(st["retries"]) == 0
    for _, _, st in profiled["mpp.lanes"]:
        # everything resident after the warm-up; padded to the next 2^k of each table's own rows a shard
        assert st["cache"] == "hit" and int(st["h2d"]) == 0
        assert int(st["rows_valid"]) <= int(st["rows_padded"]) < 2 * int(st["rows_valid"]) + 8 * 3 * int(profiled["mpp.gather"][0][2]["ndev"])
    assert {st["cache"] for _, _, st in profiled["mpp.program"]} == {"hit"}
    assert {st["kernel"] for _, _, st in profiled["mpp.dispatch"]} == {"mpp_j2_agg_g3"}
    assert all(int(st["groups"]) > 10 for _, _, st in profiled["mpp.merge"])
    for _, _, st in profiled["mpp.fetch"]:
        # (PR 34) the padded probe rows of both folds (lineitem's ~64k, orders' ~16k), all answered by blocks at this scale
        assert int(st["probe_rows_blocked"]) == int(st["probe_rows"]) >= 75_000


def test_rehearsal_of_the_cell_is_correct_and_prints_its_metrics(tmp_path):
    """`tpch_sf2_mpp.q3_1c` as the driver runs it, on the CPU at SF 0.01: the
    turned-round form of `benchmark/tests/test_join_cell_is_files_only.py`'s
    `test_q3_is_answered_right_and_today_off_the_device`."""
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "tpch_sf2_mpp.q3_1c", "--seed", "2147483777",
           "--seconds", "2", "--trace", "1", "--platform", "cpu", "--scale", str(SF)]
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=1", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] >= 4, line
    assert {k: c["value"] for k, c in line["checks"].items()} == {"answers_wrong": 0, "statements_failed": 0, "not_on_device": 0}
    m = line["metrics"]
    # every new per-layer metric but `mpp_kernel_ms`, which reads the TPU's `XLA Modules` line: no such line here
    for name in ("mpp_gather_p50_ms", "mpp_lanes_ms", "mpp_dispatch_ms", "mpp_fetch_ms", "mpp_merge_ms", "mpp_padded_ratio", "mpp_probe_blocked_pct"):
        assert name in m, sorted(m)
    assert m["mpp_probe_blocked_pct"]["value"] == pytest.approx(100.0)  # at SF2 80: `customer`'s codes then span 73 rows of the bitmap, the arm gathers
    assert "mpp_kernel_ms" not in m and "scan_roofline" not in m  # no chip, no kernel time, no share
    assert m["compiles_in_window"]["value"] == 0 and 1.0 <= m["mpp_padded_ratio"]["value"] <= 2.0
    phases = sum(m[n]["value"] for n in ("mpp_lanes_ms", "mpp_dispatch_ms", "mpp_fetch_ms", "mpp_merge_ms"))
    assert 0 < phases <= 1.05 * m["mpp_gather_p50_ms"]["value"] * 1.5  # sums over statements against a median: loosely
    assert any(label == "mpp_gather" for label, _ in line["breakdown"]["idle_gaps"])


@pytest.mark.parametrize("chips,cell", [(1, "tpch_sf2_mpp.q3_1c"), (4, "tpch_sf2_mpp4.q3_1c")])
def test_gathers_on_fewer_devices_than_the_cell_asks_for_come_out_not_on_device(tmp_path, chips, cell):
    """The benchmark's own fault `mpp_fewer_devices` (`benchmark/tests/faults.py`)
    under the two shipped Q3 cells, as `BENCHMARK.json` holds them: the one-chip
    `tpch_sf2_mpp.q3_1c` and the four-chip `tpch_sf2_mpp4.q3_1c` (PR 33), in a
    copy of the checkout's benchmark. One chip: none is left, the gather gives
    up and the host executor answers. Four: the gather runs on three. Answers
    right, `correct` false. Tier-1's carrier of
    `benchmark/tests/test_faults.py::test_gathers_on_fewer_devices...`, whose
    helper cannot build its two-table cell while `mpp_gather_p50_ms.py` is a
    file of the benchmark's (PERF.md section 7 a)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__", "test_*", "recorded_*"))
    os.symlink(os.path.join(ROOT, "tidb_tpu"), root / "tidb_tpu")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    with open(root / "BENCHMARK.json") as f:
        assert {w["name"]: w["chips"] for w in json.load(f)["workloads"]}[cell] == chips

    def drive(*entry):
        cmd = [sys.executable, *entry, "--workload", cell, "--seed", "2147483777", "--seconds", "2", "--trace", "0",
               "--platform", "cpu", "--scale", str(SF)]
        env = dict(os.environ, XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
        p = subprocess.run(cmd, capture_output=True, text=True, timeout=600, cwd=root, env=env)
        assert p.returncode == 0, p.stderr[-3000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    if chips > 1:  # the sound drive first: a gather over the cell's four devices is on the device
        line = drive(str(root / "benchmark/run.py"))
        assert line["correct"] is True and line["device"]["count"] == chips and line["attempted"] >= 3, line
    line = drive(str(root / "benchmark/tests/faults.py"), "mpp_fewer_devices")
    assert line["correct"] is False and line["attempted"] >= 3, line
    checks = {k: c["value"] for k, c in line["checks"].items()}
    assert checks == {"answers_wrong": 0, "statements_failed": 0, "not_on_device": line["attempted"]}


def test_a_gather_as_a_fresh_process_s_first_device_statement():
    """Nothing has called `dag_kernel._ensure_x64()` when a process's first
    device statement is a gather: `_segment_partial` then raised `OverflowError:
    Python integer 4294967296 out of bounds for int32`. The gather calls it."""
    code = """
import sys
sys.path.insert(0, %r)
import tidb_tpu
db = tidb_tpu.open()
db.execute("CREATE TABLE o (k BIGINT PRIMARY KEY, d BIGINT)")
db.execute("CREATE TABLE l (k BIGINT, p BIGINT)")
db.execute("INSERT INTO o VALUES (1, 10), (2, 20), (3, 4294967296)")
db.execute("INSERT INTO l VALUES (1, 5), (1, 6), (3, 4294967296), (4, 1)")
s = db.session()
s.execute("SET tidb_enforce_mpp = 1")
rows = s.query("SELECT o.d, SUM(l.p) FROM l JOIN o ON l.k = o.k GROUP BY o.d ORDER BY o.d")
assert len(s.mpp_details) == 1, s.mpp_details
print([tuple(int(c) for c in r) for r in rows])
""" % ROOT
    env = {k: v for k, v in os.environ.items() if k != "JAX_ENABLE_X64"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1] == "[(10, 11), (4294967296, 4294967296)]"
