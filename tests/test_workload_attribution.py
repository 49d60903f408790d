"""Workload attribution (ISSUE 20): per-statement RU metering assembled from
the exec-details sidecars + write-side 2PC accounting, folded into per-group
usage (``information_schema.resource_group_usage``), the keyspace traffic
heatmap built from the store-side rings (``keyspace_heatmap`` /
``cluster_keyspace_heatmap`` / ``GET /keyviz``), the balancer consuming
MEASURED traffic instead of the cop-digest heuristic, and the DRYRUN
observational runaway checker.

Acceptance: on a 3-store fleet with two concurrent sessions in different
resource groups, ``resource_group_usage`` splits the RUs within ±10% of the
per-statement sums; ``keyspace_heatmap`` names the hottest region of an
induced skew; a region migration mid-workload attributes post-cutover
traffic to the new owner with no double-count on the boRegionMiss re-route.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

import tidb_tpu
from tidb_tpu.kv import tablecodec
from tidb_tpu.kv.memstore import MemStore, Mutation, OP_PUT
from tidb_tpu.kv.sharded import ShardedStore
from tidb_tpu.session.session import DB
from tidb_tpu.utils import eventlog as _ev


def _fleet(n=3):
    return ShardedStore([MemStore(region_split_keys=100_000) for _ in range(n)])


def _mkdb(fleet):
    db = DB(store=fleet)
    return db, db.session()


@pytest.fixture
def fresh_log():
    _ev.reset()
    yield
    _ev.reset()


# -- per-group RU accounting --------------------------------------------------


def test_ru_split_across_groups_matches_statement_sums():
    """The acceptance split: two concurrent sessions in different groups on
    a 3-store fleet; resource_group_usage's RU per group lands within ±10%
    of the per-statement sums the statements summary recorded."""
    db, s = _mkdb(_fleet())
    db.execute("CREATE RESOURCE GROUP ra RU_PER_SEC = 0")
    db.execute("CREATE RESOURCE GROUP rb RU_PER_SEC = 0")
    # distinct tables per group → distinct digests, so the summary's
    # per-digest RESOURCE_GROUP attribution never mixes the two tenants
    for name in ("wa", "wb"):
        s.execute(f"CREATE TABLE {name} (id BIGINT PRIMARY KEY, v BIGINT)")
        s.execute(
            f"INSERT INTO {name} VALUES " + ",".join(f"({i},{i})" for i in range(200))
        )

    def tenant(group, table, n):
        st = db.session()
        st.execute(f"SET RESOURCE GROUP {group}")
        for _ in range(n):
            st.query(f"SELECT SUM(v) FROM {table}")

    ta = threading.Thread(target=tenant, args=("ra", "wa", 20))
    tb = threading.Thread(target=tenant, args=("rb", "wb", 8))
    ta.start()
    tb.start()
    ta.join()
    tb.join()

    usage = {
        r[0]: (r[1], r[2])
        for r in s.query(
            "SELECT RESOURCE_GROUP, STATEMENTS, RU "
            "FROM information_schema.resource_group_usage"
        )
    }
    assert "ra" in usage and "rb" in usage and "default" in usage
    assert usage["ra"][1] > usage["rb"][1] > 0, "20 queries must out-consume 8"

    by_group = {}
    for grp, sum_ru in s.query(
        "SELECT RESOURCE_GROUP, SUM_RU FROM information_schema.statements_summary"
    ):
        by_group[grp] = by_group.get(grp, 0.0) + sum_ru
    for grp in ("ra", "rb"):
        assert by_group.get(grp, 0.0) > 0
        assert usage[grp][1] == pytest.approx(by_group[grp], rel=0.10), (
            f"group {grp}: cumulative usage {usage[grp][1]} vs "
            f"statement sums {by_group[grp]}"
        )


def test_ru_breakdown_columns_and_write_accounting():
    """resource_group_usage carries the full ResourceUsage breakdown, and
    the write side (prewrite key counts riding the response headers) lands
    as keys_written/WRU for the writing group."""
    db, s = _mkdb(_fleet())
    db.execute("CREATE RESOURCE GROUP wg RU_PER_SEC = 0")
    s.execute("CREATE TABLE ww (id BIGINT PRIMARY KEY, v BIGINT)")
    sw = db.session()
    sw.execute("SET RESOURCE GROUP wg")
    sw.execute("INSERT INTO ww VALUES " + ",".join(f"({i},{i})" for i in range(50)))
    rows = s.query(
        "SELECT RESOURCE_GROUP, RU, RRU, WRU, KEYS_WRITTEN, BYTES_WRITTEN, "
        "KEYS_SCANNED, COP_RPCS, ROWS_RETURNED "
        "FROM information_schema.resource_group_usage"
    )
    got = {r[0]: r for r in rows}
    g = got["wg"]
    assert g[4] >= 50, f"50 inserted rows must be counted as keys written: {g}"
    assert g[3] > 0 and g[5] > 0, "write RUs and bytes must be non-zero"
    assert g[1] == pytest.approx(g[2] + g[3], rel=1e-6), "RU = RRU + WRU"
    # and the read side shows scan volume for a scanning group
    sw.query("SELECT SUM(v) FROM ww")
    g2 = {
        r[0]: r
        for r in s.query(
            "SELECT RESOURCE_GROUP, RU, RRU, WRU, KEYS_WRITTEN, BYTES_WRITTEN, "
            "KEYS_SCANNED, COP_RPCS, ROWS_RETURNED "
            "FROM information_schema.resource_group_usage"
        )
    }["wg"]
    assert g2[6] >= 50 and g2[7] >= 1 and g2[8] >= 1


def test_explain_analyze_reports_ru():
    db = tidb_tpu.open()
    db.execute("CREATE TABLE ea (id BIGINT PRIMARY KEY, v BIGINT)")
    db.execute("INSERT INTO ea VALUES (1, 10), (2, 20)")
    s = db.session()
    rows = s.execute("EXPLAIN ANALYZE SELECT SUM(v) FROM ea").rows
    text = "\n".join(r[0] for r in rows)
    assert "ru:" in text, f"EXPLAIN ANALYZE must report the statement's RUs:\n{text}"


def test_slow_log_and_top_sql_carry_ru():
    db, s = _mkdb(_fleet())
    db.execute("CREATE RESOURCE GROUP tz RU_PER_SEC = 0")
    st = db.session()
    st.execute("SET RESOURCE GROUP tz")
    st.execute("SET tidb_slow_log_threshold = 0")  # everything is slow now
    st.execute("CREATE TABLE sl (id BIGINT PRIMARY KEY, v BIGINT)")
    st.execute("INSERT INTO sl VALUES " + ",".join(f"({i},{i})" for i in range(100)))
    for _ in range(5):
        st.query("SELECT SUM(v) FROM sl")
    rows = s.query(
        "SELECT QUERY, RU, RESOURCE_GROUP FROM information_schema.slow_query"
    )
    ours = [r for r in rows if "FROM sl" in r[0] and "SUM" in r[0]]
    assert ours and any(r[1] > 0 for r in ours)
    assert all(r[2] == "tz" for r in ours)
    st.execute("SET tidb_enable_top_sql = 1")
    deadline = time.time() + 10
    mine = []
    while time.time() < deadline and not mine:
        for _ in range(5):
            st.query("SELECT SUM(v) FROM sl")
        ts = s.query(
            "SELECT QUERY_SAMPLE_TEXT, RU FROM information_schema.tidb_top_sql"
        )
        mine = [r for r in ts if "FROM sl" in r[0] and r[1] > 0]
    assert mine, "Top-SQL must rank RUs alongside CPU"


# -- the keyspace traffic heatmap ---------------------------------------------


def test_keyspace_heatmap_names_hottest_region():
    """Induced skew: one hammered table out of three must own the hottest
    heatmap row — including when every serve is a device-cache hit (the
    cop-serve seam, not just the MVCC build seams)."""
    db, s = _mkdb(_fleet())
    for name in ("hc0", "hc1", "hc2"):
        s.execute(f"CREATE TABLE {name} (id BIGINT PRIMARY KEY, v BIGINT)")
        s.execute(
            f"INSERT INTO {name} VALUES " + ",".join(f"({i},{i})" for i in range(300))
        )
        s.query(f"SELECT SUM(v) FROM {name}")  # touch every table once
    for _ in range(30):  # the skew: warm, cache-served
        s.query("SELECT SUM(v) FROM hc1")
    rows = s.query(
        "SELECT INSTANCE, REGION_ID, TABLE_NAME, READ_KEYS "
        "FROM information_schema.keyspace_heatmap"
    )
    assert rows, "heatmap must have rows after traffic"
    hottest = max(rows, key=lambda r: r[3])
    assert hottest[2] == "test.hc1", f"hottest region must belong to hc1: {rows}"
    assert hottest[3] >= 30 * 300, "every warm serve counts, not just cold builds"
    # the per-bucket view carries timestamps and the same attribution
    brows = s.query(
        "SELECT TABLE_NAME, BUCKET_TS, READ_KEYS "
        "FROM information_schema.cluster_keyspace_heatmap"
    )
    assert any(r[0] == "test.hc1" and r[1] > 0 and r[2] > 0 for r in brows)


def test_keyviz_endpoint():
    from tidb_tpu.server.status import StatusServer

    db, s = _mkdb(_fleet())
    s.execute("CREATE TABLE kv1 (id BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO kv1 VALUES " + ",".join(f"({i},{i})" for i in range(50)))
    s.query("SELECT SUM(v) FROM kv1")
    tid = db.catalog.table("test", "kv1").id
    st = StatusServer(db, port=0)
    port = st.start()
    try:
        body = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port}/keyviz").read()
        )
        ents = body["instances"]
        assert ents and all(e["ok"] for e in ents)
        tids = {
            h["table_id"] for e in ents for h in e["heatmap"]
        }
        assert tid in tids, f"the scanned table must appear in /keyviz: {body}"
        # a zero-second window empties the buckets but not the handler
        body2 = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/keyviz?seconds=0"
            ).read()
        )
        assert all(
            not h["buckets"]
            for e in body2["instances"]
            for h in e.get("heatmap", ())
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/keyviz?seconds=bogus")
        assert ei.value.code == 400
    finally:
        st.close()


def test_balancer_weights_follow_measured_traffic():
    """The hot boost is the heatmap now: a hammered table's placement
    weight must exceed an equal-rowcount cold table's by the measured key
    traffic (the convergence acceptance lives in test_placement's
    test_balancer_embedded_hot_table_signal_converges)."""
    from tidb_tpu.kv.placement import _shard_weights

    db, s = _mkdb(_fleet())
    for name in ("bw0", "bw1"):
        s.execute(f"CREATE TABLE {name} (id BIGINT PRIMARY KEY, v BIGINT)")
        s.execute(
            f"INSERT INTO {name} VALUES " + ",".join(f"({i},{i})" for i in range(200))
        )
    s.execute("ANALYZE TABLE bw0")
    s.execute("ANALYZE TABLE bw1")
    for _ in range(20):
        s.query("SELECT SUM(v) FROM bw1")
    _w, tables = _shard_weights(db, db.store)
    by_name = {name: w for (w, _tid, _si, name) in tables}
    assert by_name["test.bw1"] > by_name["test.bw0"] + 1000, (
        f"measured traffic must dominate the hot table's weight: {by_name}"
    )


# -- migration attribution ----------------------------------------------------


def test_migration_attributes_post_cutover_traffic_to_new_owner():
    """Mid-workload region migration: reads after the cutover land on the
    NEW owner's rings; the fenced ex-owner's totals freeze."""
    stores = [MemStore(region_split_keys=100_000) for _ in range(3)]
    fleet = ShardedStore(stores)
    db, s = _mkdb(fleet)
    s.execute("CREATE TABLE mg (id BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO mg VALUES " + ",".join(f"({i},{i})" for i in range(200)))
    tid = db.catalog.table("test", "mg").id
    src = fleet.shard_of_table(tid)
    dst = (src + 1) % 3

    def read_keys(i):
        return sum(
            sum(b[1] for b in e["buckets"])
            for e in stores[i].traffic.snapshot()
            if e["table_id"] == tid
        )

    s.query("SELECT SUM(v) FROM mg")
    assert read_keys(src) >= 200, "pre-move traffic belongs to the source"

    fleet.migrate_table(tid, dst)
    pre_dst = read_keys(dst)
    for _ in range(3):
        s.query("SELECT SUM(v) FROM mg")  # re-routes, then serves warm
    assert read_keys(dst) >= pre_dst + 3 * 200, (
        "post-cutover serves must be attributed to the new owner"
    )
    # the migration purge forgets the ex-owner's rings for the table —
    # post-cutover the heatmap shows ONE owner, never a split attribution
    assert read_keys(src) == 0, "the fenced ex-owner's rings must be purged"


def test_2pc_reroute_commit_counts_writes_once():
    """The no-double-count acceptance: a txn that prewrote before the move
    commits after it through a stale client — the boRegionMiss re-route
    lands the commit exactly once in the write traffic AND the group's
    keys_written."""
    stores = [MemStore(region_split_keys=100_000) for _ in range(3)]
    fleet_a = ShardedStore(stores)
    db, s = _mkdb(fleet_a)
    s.execute("CREATE TABLE rr (id BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO rr VALUES (1, 1)")
    tid = db.catalog.table("test", "rr").id
    src = fleet_a.shard_of_table(tid)

    def write_keys_everywhere():
        return sum(
            sum(b[3] for b in e["buckets"])
            for st in stores
            for e in st.traffic.snapshot()
            if e["table_id"] == tid
        )

    fleet_b = ShardedStore(stores)  # the txn's client; cache goes stale
    k = tablecodec.record_key(tid, 777)
    start_ts = fleet_b.tso.ts()
    fleet_b.prewrite([Mutation(OP_PUT, k, b"vv")], k, start_ts)

    fleet_a.migrate_table(tid, (src + 1) % 3)
    before = write_keys_everywhere()  # post-purge baseline: dst rings only
    commit_ts = fleet_b.tso.ts()
    fleet_b.commit([k], start_ts, commit_ts)  # re-routes; migrated lock found
    assert fleet_b.get_snapshot(fleet_b.tso.ts()).get(k) == b"vv"
    assert write_keys_everywhere() == before + 1, (
        "the re-routed commit must be counted exactly once across the fleet"
    )
    # a replayed commit (the client retrying after a lost reply) is the
    # idempotent re-commit path: zero additional write accounting
    fleet_b.commit([k], start_ts, commit_ts)
    assert write_keys_everywhere() == before + 1


# -- the observational runaway checker ---------------------------------------


def test_runaway_dryrun_records_without_enforcement(fresh_log):
    """DRYRUN arms the same per-statement deadline as KILL but only
    observes: the query completes, a RunawayRecord lands in
    runaway_watches, and a ``resourcegroup.runaway`` WARN event is
    emitted — no kill, no cooldown."""
    db = tidb_tpu.open()
    db.execute("CREATE TABLE rt (a BIGINT)")
    db.execute("INSERT INTO rt VALUES (1), (2), (3)")
    db.execute(
        "CREATE RESOURCE GROUP rd RU_PER_SEC = 0 "
        "QUERY_LIMIT = (EXEC_ELAPSED = '0.0001ms', ACTION = DRYRUN)"
    )
    s = db.session()
    s.execute("SET RESOURCE GROUP rd")

    def records():
        return [
            r
            for r in db.query(
                "SELECT resource_group_name, action "
                "FROM information_schema.runaway_watches"
            )
            if r == ("rd", "DRYRUN")
        ]

    n0 = len(records())
    assert s.query("SELECT COUNT(*) FROM rt") == [(3,)]  # NOT killed
    assert len(records()) == n0 + 1, (
        "one statement must yield exactly one runaway record, even though "
        "both the mid-query deadline and the post-statement check saw the "
        "breach"
    )
    lg = _ev.on(_ev.WARN)
    assert lg is not None
    evs = lg.search(component="resourcegroup")
    assert any(e[3] == "runaway" and e[4].get("group") == "rd" for e in evs), (
        f"a WARN event must name the runaway group: {evs}"
    )
