"""Distributed MPP pipeline tests on the virtual 8-device CPU mesh: shuffle
and broadcast joins, join+agg, and the SQL-integrated MPPGather path
(ref: §3.3 MPP query path; exchanges ride collectives, not gRPC)."""

import numpy as np
import pytest

import tidb_tpu
from tidb_tpu.parallel import make_mesh
from tidb_tpu.parallel.mpp import (
    DistAggSpec,
    DistJoinSpec,
    build_dist_join_agg,
    finalize_dist_agg,
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


@pytest.mark.parametrize("exchange", ["hash", "broadcast"])
def test_dist_join_agg_matches_oracle(mesh, exchange):
    import jax.numpy as jnp

    ndev = mesh.devices.size
    nl, nr = ndev * 512, ndev * 64
    rng = np.random.default_rng(3)
    l_cid = rng.integers(0, nr, nl)
    l_qty = rng.integers(1, 10, nl)
    r_id = np.arange(nr)
    rng.shuffle(r_id)
    r_cat = rng.integers(0, 5, nr)

    join = DistJoinSpec(left_keys=[0], right_keys=[0], exchange=exchange, row_cap=2048)
    agg = DistAggSpec(n_keys=1, sums=[1], group_cap=64)
    fn = build_dist_join_agg(
        mesh,
        join,
        agg,
        n_left=2,
        n_right=2,
        left_selection=lambda cid, qty: qty > 2,
        agg_inputs=lambda cols: [cols[3], cols[1]],
    )
    outs = fn(jnp.asarray(l_cid), jnp.asarray(l_qty), jnp.asarray(r_id), jnp.asarray(r_cat))
    keys, sums, cnt, total = finalize_dist_agg(outs[:-2], 1, 1)
    assert int(np.asarray(outs[-2])) == 0  # no rows dropped
    assert int(np.asarray(outs[-1])) == 0  # no group overflow

    cat_of = np.zeros(nr, dtype=np.int64)
    cat_of[r_id] = r_cat
    mask = l_qty > 2
    ref: dict = {}
    for cid, qty in zip(l_cid[mask], l_qty[mask]):
        c = int(cat_of[cid])
        s, n = ref.get(c, (0, 0))
        ref[c] = (s + int(qty), n + 1)
    got = {int(keys[0][i]): (int(sums[0][i]), int(cnt[i])) for i in range(len(cnt))}
    assert got == ref
    assert int(total) == int(mask.sum())


def test_route_rows_overflow_reported(mesh):
    import jax.numpy as jnp

    ndev = mesh.devices.size
    nl = ndev * 128
    # every left row joins dim id 0 → all rows shuffle to one owner
    l_cid = np.zeros(nl, dtype=np.int64)
    l_qty = np.ones(nl, dtype=np.int64)
    r_id = np.arange(ndev * 8)
    r_cat = np.zeros(ndev * 8, dtype=np.int64)
    join = DistJoinSpec(left_keys=[0], right_keys=[0], exchange="hash", row_cap=16)
    agg = DistAggSpec(n_keys=1, sums=[1], group_cap=16)
    fn = build_dist_join_agg(
        mesh, join, agg, n_left=2, n_right=2, agg_inputs=lambda cols: [cols[3], cols[1]]
    )
    outs = fn(jnp.asarray(l_cid), jnp.asarray(l_qty), jnp.asarray(r_id), jnp.asarray(r_cat))
    assert int(np.asarray(outs[-2])) > 0  # dropped rows are REPORTED


@pytest.fixture()
def sqldb():
    d = tidb_tpu.open()
    d.execute("CREATE TABLE fact (cid BIGINT, qty BIGINT, price DECIMAL(10,2))")
    d.execute("CREATE TABLE dim (id BIGINT PRIMARY KEY, cat VARCHAR(8))")
    import random

    random.seed(7)
    d.execute("INSERT INTO dim VALUES " + ",".join(f"({i},'c{i % 5}')" for i in range(40)))
    d.execute(
        "INSERT INTO fact VALUES "
        + ",".join(
            f"({random.randint(0, 39)},{random.randint(1, 9)},{random.randint(100, 999) / 100})"
            for _ in range(500)
        )
    )
    return d


MPPQ = (
    "SELECT cat, COUNT(*), SUM(qty), AVG(price) FROM fact JOIN dim ON fact.cid = dim.id"
    " WHERE qty > 2 GROUP BY cat ORDER BY cat"
)


def test_sql_mpp_gather_matches_host(sqldb):
    s = sqldb.session()
    mpp = s.execute(MPPQ).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(MPPQ).rows
    assert mpp == host and len(mpp) == 5


def test_sql_mpp_explain_shows_fragments(sqldb):
    lines = "\n".join(r[0] for r in sqldb.query("EXPLAIN " + MPPQ))
    assert "PhysMPPGather" in lines and "Fragment#" in lines


def test_mpp_rewrite_requires_unique_build_side(sqldb):
    # join on a non-unique dim column must stay on the host join
    lines = "\n".join(
        r[0]
        for r in sqldb.query(
            "EXPLAIN SELECT COUNT(*) FROM fact JOIN dim ON fact.qty = dim.id + 0 GROUP BY fact.cid"
        )
    )
    assert "PhysMPPGather" not in lines


def test_mpp_with_nulls(sqldb):
    sqldb.execute("INSERT INTO fact VALUES (NULL, 5, 1.00), (3, NULL, 2.00)")
    s = sqldb.session()
    mpp = s.execute(MPPQ).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(MPPQ).rows
    assert mpp == host


def test_sql_hash_exchange_path(sqldb, monkeypatch):
    """Force the shuffle (hash) exchange and the grow-on-overflow retry."""
    from tidb_tpu.parallel import gather

    monkeypatch.setattr(gather, "FORCE_EXCHANGE", "hash")
    sqldb.execute("ANALYZE TABLE dim")  # stats present → threshold applies
    s = sqldb.session()
    lines = "\n".join(r[0] for r in s.execute("EXPLAIN " + MPPQ).rows)
    assert "hash join exchange" in lines
    mpp = s.execute(MPPQ).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(MPPQ).rows
    assert mpp == host


def test_sql_mpp_overflow_retry(sqldb, monkeypatch):
    """A skewed join key overflows the initial row_cap; the coordinator must
    retry with a bigger capacity and still return exact results."""
    from tidb_tpu.parallel import gather
    from tidb_tpu.parallel.mpp import DistJoinSpec

    monkeypatch.setattr(gather, "FORCE_EXCHANGE", "hash")
    sqldb.execute("ANALYZE TABLE dim")
    # all fact rows point at one dim id → every row shuffles to one owner
    sqldb.execute("CREATE TABLE skew (cid BIGINT, qty BIGINT)")
    sqldb.execute("INSERT INTO skew VALUES " + ",".join("(7, 1)" for _ in range(300)))
    s = sqldb.session()
    q = "SELECT cat, COUNT(*) FROM skew JOIN dim ON skew.cid = dim.id GROUP BY cat"
    mpp = s.execute(q).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(q).rows
    assert mpp == host == [("c2", 300)]


def test_enforce_mpp_single_table(sqldb):
    s = sqldb.session()
    s.execute("SET tidb_enforce_mpp = 1")
    q = "SELECT cid, COUNT(*), SUM(qty) FROM fact GROUP BY cid ORDER BY cid"
    lines = "\n".join(r[0] for r in s.execute("EXPLAIN " + q).rows)
    assert "PhysMPPGather" in lines
    mpp = s.execute(q).rows
    s.execute("SET tidb_enforce_mpp = 0")
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(q).rows
    assert mpp == host


def test_sql_mpp_scalar_aggregate(sqldb):
    """Scalar (no GROUP BY) aggregates over an MPP join must match the host
    path — the pipeline routes them through a synthetic constant group key."""
    q = "SELECT COUNT(*), SUM(qty) FROM fact JOIN dim ON fact.cid = dim.id"
    s = sqldb.session()
    lines = "\n".join(r[0] for r in s.execute("EXPLAIN " + q).rows)
    assert "PhysMPPGather" in lines
    mpp = s.execute(q).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(q).rows
    assert mpp == host


def test_sql_mpp_scalar_aggregate_single_table(sqldb):
    s = sqldb.session()
    s.execute("SET tidb_enforce_mpp = 1")
    q = "SELECT COUNT(*), SUM(qty), AVG(qty) FROM fact WHERE qty > 2"
    mpp = s.execute(q).rows
    s.execute("SET tidb_enforce_mpp = 0")
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(q).rows
    assert mpp == host


@pytest.fixture()
def q3db():
    """Three-table TPC-H Q3 shape: customer ⋈ orders ⋈ lineitem — orders is
    NON-unique from lineitem's perspective chain and lineitem joins orders on
    a unique PK while orders→customer fans out (non-unique probe-side chain)."""
    d = tidb_tpu.open()
    d.execute("CREATE TABLE customer (c_custkey BIGINT PRIMARY KEY, c_mktsegment BIGINT)")
    d.execute("CREATE TABLE orders (o_orderkey BIGINT PRIMARY KEY, o_custkey BIGINT, o_odate BIGINT)")
    d.execute("CREATE TABLE lineitem (l_orderkey BIGINT, l_extendedprice DECIMAL(10,2))")
    import random

    random.seed(11)
    d.execute("INSERT INTO customer VALUES " + ",".join(f"({i},{i % 3})" for i in range(30)))
    d.execute(
        "INSERT INTO orders VALUES "
        + ",".join(f"({i},{random.randint(0, 29)},{8000 + i % 50})" for i in range(200))
    )
    d.execute(
        "INSERT INTO lineitem VALUES "
        + ",".join(f"({random.randint(0, 199)},{random.randint(100, 99999) / 100})" for _ in range(1500))
    )
    for t in ("customer", "orders", "lineitem"):
        d.execute(f"ANALYZE TABLE {t}")
    return d


Q3FULL = (
    "SELECT o_odate, SUM(l_extendedprice) AS rev FROM lineitem"
    " JOIN orders ON l_orderkey = o_orderkey"
    " JOIN customer ON o_custkey = c_custkey"
    " WHERE c_mktsegment = 1 GROUP BY o_odate ORDER BY rev DESC, o_odate LIMIT 10"
)


def test_mpp_two_join_chain_full_q3(q3db):
    """The full Q3 join tree (2 joins, 3 readers) compiles into one mesh
    program (ref: fragment trees with multiple exchanges, mpp_exec.go)."""
    s = q3db.session()
    lines = "\n".join(r[0] for r in s.execute("EXPLAIN " + Q3FULL).rows)
    assert "PhysMPPGather" in lines
    assert lines.count("Join") >= 2
    mpp = s.execute(Q3FULL).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(Q3FULL).rows
    assert mpp == host and len(mpp) == 10


def test_mpp_input_lanes_are_staged_sharded_over_the_mesh(q3db):
    """Pooled (and whole-reader) input lanes land row-sharded on the mesh's
    devices when they are staged — one shard per device, once — instead of
    whole on device 0 for the shard_map program to re-scatter every call."""
    from tidb_tpu.parallel import gather

    with gather._MPP_CACHE_MU:
        gather._MPP_DEV_CACHE.clear()
    s = q3db.session()
    q = "SELECT o_odate, COUNT(*) FROM lineitem JOIN orders ON l_orderkey = o_orderkey GROUP BY o_odate"
    lines = "\n".join(r[0] for r in s.execute("EXPLAIN " + q).rows)
    assert "PhysMPPGather" in lines
    s.execute(q)
    ndev = make_mesh().devices.size
    lanes = []
    for ent in gather._MPP_DEV_CACHE.values():
        if isinstance(ent, dict):  # per-column pool of a plain reader
            lanes.append(ent["live"])
            lanes += [a for d, v, *_ in ent["cols"].values() for a in (d, v)]
        else:  # whole-reader entry: (lanes, n, bounds)
            lanes += list(ent[0])
    assert lanes
    for a in lanes:
        assert len(a.sharding.device_set) == ndev
        shards = a.addressable_shards
        assert len(shards) == ndev and {sh.data.shape[0] for sh in shards} == {a.shape[0] // ndev}
    # the cache identity carries the mesh's device ids, so a gather on a
    # different device set can never be handed lanes committed elsewhere
    ids = tuple(int(d.id) for d in make_mesh().devices.flat)
    assert all(ids in k for k in gather._MPP_DEV_CACHE)


def test_mpp_non_unique_build_side(q3db):
    """Build side with duplicate keys → expansion join (each probe row fans
    out to its match count), not a host fallback."""
    q3db.execute("CREATE TABLE tags (okey BIGINT, tag BIGINT)")
    # duplicate keys: each order key appears 0..3 times
    import random

    random.seed(3)
    q3db.execute(
        "INSERT INTO tags VALUES "
        + ",".join(f"({random.randint(0, 199)},{i % 7})" for i in range(400))
    )
    q3db.execute("ANALYZE TABLE tags")
    q = (
        "SELECT tag, COUNT(*), SUM(o_odate) FROM orders JOIN tags ON o_orderkey = okey"
        " GROUP BY tag ORDER BY tag"
    )
    s = q3db.session()
    lines = "\n".join(r[0] for r in s.execute("EXPLAIN " + q).rows)
    assert "PhysMPPGather" in lines
    mpp = s.execute(q).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(q).rows
    assert mpp == host and len(mpp) == 7


def test_mpp_non_unique_overflow_retry(q3db):
    """Expansion capacity overflow (forced by data volume: 10k joined rows
    against the initial per-shard cap) is detected and retried bigger."""
    q3db.execute("CREATE TABLE dup (k BIGINT, v BIGINT)")
    q3db.execute("INSERT INTO dup VALUES " + ",".join(f"(7,{i})" for i in range(200)))
    q3db.execute("CREATE TABLE probe (k BIGINT)")
    q3db.execute("INSERT INTO probe VALUES " + ",".join("(7)" for _ in range(50)))
    q3db.execute("ANALYZE TABLE dup")
    q3db.execute("ANALYZE TABLE probe")
    # 50 probes × 200 matches = 10k joined rows per shard-set: overflows the
    # initial per-shard cap and must grow
    q = "SELECT COUNT(*) FROM probe JOIN dup ON probe.k = dup.k"
    s = q3db.session()
    mpp = s.execute(q).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(q).rows
    assert mpp == host == [(10000,)]


def test_mpp_topn_over_join(q3db):
    """TopN over a join chain runs per-shard heads inside the fragment (ref:
    TopN in mpp_exec.go fragments), root-merged."""
    q = (
        "SELECT o_odate, l_extendedprice FROM lineitem JOIN orders ON l_orderkey = o_orderkey"
        " ORDER BY l_extendedprice DESC LIMIT 7"
    )
    s = q3db.session()
    lines = "\n".join(r[0] for r in s.execute("EXPLAIN " + q).rows)
    assert "PhysMPPGather" in lines and "TopN" in lines
    mpp = s.execute(q).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(q).rows
    assert mpp == host and len(mpp) == 7


def test_mpp_limit_over_join(q3db):
    q = "SELECT o_odate FROM lineitem JOIN orders ON l_orderkey = o_orderkey LIMIT 9"
    s = q3db.session()
    mpp = s.execute(q).rows
    s.execute("SET tidb_allow_mpp = 0")
    host = s.execute(q).rows
    assert len(mpp) == len(host) == 9
