"""int8 MXU dot grouped sums (ops/mxu_groupby.py) + fused multi-block agg
dispatch — exactness vs the numpy oracle and host-engine parity with the
dot path forced."""

import numpy as np
import pytest

import tidb_tpu
from tidb_tpu.copr import tpu_engine
from tidb_tpu.executor.load import bulk_load
from tidb_tpu.ops import dag_kernel
from tidb_tpu.ops.mxu_groupby import grouped_sums_dot


def np_reference(seg, pairs, B):
    """NumPy oracle: exact grouped COUNT/SUM per (value, weight) lane."""
    L = len(pairs)
    counts = np.zeros((B, L), dtype=np.int64)
    sums = np.zeros((B, L), dtype=np.int64)
    for k, (vals, w) in enumerate(pairs):
        for b in range(B):
            m = (np.asarray(seg) == b) & np.asarray(w)
            counts[b, k] = int(m.sum())
            sums[b, k] = int(np.asarray(vals)[m].sum()) if m.any() else 0
    return counts, sums


def test_dot_exact_vs_oracle():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    n, B = 70_000, 11
    seg = jnp.asarray(rng.integers(0, B + 3, n).astype(np.int32))
    specs = [
        (rng.integers(-5000, 9_000_000, n), (-5000, 9_000_000)),
        (rng.integers(0, 11, n), (0, 10)),
        (rng.integers(-(2**40), 2**40, n), (-(2**40), 2**40)),
        (np.zeros(n, dtype=np.int64), (0, 0)),  # count lane
        (rng.integers(-(2**31) + 1, 2**31 - 1, n).astype(np.int32), None),  # envelope
    ]
    pairs = [(jnp.asarray(d), jnp.asarray(rng.random(n) < 0.85)) for d, _ in specs]
    bounds = [b for _, b in specs]
    counts, sums = jax.jit(
        lambda s, *flat: grouped_sums_dot(
            s, [(flat[2 * i], flat[2 * i + 1]) for i in range(len(pairs))], B, n, bounds
        )
    )(seg, *[x for p in pairs for x in p])
    rc, rs = np_reference(
        np.asarray(seg), [(np.asarray(v).astype(np.int64), np.asarray(w)) for v, w in pairs], B
    )
    assert np.array_equal(np.asarray(counts), rc)
    assert np.array_equal(np.asarray(sums), rs)


def test_dot_rejects_unbounded_int64():
    import jax.numpy as jnp

    n = 128
    with pytest.raises(ValueError, match="unbounded"):
        grouped_sums_dot(
            jnp.zeros(n, jnp.int32),
            [(jnp.zeros(n, jnp.int64), jnp.ones(n, bool))],
            4,
            n,
            [None],
        )


@pytest.fixture()
def dotdb(monkeypatch):
    # force the int8-dot MXU route for tiny tables: drop the eqmask band to
    # nothing and clear compiled kernels cached under the old routing
    monkeypatch.setattr(dag_kernel, "_DENSE_EQMASK_MAX", 0)
    monkeypatch.setattr(dag_kernel, "_COMPILE_CACHE", {})
    monkeypatch.setattr(tpu_engine, "_BLOCK", 512)
    db = tidb_tpu.open(region_split_keys=1 << 62)
    db.execute("CREATE TABLE b (k BIGINT, v DECIMAL(10,2), s VARCHAR(4), d DATE)")
    rng = np.random.default_rng(5)
    n = 2500
    bulk_load(
        db,
        "b",
        [
            rng.integers(0, 5, n),
            rng.integers(0, 100000, n),
            np.array([b"aa", b"bb", b"cc"], dtype=object)[rng.integers(0, 3, n)],
            8036 + rng.integers(0, 2000, n),
        ],
    )
    return db


def both(db, sql):
    s = db.session()
    out = {}
    for eng in ("tpu", "host"):
        s.execute(f"SET tidb_isolation_read_engines = '{eng}'")
        out[eng] = s.query(sql)
    return out["tpu"], out["host"]


def test_dot_path_group_agg_parity(dotdb):
    t, h = both(
        dotdb,
        "SELECT s, k, COUNT(*), SUM(v), AVG(v), COUNT(v) FROM b GROUP BY s, k ORDER BY s, k",
    )
    assert t == h and len(t) == 15


def test_dot_path_selection_and_exprs(dotdb):
    t, h = both(
        dotdb,
        "SELECT k, SUM(v * (1 - v/100000)), COUNT(*) FROM b"
        " WHERE d <= '1997-01-01' GROUP BY k ORDER BY k",
    )
    assert t == h


def test_fused_agg_single_dispatch(dotdb, monkeypatch):
    # big-table aggregations must reach the device as ONE fused program
    calls = []
    real = dag_kernel.get_kernel

    def counting(dag, n_pad, agg_cap, nb=1, **kw):
        k = real(dag, n_pad, agg_cap, nb, **kw)
        calls.append((nb, k))
        return k

    monkeypatch.setattr(tpu_engine, "get_kernel", counting)
    s = dotdb.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    rows = s.query("SELECT k, COUNT(*) FROM b GROUP BY k ORDER BY k")
    assert len(rows) == 5
    assert calls and all(nb > 1 for nb, _ in calls), "agg did not fuse blocks"


def test_mid_cardinality_group_by_sql_parity():
    # 41*6=246 buckets: past the int8 dot's MAX_B, so the lex-sort path —
    # the band a pallas kernel owned until it summed wrong on the chip
    db = tidb_tpu.open()
    db.execute("CREATE TABLE m (g1 VARCHAR(8), g2 VARCHAR(8), amt DECIMAL(10,2))")
    rng = np.random.default_rng(3)
    n = 6000
    g1s = [f"k{i}".encode() for i in range(40)]
    g2s = [f"v{i}".encode() for i in range(5)]
    from tidb_tpu.executor.load import bulk_load

    bulk_load(
        db,
        "m",
        [
            [g1s[int(i)] for i in rng.integers(0, 40, n)],
            [None if rng.random() < 0.05 else g2s[int(i)] for i in rng.integers(0, 5, n)],
            [None if rng.random() < 0.1 else int(rng.integers(0, 100000)) for _ in range(n)],
        ],
    )
    db.execute("ANALYZE TABLE m")
    s = db.session()
    q = "SELECT g1, g2, COUNT(*), COUNT(amt), SUM(amt), AVG(amt) FROM m GROUP BY g1, g2 ORDER BY g1, g2"
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    a = s.query(q)
    s.execute("SET tidb_isolation_read_engines = 'host'")
    b = s.query(q)
    assert a == b and len(a) > 200


def test_mxu_gate_falls_back_for_minmax():
    # MIN/MAX have no matmul form: mid-cardinality group-by must still be
    # correct (sort path)
    db = tidb_tpu.open()
    db.execute("CREATE TABLE m2 (g VARCHAR(8), v BIGINT)")
    from tidb_tpu.executor.load import bulk_load

    rng = np.random.default_rng(5)
    gs = [f"g{i}".encode() for i in range(60)]
    n = 3000
    bulk_load(db, "m2", [[gs[int(i)] for i in rng.integers(0, 60, n)], rng.integers(-(10**12), 10**12, n)])
    db.execute("ANALYZE TABLE m2")
    s = db.session()
    q = "SELECT g, MIN(v), MAX(v), COUNT(*) FROM m2 GROUP BY g ORDER BY g"
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    a = s.query(q)
    s.execute("SET tidb_isolation_read_engines = 'host'")
    assert a == s.query(q) and len(a) == 60
