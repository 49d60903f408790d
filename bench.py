"""Benchmark: TPC-H Q1/Q6-shaped aggregation pushdown, TPU engine vs the
host (numpy/unistore-analog) reference engine.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where value
is the TPU engine's Q1 scan+agg throughput (rows/sec/chip, end-to-end SQL
path, warm device cache) and vs_baseline is the speedup over the host
engine on identical data and plans (BASELINE.md configs 2 and 3).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tidb_tpu.bench.tpchlike import (  # noqa: E402  (after the sys.path line)
    COUNT_STAR,
    Q1,
    Q1_ROLLUP,
    Q3,
    Q6,
    Q10,
    WINDOWED,
    gen_tables,
    load_tables,
)

N_ROWS = int(os.environ.get("BENCH_ROWS", "20000000"))
# join bench tables stay at a fixed size so the host-reference join time
# doesn't swamp the run as N_ROWS scales
N_JOIN = int(os.environ.get("BENCH_JOIN_ROWS", "4000000"))
# best-of sampling: the tpu side takes several draws for a stable minimum;
# the host engine runs in-process numpy, so one timed draw (plus the
# warm-up) is representative and keeps multi-second reference queries cheap
REPS = int(os.environ.get("BENCH_REPS", "7"))
HOST_REPS = int(os.environ.get("BENCH_HOST_REPS", "1"))


def setup():
    import tidb_tpu

    db = tidb_tpu.open(region_split_keys=1 << 62)  # single region per chip
    return db, load_tables(db, gen_tables(0, N_ROWS, N_JOIN))


def timed(session, sql, reps):
    session.query(sql)  # warm (compile + cache build)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        session.query(sql)
        best = min(best, time.perf_counter() - t0)
    return best


QPS_THREADS = int(os.environ.get("BENCH_QPS_THREADS", "8"))
QPS_ITERS = int(os.environ.get("BENCH_QPS_ITERS", "200"))


def concurrent_qps(db, worker, n_threads, iters, setup=None):
    from tidb_tpu.bench.qps import concurrent_qps as _cq

    return _cq(db, worker, n_threads, iters, setup=setup)


def qps_point_select(db) -> float:
    """Point-select serving throughput: every thread EXECUTEs a prepared
    ``SELECT ... WHERE pk = ?`` with rotating parameters — the shape the
    value-agnostic prepared-plan cache exists for."""
    db.execute("CREATE TABLE qps_p (id BIGINT PRIMARY KEY, v BIGINT)")
    db.execute("INSERT INTO qps_p VALUES " + ",".join(f"({i},{i * 3})" for i in range(1000)))

    def setup(s, i):
        s.prepare("SELECT v FROM qps_p WHERE id = ?", name="pt")
        s.execute_prepared("pt", [i])  # warm the per-session caches

    def worker(s, i, k):
        rows = s.execute_prepared("pt", [(i * 131 + k) % 1000]).rows
        if len(rows) != 1:  # never inside an assert: python -O strips it
            raise RuntimeError(f"point select returned {len(rows)} rows")

    return concurrent_qps(db, worker, QPS_THREADS, QPS_ITERS, setup=setup)


def qps_point_select_cold(db) -> float:
    """Cold-session point selects: a FRESH session per query over text SQL —
    the short-lived-connection serving shape. The instance-level AST cache
    and the cross-session point-get batcher are what keep this within reach
    of the warm-session number."""
    db.execute("CREATE TABLE qps_c (id BIGINT PRIMARY KEY, v BIGINT)")
    db.execute("INSERT INTO qps_c VALUES " + ",".join(f"({i},{i * 3})" for i in range(1000)))
    db.query("SELECT v FROM qps_c WHERE id = 0")

    def worker(_s, i, k):
        s2 = db.session()
        rows = s2.query(f"SELECT v FROM qps_c WHERE id = {(i * 7 + k) % 16}")
        if len(rows) != 1:  # never inside an assert: python -O strips it
            raise RuntimeError(f"cold point select returned {len(rows)} rows")

    return concurrent_qps(db, worker, QPS_THREADS, QPS_ITERS)


def qps_q1_concurrent(db) -> float:
    """Q1 under concurrency: N sessions hammer the same warm aggregation —
    measures how much of the fixed SQL-layer tax survives parallel load
    (device work serializes on the chip; the SQL layer must not add to it)."""
    def setup(s, i):
        s.execute("SET tidb_isolation_read_engines = 'tpu'")
        s.query(Q1)  # warm plan + device caches per session

    def worker(s, i, k):
        s.query(Q1)

    return concurrent_qps(db, worker, min(QPS_THREADS, 4), 3, setup=setup)


def chip_time(db, session, sql) -> float:
    """Amortized ON-CHIP time for one query's device task: dispatch the
    production-shaped kernel K times asynchronously and sync once, dividing
    out the host↔device round trip. Returns seconds per full-table run."""
    from tidb_tpu.copr import tpu_engine as te

    captured = {}
    real = te._execute_dag_device

    def cap(store, dag, region, ranges, read_ts, warn=None):
        captured["args"] = (dag, region, ranges, read_ts)
        return real(store, dag, region, ranges, read_ts, warn)

    te._execute_dag_device = cap
    try:
        session.query(sql)
    finally:
        te._execute_dag_device = real
    dag, region, ranges, read_ts = captured["args"]
    run_once, sync = te.device_probe_fn(db.store, dag, region, ranges, read_ts)
    sync(run_once())  # warm
    K = 32
    t0 = time.perf_counter()
    outs = [run_once() for _ in range(K)]
    sync(outs[-1])
    return (time.perf_counter() - t0) / K


_REMOTE_SERVER_SCRIPT = r"""
import sys, time
sys.path.insert(0, {repo!r})
import os
os.environ["BENCH_ROWS"] = str({rows})
os.environ["BENCH_JOIN_ROWS"] = str({jrows})
import bench
db, _ = bench.setup()
from tidb_tpu.kv.remote import StoreServer
srv = StoreServer(db.store)
print(f"PORT {{srv.start()}}", flush=True)
while True:
    time.sleep(1)
"""


def remote_probe():
    """Q1/Q3 through the REAL topology: this process is a pure SQL layer
    over a storage-server subprocess that owns the data AND the device (ref:
    tests/realtikvtest — the reference benches against real TiKV, not only
    unistore). Runs BEFORE the embedded benches so the parent process has
    not initialized the device backend the server needs to own."""
    import subprocess
    import threading

    repo = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.Popen(
        [sys.executable, "-c", _REMOTE_SERVER_SCRIPT.format(
            repo=repo, rows=N_ROWS, jrows=N_JOIN)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    got: list = []

    def reader():
        for line in proc.stdout:
            if line.startswith("PORT "):
                got.append(int(line.split()[1]))
                return

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    # drain stderr concurrently: a chatty child must not deadlock on a full
    # pipe buffer before it prints PORT
    err_chunks: list = []
    te = threading.Thread(
        target=lambda: err_chunks.append(proc.stderr.read()), daemon=True
    )
    te.start()
    t.join(timeout=600)
    if not got:
        proc.kill()
        err_tail = (err_chunks[0] if err_chunks else "" or "")[-2000:]
        raise RuntimeError(f"bench store server did not come up: {err_tail}")
    try:
        import tidb_tpu

        db = tidb_tpu.open(remote=f"127.0.0.1:{got[0]}")
        s = db.session()
        s.execute("SET tidb_isolation_read_engines = 'tpu'")
        q1_remote = timed(s, Q1, max(1, REPS // 2))
        s.execute("ANALYZE TABLE orders")
        s.execute("ANALYZE TABLE lineitem2")
        q3_remote = timed(s, Q3, max(1, REPS // 2))
        return q1_remote, q3_remote
    finally:
        proc.kill()
        proc.wait(timeout=30)


def _require_tpu():
    """Fail at start unless JAX's default platform is the TPU — checked in a
    throwaway child, because this process must stay off JAX until the
    store-server child of remote_probe() has had the chip and gone."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, timeout=300,
    )
    platform = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
    if out.returncode != 0 or platform != "tpu":
        raise SystemExit(
            f"bench.py measures the tpu engine on a TPU; jax platform is "
            f"{platform or 'unavailable'!r}: {out.stderr[-500:]}"
        )


def main():
    _require_tpu()
    q1_remote, q3_remote = remote_probe()
    db, load_s = setup()
    s = db.session()

    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    q1_tpu = timed(s, Q1, REPS)
    q1_chip = chip_time(db, s, Q1)
    q6_chip = chip_time(db, s, Q6)
    q10_chip = chip_time(db, s, Q10)
    q6_tpu = timed(s, Q6, REPS)
    cnt_tpu = timed(s, COUNT_STAR, REPS)
    q10_tpu = timed(s, Q10, REPS)
    # the Expand fusion vs the per-set union (same query, toggled rewrite)
    rollup_fused = timed(s, Q1_ROLLUP, max(1, REPS // 2))
    s.execute("SET tidb_opt_fused_rollup = 0")
    rollup_union = timed(s, Q1_ROLLUP, max(1, REPS // 2))
    s.execute("SET tidb_opt_fused_rollup = 1")
    q3_tpu = timed(s, Q3, max(1, REPS // 2))
    win_tpu = timed(s, WINDOWED, max(1, REPS // 2))
    tpu_rows = s.query(Q1)

    # concurrent-QPS lanes (threads × sessions over this same DB)
    qps_ps = qps_point_select(db)
    qps_cold = qps_point_select_cold(db)
    qps_q1 = qps_q1_concurrent(db)

    s.execute("SET tidb_isolation_read_engines = 'host'")
    q1_host = timed(s, Q1, HOST_REPS)
    q6_host = timed(s, Q6, HOST_REPS)
    cnt_host = timed(s, COUNT_STAR, HOST_REPS)
    q10_host = timed(s, Q10, HOST_REPS)
    s.execute("SET tidb_allow_mpp = 0")  # host reference path for the join
    q3_host = timed(s, Q3, HOST_REPS)
    win_host = timed(s, WINDOWED, HOST_REPS)
    s.execute("SET tidb_allow_mpp = 1")
    host_rows = s.query(Q1)

    if [r[:2] + tuple(str(x) for x in r[2:]) for r in tpu_rows] != [
        r[:2] + tuple(str(x) for x in r[2:]) for r in host_rows
    ]:  # never inside an assert: python -O strips it
        raise SystemExit("engine results diverge")

    value = N_ROWS / q1_tpu
    vs = q1_host / q1_tpu
    result = {
        "metric": "tpch_q1_sf~1_rows_per_sec_per_chip",
        "value": round(value),
        "unit": "rows/s",
        "vs_baseline": round(vs, 2),
        "detail": {
            "rows": N_ROWS,
            "q1_tpu_ms": round(q1_tpu * 1e3, 1),
            # amortized device-only time (dispatch round trip divided out):
            # what the chip itself sustains on Q1
            "q1_chip_ms": round(q1_chip * 1e3, 1),
            "q1_chip_rows_per_sec": round(N_ROWS / q1_chip),
            "q1_host_ms": round(q1_host * 1e3, 1),
            "q6_tpu_ms": round(q6_tpu * 1e3, 1),
            "q6_chip_ms": round(q6_chip * 1e3, 1),
            "q10_chip_ms": round(q10_chip * 1e3, 1),
            "q6_host_ms": round(q6_host * 1e3, 1),
            "q6_speedup": round(q6_host / q6_tpu, 2),
            "count_tpu_ms": round(cnt_tpu * 1e3, 1),
            # the fixed SQL-layer tax: COUNT(*) is near-zero device compute,
            # so its warm end-to-end latency IS the per-query overhead the
            # fast lane attacks (parse/plan reuse, shared pool, digest memo)
            "fixed_overhead_ms": round(cnt_tpu * 1e3, 1),
            "qps_point_select": round(qps_ps, 1),
            "qps_point_select_cold": round(qps_cold, 1),
            "qps_q1_concurrent": round(qps_q1, 2),
            "count_host_ms": round(cnt_host * 1e3, 1),
            "q10_topn_tpu_ms": round(q10_tpu * 1e3, 1),
            "rollup_fused_ms": round(rollup_fused * 1e3, 1),
            "rollup_union_ms": round(rollup_union * 1e3, 1),
            "q10_topn_host_ms": round(q10_host * 1e3, 1),
            "q3_join_mpp_ms": round(q3_tpu * 1e3, 1),
            "q3_join_host_ms": round(q3_host * 1e3, 1),
            # the REAL topology: SQL layer + storage-server process over TCP
            "q1_remote_ms": round(q1_remote * 1e3, 1),
            "q3_remote_mpp_ms": round(q3_remote * 1e3, 1),
            "window_tpu_ms": round(win_tpu * 1e3, 1),
            "window_host_ms": round(win_host * 1e3, 1),
            "load_s": round(load_s, 1),
            "platform": _platform(),
        },
    }
    print(json.dumps(result))


def _platform():
    import jax

    return str(jax.devices()[0].platform)


if __name__ == "__main__":
    main()
