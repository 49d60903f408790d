#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still runs ON THE
CHIP: wire server → session → planner → cop client / MPP gather →
copr/tpu_engine → ops/ kernels → chunk → wire, through the entry points a
user calls, with every answer checked against a reference that never touches
the device path and every device-eligible statement checked to have RUN on
the device (the resilience fallbacks would otherwise answer from the host,
correctly and quietly).

    python3 chip_smoke.py                     # on a TPU: 20M-row lineitem
    python3 chip_smoke.py --platform cpu --rows 65536    # sandbox rehearsal

This orchestrator never imports jax (nor anything of tidb_tpu): a process
that has touched jax holds the chip, and the phases below each need it. They
run as child processes, one after another:

  device    what jax sees (platform, kind, count, versions) + the host↔device
            link: dispatch+sync time, H2D and D2H rates.
  phase A   the deployed topology: `python -m tidb_tpu --store-server` owns
            the chip; a SQL-node child (which must stay off jax) bulk-loads
            over the wire and runs Q1 and the Q3 MPP join. The server is then
            SIGTERMed and must exit 0 — phase B proves the chip was released.
  phase B   embedded store + MySQL wire server at full size: the five
            BASELINE configs, window, fused rollup, mid- and high-cardinality
            GROUP BY, point select, write-then-read (INSERT and UPDATE read
            back through the delta operand), warm repeat.

Standard output is two lines. The LAST is the verdict, exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}` with the
device as jax reports it; the one before it is `SMOKE_REPORT ` + one JSON
object with everything else (versions, link timings, per phase and statement:
load/first-answer/compile seconds, warm ms, h2d/d2h bytes, bytes_in_use) —
information for the next PR, not metrics. Exit 0 only if every phase passed
on the expected platform; a failed phase prints `"ok": false` and exits 1.
On the wrong platform, or with no repo beside the script, it exits non-zero
and prints nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import re
import signal
import statistics
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1150.0  # the contract allows 1200 s, compilation included
RESULT_TAG = "SMOKE_RESULT "  # child → orchestrator
REPORT_TAG = "SMOKE_REPORT "  # orchestrator → reader: the line before the verdict
BLOCK_ROWS = 4_000_000  # join tables, the dictionary table and phase A's lineitem


class SmokeFailure(Exception):
    """A phase ran but something it must prove did not hold."""


# --------------------------------------------------------------------------
# orchestrator — stays off jax
# --------------------------------------------------------------------------


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=20_000_000, help="phase B lineitem rows")
    ap.add_argument("--seed", type=int, default=0, help="data generator seed")
    ap.add_argument("--reps", type=int, default=5, help="warm readings per statement")
    ap.add_argument(
        "--platform", choices=["cpu"], default=None,
        help="explicit rehearsal on the CPU backend (children get JAX_PLATFORMS=cpu); "
        "without it the smoke only ever passes on a TPU",
    )
    # internal: the role a child process plays
    ap.add_argument("--child", choices=["device", "sql-node", "embedded"], help=argparse.SUPPRESS)
    ap.add_argument("--remote", help=argparse.SUPPRESS)
    ap.add_argument("--want-platform", default="tpu", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


class Orchestrator:
    def __init__(self, args):
        self.args = args
        self.t0 = time.time()
        self.procs: list[subprocess.Popen] = []
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = REPO + os.pathsep + self.env.get("PYTHONPATH", "")
        self.want = "tpu"
        self.device: dict | None = None  # known once the device child has passed
        if args.platform == "cpu":
            self.env["JAX_PLATFORMS"] = "cpu"
            self.want = "cpu"

    def remaining(self) -> float:
        left = BUDGET_S - (time.time() - self.t0)
        if left <= 0:
            raise SmokeFailure(f"out of time: the smoke must finish inside {BUDGET_S:.0f}s")
        return left

    def spawn(self, cmd, **kw) -> subprocess.Popen:
        # own session: kill_all() can take the whole group down
        p = subprocess.Popen(cmd, cwd=REPO, env=self.env, start_new_session=True, **kw)
        self.procs.append(p)
        return p

    def kill_all(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass  # exited between poll() and the signal
                p.wait()

    def run_child(self, role: str, *extra: str) -> dict:
        """Run one phase child to its end; its stdout's last line carries the
        result. stderr passes through, so a failure's reason is on ours."""
        a = self.args
        cmd = [
            sys.executable, os.path.abspath(__file__), "--child", role,
            "--rows", str(a.rows), "--seed", str(a.seed), "--reps", str(a.reps),
            "--want-platform", self.want, *extra,
        ]
        p = self.spawn(cmd, stdout=subprocess.PIPE, text=True)
        try:
            out, _ = p.communicate(timeout=self.remaining())
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{role} child did not finish in time") from None
        lines = [ln for ln in out.splitlines() if ln.startswith(RESULT_TAG)]
        if p.returncode != 0 or not lines:
            raise SmokeFailure(f"{role} child failed (exit {p.returncode})")
        print(f"chip_smoke: {role} child done at {time.time() - self.t0:.0f}s", file=sys.stderr, flush=True)
        return json.loads(lines[-1][len(RESULT_TAG):])

    def phase_store_server(self) -> dict:
        """Phase A: the store server is started the way an operator starts
        it; one region a table (the layout that reaches the block paths) via
        its config file. It must leave with exit 0 on SIGTERM."""
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            cfg = os.path.join(tmp, "store.toml")
            with open(cfg, "w") as f:
                f.write(f"[storage]\nregion-split-keys = {1 << 62}\n")
            srv = self.spawn(
                [sys.executable, "-m", "tidb_tpu", "--store-server", "--host", "127.0.0.1",
                 "-P", "0", "--config", cfg],
                stdout=subprocess.PIPE, text=True,
            )
            lines: "queue.Queue[str]" = queue.Queue()
            threading.Thread(
                target=lambda: [lines.put(ln) for ln in srv.stdout], name="smoke-srv-out", daemon=True
            ).start()
            try:
                ready = lines.get(timeout=min(120.0, self.remaining()))
            except queue.Empty:
                raise SmokeFailure("store server printed no `ready port=N` line") from None
            m = re.match(r"ready port=(\d+)", ready)
            if m is None:
                raise SmokeFailure(f"store server said {ready!r}, not `ready port=N`")
            res = self.run_child("sql-node", "--remote", f"127.0.0.1:{m.group(1)}")
            srv.send_signal(signal.SIGTERM)
            try:
                rc = srv.wait(timeout=min(60.0, self.remaining()))
            except subprocess.TimeoutExpired:
                raise SmokeFailure("store server ignored SIGTERM for 60s") from None
            if rc != 0:
                raise SmokeFailure(f"store server exited {rc} on SIGTERM, not 0")
            return res

    def run(self) -> dict:
        dev = self.run_child("device")
        self.device = dev["device"]
        out = {**dev, "rows": self.args.rows, "seed": self.args.seed, "phases": {}}
        out["phases"]["store_server"] = self.phase_store_server()
        out["phases"]["embedded"] = self.run_child("embedded")
        if out["phases"]["embedded"]["device"] != self.device:
            raise SmokeFailure(f"phase B ran on {out['phases']['embedded']['device']}, not {self.device}")
        out["elapsed_s"] = round(time.time() - self.t0, 1)
        return out


def orchestrate(args) -> int:
    if not os.path.isdir(os.path.join(REPO, "tidb_tpu")):
        print(f"chip_smoke: no tidb_tpu package beside {__file__}", file=sys.stderr)
        return 2
    orch = Orchestrator(args)
    # a killed smoke must not leave a store server holding the chip
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        report = orch.run()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        if orch.device is not None:  # the right platform was there; a phase failed on it
            print(json.dumps({"ok": False, "device": orch.device}), flush=True)
        return 1
    finally:
        orch.kill_all()
    print(REPORT_TAG + json.dumps(report))
    print(json.dumps({"ok": True, "device": orch.device}), flush=True)
    return 0


# --------------------------------------------------------------------------
# children — each is the only process on the device while it lives
# --------------------------------------------------------------------------


def emit(result: dict) -> None:
    print(RESULT_TAG + json.dumps(result), flush=True)


_T0 = time.time()


def progress(what: str, rec=None) -> None:
    """Progress on stderr as it happens, stamped with the process's age: a
    run killed at its time limit still says where the time went."""
    body = "" if rec is None else " " + json.dumps(
        {k: round(v, 3) if isinstance(v, float) else v for k, v in rec.items()}
    )
    print(f"chip_smoke: +{time.time() - _T0:.0f}s [{what}]{body}", file=sys.stderr, flush=True)


def require_platform(platform: str, want: str) -> None:
    if platform != want:
        hint = "" if want == "cpu" else " (the sandbox rehearsal is `--platform cpu`)"
        print(
            f"chip_smoke: jax platform is {platform!r}; this run needs {want!r}{hint}",
            file=sys.stderr,
        )
        sys.exit(2)


def child_device(args) -> None:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    require_platform(devs[0].platform, args.want_platform)
    versions = {"jax": jax.__version__}
    for pkg in ("jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None

    def med(fn, n):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    # the link as the engine uses it: a tiny dispatch that ends in a sync, the
    # same ending in a host fetch, and bulk transfers both ways
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8, jnp.int32)
    f(x).block_until_ready()
    host = np.arange((64 << 20) // 4, dtype=np.int32)
    on_dev = jax.device_put(host)
    on_dev.block_until_ready()
    h2d_s = med(lambda: jax.device_put(host).block_until_ready(), 5)

    def d2h_once() -> float:
        fresh = on_dev + 1  # an array keeps its host copy after the first fetch
        fresh.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(fresh)
        return time.perf_counter() - t0

    d2h_s = statistics.median(d2h_once() for _ in range(5))
    link = {
        "dispatch_sync_us": med(lambda: f(x).block_until_ready(), 200) * 1e6,
        "dispatch_fetch_us": med(lambda: np.asarray(f(x)), 200) * 1e6,
        "transfer_bytes": host.nbytes,
        "h2d_ns_per_byte": h2d_s / host.nbytes * 1e9,
        "d2h_ns_per_byte": d2h_s / host.nbytes * 1e9,
    }
    stats = devs[0].memory_stats() or {}
    from tidb_tpu.ops.dag_kernel import COMPILE_CACHE_DIR

    emit({
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
        "versions": versions,
        "hbm_bytes_limit": stats.get("bytes_limit"),
        "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR,
        "link": link,
    })


# -- EXPLAIN ANALYZE → which engine ran, what it cost ------------------------

_COP_RE = re.compile(r"cop_task: \{([^}]*)\}")
_MPP_RE = re.compile(r"mpp_task: \{([^}]*)\}")


def parse_explain(lines) -> dict:
    info = {"cop_tasks": 0, "engines": {}, "degraded": [], "compile_ms": 0.0, "h2d_bytes": 0,
            "d2h_bytes": 0, "delta_rows": 0, "mpp_ndev": [], "mpp_compiles": 0}
    for line in lines:
        for body in _COP_RE.findall(line):
            info["cop_tasks"] += int(re.search(r"num: (\d+)", body).group(1))
            for tok in re.search(r"engine: ([^,]*)", body).group(1).split():
                name, _, cnt = tok.partition("×")
                info["engines"][name] = info["engines"].get(name, 0) + int(cnt or 1)
            m = re.search(r"degraded: (.*)$", body)
            if m:
                info["degraded"].append(m.group(1))
            m = re.search(r"compile: ([\d.]+)ms", body)
            if m:
                info["compile_ms"] += float(m.group(1))
            m = re.search(r"h2d: (\d+)B, d2h: (\d+)B", body)
            if m:
                info["h2d_bytes"] += int(m.group(1))
                info["d2h_bytes"] += int(m.group(2))
            m = re.search(r"delta_rows: (\d+)", body)
            if m:
                info["delta_rows"] += int(m.group(1))
        for body in _MPP_RE.findall(line):
            info["mpp_ndev"].append(int(re.search(r"ndev: (\d+)", body).group(1)))
            m = re.search(r"compile: (\d+)", body)
            if m:
                info["mpp_compiles"] += int(m.group(1))
    return info


def norm(rows) -> list:
    """Rows as tuples of str|None: the wire's text protocol already is, the
    session API's python values are brought to the same form."""
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


def same_numbers(got, want) -> bool:
    """Row-for-row equality where a numeric cell may differ in text only
    ('12.50' vs '12.5'); strings and NULLs compare exactly."""
    from decimal import Decimal, InvalidOperation

    if len(got) != len(want):
        return False
    for g, w in zip(got, want):
        if len(g) != len(w):
            return False
        for a, b in zip(g, w):
            if a == b:
                continue
            if a is None or b is None:
                return False
            try:
                if Decimal(a) != Decimal(b):
                    return False
            except InvalidOperation:
                return False
    return True


class Prober:
    """Runs statements through ONE connection (``run(sql) -> rows``) and
    holds each to: right answer, ran on the device, degraded nowhere."""

    def __init__(self, run, reps: int, ndev: int, compile_clock=None):
        self.run = run
        self.reps = reps
        self.ndev = ndev
        self.compile_clock = compile_clock or (lambda: (0, 0.0))
        self.failures: list[str] = []
        self.report: dict = {}

    def set_engine(self, engine: str) -> None:
        self.run(f"SET tidb_isolation_read_engines = '{engine}'")
        self.run(f"SET tidb_allow_mpp = {1 if engine == 'tpu' else 0}")

    def fail(self, name: str, why: str) -> None:
        print(f"chip_smoke: [{name}] {why}", file=sys.stderr)
        self.failures.append(f"{name}: {why}")

    def host_answer(self, sql: str) -> list:
        self.set_engine("host")
        try:
            return norm(self.run(sql))
        finally:
            self.set_engine("tpu")

    def check_on_device(self, name: str, info: dict, mpp: bool, when: str) -> None:
        if info["degraded"]:
            self.fail(name, f"{when}: degraded to the host: {info['degraded']}")
        if set(info["engines"]) - {"tpu"}:
            self.fail(name, f"{when}: cop tasks answered by {info['engines']}, expected tpu only")
        if not info["cop_tasks"] and not info["mpp_ndev"]:
            self.fail(name, f"{when}: no cop task and no MPP gather in EXPLAIN ANALYZE")
        if mpp and not info["mpp_ndev"]:
            self.fail(name, f"{when}: expected an MPP gather, the plan has none")
        if any(n != self.ndev for n in info["mpp_ndev"]):
            self.fail(name, f"{when}: MPP ran on ndev={info['mpp_ndev']}, {self.ndev} visible")

    def statement(self, name, sql, *, want=None, device=True, mpp=False, expect=None) -> list:
        """Cold EXPLAIN ANALYZE (time to first answer, compile), the answer
        against ``want`` (rows, or None = ask the host engine), warm
        readings, warm EXPLAIN ANALYZE (nothing may compile)."""
        rec: dict = {}
        self.report[name] = rec
        try:
            c0 = self.compile_clock()
            t0 = time.perf_counter()
            cold = parse_explain(r[0] for r in self.run("EXPLAIN ANALYZE " + sql))
            rec["first_s"] = time.perf_counter() - t0
            c1 = self.compile_clock()
            rec["compiles"], rec["compile_s"] = c1[0] - c0[0], c1[1] - c0[1]
            rec["cop_compile_ms"] = cold["compile_ms"]
            rec["h2d_bytes_cold"] = cold["h2d_bytes"]
            ts = []
            for _ in range(max(1, self.reps)):
                t0 = time.perf_counter()
                rows = self.run(sql)
                ts.append(time.perf_counter() - t0)
                if ts[-1] > 1.0:
                    break  # a slow statement gets one warm reading, not five
            got = norm(rows)
            rec["rows"] = len(got)
            rec["warm_ms"] = statistics.median(ts) * 1e3
            rec["warm_readings"] = len(ts)
            warm = parse_explain(r[0] for r in self.run("EXPLAIN ANALYZE " + sql))
            rec["h2d_bytes"], rec["d2h_bytes"] = warm["h2d_bytes"], warm["d2h_bytes"]
            rec["delta_rows"] = warm["delta_rows"]
            if device:
                self.check_on_device(name, cold, mpp, "cold")
                self.check_on_device(name, warm, mpp, "warm")
                if warm["compile_ms"] or warm["mpp_compiles"]:
                    self.fail(name, f"the warm run compiled: {warm}")
            if expect is not None:
                expect(cold, warm, got)
            if want is None:
                t0 = time.perf_counter()
                want = self.host_answer(sql)
                rec["host_ref_s"] = time.perf_counter() - t0
            if not same_numbers(got, want):
                self.fail(name, f"wrong answer: got {got[:4]}… want {want[:4]}…")
            progress(name, rec)
            return got
        except Exception as e:  # one broken statement must not hide the others
            import traceback

            traceback.print_exc()
            self.fail(name, f"raised {type(e).__name__}: {e}")
            return []


def days(y: int, m: int, d: int) -> int:
    import datetime

    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def dec(scaled: int, scale: int) -> str:
    """A scaled integer as DECIMAL text: dec(1234, 2) == '12.34'."""
    from decimal import Decimal

    return str(Decimal(int(scaled)).scaleb(-scale))


def process_checks(p: Prober) -> None:
    """Counters and events of THIS process: nothing degraded, nothing fell
    back, the native codec is the C++ one."""
    from tidb_tpu import native
    from tidb_tpu.utils import eventlog, metrics

    if metrics.COP_DEGRADED.total():
        p.fail("process", f"tidb_tpu_copr_degraded_task_total = {metrics.COP_DEGRADED.total()}")
    bad = [
        f"{ev[2]}.{ev[3]} {ev[4]}"
        for ev in eventlog.get().search(min_level=eventlog.WARN, limit=None)
        if (ev[2], ev[3]) in (("copr", "degrade"), ("mpp", "host_join_fallback"), ("native", "unavailable"))
    ]
    if bad:
        p.fail("process", f"fallback events in the event log: {bad[:3]}")
    if native.lib() is None:
        p.fail("process", "the native codec did not load (tidb_tpu.native.lib() is None)")


def finish(p: Prober, result: dict) -> None:
    result["statements"] = p.report
    if p.failures:
        raise SmokeFailure(f"{len(p.failures)} check(s) failed: " + "; ".join(p.failures))
    emit(result)


def child_sql_node(args) -> None:
    """Phase A's SQL layer: everything device-side happens in the store
    server; this process plans, ships DAGs and must never load jax."""
    import tidb_tpu
    from tidb_tpu.bench import tpchlike as T

    n = min(args.rows, BLOCK_ROWS)
    tables = T.gen_tables(args.seed, n, n)
    db = tidb_tpu.open(remote=args.remote)
    t0 = time.time()
    T.load_tables(db, tables)
    result = {"rows": n, "load_s": time.time() - t0}
    progress("loaded over the wire", result)
    dev = db.store.mpp_devices()  # as the SERVER's jax reports it
    require_platform(dev["platform"], args.want_platform)
    result["device"] = dev
    s = db.session()
    p = Prober(lambda sql: s.execute(sql).rows, args.reps, int(dev["ndev"]))
    p.set_engine("tpu")
    p.statement("q1", T.Q1)
    p.statement("q3_mpp", T.Q3, mpp=True)
    process_checks(p)
    if "jax" in sys.modules:
        p.fail("process", "the SQL node imported jax — it must never own a device backend")
    finish(p, result)


def child_embedded(args) -> None:
    """Phase B: one process owns store, device and MySQL wire server; every
    statement arrives over TCP like a client's."""
    import jax
    import numpy as np

    devs = jax.devices()
    require_platform(devs[0].platform, args.want_platform)
    clock = {"n": 0, "s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            clock["n"] += 1
            clock["s"] += duration

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            clock["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    import tidb_tpu
    from tidb_tpu.bench import tpchlike as T
    from tidb_tpu.copr.colcache import hbm_budget
    from tidb_tpu.executor.load import bulk_load
    from tidb_tpu.server import Client, Server
    from tidb_tpu.utils import metrics

    n, nj = args.rows, min(args.rows, BLOCK_ROWS)
    tables = T.gen_tables(args.seed, n, nj)
    db = tidb_tpu.open(region_split_keys=1 << 62)  # single region per chip
    t0 = time.time()
    T.load_tables(db, tables)
    # a dictionary column with 200 distinct values: past the int8 dot's 64
    # buckets, the band the lex-sort path now owns
    rng = np.random.default_rng(args.seed + 1)
    keys = np.array([f"k{i:03d}".encode() for i in range(200)], dtype="S4")
    g_k, g_v = rng.integers(0, 200, nj), rng.integers(100, 5100, nj)
    db.execute("CREATE TABLE g (k VARCHAR(8), v DECIMAL(12,2))")
    bulk_load(db, "g", [keys[g_k], g_v])
    db.execute("ANALYZE TABLE g")
    result = {"rows": n, "join_rows": nj, "load_s": time.time() - t0}
    progress("loaded", result)

    client = Client(port=Server(db).start(), db="test")
    p = Prober(client.query, args.reps, len(devs), lambda: (clock["n"], clock["s"]))
    p.set_engine("tpu")

    li = tables["lineitem"]
    qty, price, disc, ship = li[0], li[1], li[2], li[6]
    p.statement("count", T.COUNT_STAR, want=[(str(n),)])
    m6 = (ship >= days(1994, 1, 1)) & (ship < days(1995, 1, 1)) & (disc >= 5) & (disc <= 7) & (qty < 2400)
    p.statement("q6", T.Q6, want=[(dec((price[m6] * disc[m6]).sum(), 4),)])
    q1_before = p.statement("q1", T.Q1)
    p.statement("q10_topn", T.Q10)
    p.statement("q3_mpp", T.Q3, mpp=True)
    p.statement("window", T.WINDOWED)
    p.statement("rollup_fused", T.Q1_ROLLUP)
    cnt = np.bincount(g_k, minlength=200)
    tot = np.bincount(g_k, weights=g_v, minlength=200).astype(np.int64)  # < 2^53: exact
    p.statement(
        "groupby_dict200", "SELECT k, COUNT(*), SUM(v) FROM g GROUP BY k ORDER BY k",
        want=[(keys[i].decode(), str(cnt[i]), dec(tot[i], 2)) for i in range(200) if cnt[i]],
    )
    # ~nj/10 groups: the lex-sort path, whose static group cap overflows and
    # retries bigger (tpu_engine's agg_cap protocol)
    okey, oprice = tables["lineitem2"]
    sums = np.bincount(okey, weights=oprice).astype(np.int64)
    live = np.bincount(okey) > 0
    p.statement(
        "groupby_orderkey",
        "SELECT COUNT(*), SUM(s), MAX(s) FROM (SELECT l_orderkey, SUM(l_extendedprice) AS s"
        " FROM lineitem2 GROUP BY l_orderkey) t",
        want=[(str(live.sum()), dec(sums.sum(), 2), dec(sums[live].max(), 2))],
    )
    o_key = int(tables["orders"][0][len(tables["orders"][0]) // 3])
    p.statement(
        "point_select", f"SELECT o_odate FROM orders WHERE o_orderkey = {o_key}",
        want=[(str(tables["orders"][1][o_key]),)], device=False,
    )

    # write, then read: the acknowledged rows must be in the next answer, and
    # the device must read them through the delta operand — fresh rows
    # unioned in (INSERT), superseded base rows masked (UPDATE) — not through
    # a re-upload. The UPDATE goes by primary key: a DML whose WHERE needs a
    # table scan reads row-at-a-time on the host (~30 µs a row), minutes at
    # this size, which would be this smoke measuring the wrong thing.
    o_keys, o_date = tables["orders"]
    p.statement(
        "orders_sum", "SELECT COUNT(*), SUM(o_odate) FROM orders",
        want=[(str(len(o_keys)), str(o_date.sum()))],
    )
    n_ins = 300
    client.query(
        "INSERT INTO lineitem VALUES "
        + ",".join("(2.00, 1000.00, 0.05, 0.02, 'A', 'F', '1995-06-17')" for _ in range(n_ins))
    )
    upd_keys = o_keys[:: max(1, len(o_keys) // 200)][:200]
    n_upd = client.query(
        f"UPDATE orders SET o_odate = 8036 WHERE o_orderkey IN ({','.join(map(str, upd_keys))})"
    )
    changed = int((o_date[upd_keys] != 8036).sum())  # MySQL counts rows it CHANGED
    if n_upd != changed:
        p.fail("write", f"UPDATE changed {n_upd} rows, numpy says {changed}")
    progress("write", {"inserted": n_ins, "updated": n_upd})

    def through_delta(name, n_rows, n_changed):
        def check(cold, warm, _got):
            # tables under colcache's device-delta-min-rows rebuild outright
            if n_rows >= 65536 and min(cold["delta_rows"], warm["delta_rows"]) < n_changed:
                p.fail(name, f"no delta operand: delta_rows {cold['delta_rows']}/{warm['delta_rows']}")
        return check

    q1_after = p.statement("q1_after_write", T.Q1, expect=through_delta("q1_after_write", n, n_ins))
    # independent of both engines: what the INSERT must have done to Q1
    from decimal import Decimal

    before = {r[:2]: r for r in q1_before}
    d_cnt = sum(int(r[9]) - int(before[r[:2]][9]) for r in q1_after)
    d_qty = sum(Decimal(r[2]) - Decimal(before[r[:2]][2]) for r in q1_after)
    if q1_after and (d_cnt, d_qty) != (n_ins, Decimal(2 * n_ins)):
        p.fail("q1_after_write", f"write not in the answer: Δcount {d_cnt}, Δsum(qty) {d_qty}")
    new_date = o_date.copy()
    new_date[upd_keys] = 8036
    p.statement(
        "orders_sum_after_write", "SELECT COUNT(*), SUM(o_odate) FROM orders",
        want=[(str(len(o_keys)), str(new_date.sum()))],
        expect=through_delta("orders_sum_after_write", len(o_keys), changed),
    )

    # the warm repeat: nothing may compile — not a cop kernel, not an MPP
    # program, not a stray jit
    before = (clock["n"], metrics.COP_COMPILE_SECONDS.snapshot(), metrics.MPP_PROGRAM_CACHE.snapshot())
    client.query(T.Q1)
    after = (clock["n"], metrics.COP_COMPILE_SECONDS.snapshot(), metrics.MPP_PROGRAM_CACHE.snapshot())
    if before != after:
        p.fail("q1_warm_repeat", f"the warm repeat compiled: {before} -> {after}")
    progress("q1_warm_repeat")

    process_checks(p)
    stats = [d.memory_stats() or {} for d in devs]
    limit = stats[0].get("bytes_limit")
    if limit is not None and hbm_budget() > limit:
        p.fail("process", f"HBM budget {hbm_budget()} exceeds the device's bytes_limit {limit}")
    result.update({
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
        "hbm_budget": hbm_budget(),
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "compiles": clock["n"], "compile_s": clock["s"], "persistent_cache_hits": clock["cache_hits"],
    })
    client.close()
    finish(p, result)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.child is None:
        return orchestrate(args)
    try:
        {"device": child_device, "sql-node": child_sql_node, "embedded": child_embedded}[args.child](args)
    except SmokeFailure as e:
        print(f"chip_smoke: [{args.child}] FAILED: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
