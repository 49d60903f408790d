#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in one process:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as `setup_s` from process start): generate the cell's tables
from the seed, open the embedded store, load, start the MySQL wire server,
connect the clients over TCP, run every text of the cell once on every
connection (this compiles, or loads from the persistent cache, every kernel
the window will use). Then the window: closed loops for `--seconds`. Then, the
program's state released, the plain reference answers every statement of the
window again and the comparison decides `correct`: every answer right, and
every statement on the cell's devices by the evidence the program hands out
itself (each cop task's ExecDetails, each MPP gather's MPPExecDetails;
`harness/spans.py` records them, `harness/check.on_device` is the rule).

The last line of stdout is the result. `--trace 0` prints the cell's
end-to-end metrics, `--trace 1` its per-layer metrics, read from the
benchmark's spans, the program's counters and a `jax.profiler` trace.

Rehearsal off the chip: `--platform cpu --scale 0.01`. Its line names `cpu`
as the device and can never be read as a measurement.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, "_bench_cache")  # in .gitignore; compile cache and traces


def log(what: str) -> None:
    print(f"benchmark: +{time.time() - T_PROCESS:.1f}s {what}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--platform", choices=["cpu"], default=None, help="rehearsal on the CPU backend")
    ap.add_argument("--scale", type=float, default=None, help="rehearsal only: another scale factor")
    ap.add_argument("--control", action="store_true",
                    help="also judge the controls (the reference in float32; a stale snapshot) and print their numbers")
    return ap.parse_args(argv)


def find_cell(name: str) -> tuple[dict, dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    return bench, cell, config


def metric_names(bench: dict, group: str, cell: str) -> list[str]:
    return [m["name"] for m in bench[group] if "workloads" not in m or cell in m["workloads"]]


def read_metrics(folder: str, names: list[str], ctx) -> dict:
    """Each metric is a reader of its own, `<folder>/<name>.py` with
    `UNIT` and `read(ctx)`; one that finds nothing to read returns None and
    is left out of the line."""
    out = {}
    for name in names:
        mod = importlib.import_module(f"{folder}.{name.replace('-', '_').replace('.', '_')}")
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


def attach(statements: list[dict], records: list[dict], thread_of_client: dict) -> list[list[dict]]:
    """The cop (or mpp) records of each statement: those of its connection's
    server thread that lie inside the statement's own interval."""
    by_thread: dict[int, list[dict]] = {}
    for c in records:
        by_thread.setdefault(c["thread"], []).append(c)
    for spans in by_thread.values():
        spans.sort(key=lambda c: c["t0"])
    out = []
    cursor = {th: 0 for th in by_thread}
    for s in statements:
        th = thread_of_client.get(s["client"])
        spans = by_thread.get(th, [])
        i = cursor.get(th, 0)
        while i < len(spans) and spans[i]["t0"] < s["t0"]:
            i += 1
        mine = []
        while i < len(spans) and spans[i]["t1"] <= s["t1"]:
            mine.append(spans[i])
            i += 1
        cursor[th] = i
        out.append(mine)
    return out


def run(args) -> int:
    if not os.path.isdir(os.path.join(ROOT, "tidb_tpu")):
        print(f"benchmark: no system under test beside {HERE} (tidb_tpu/ is missing)", file=sys.stderr)
        return 2
    bench, cell, config = find_cell(args.workload)
    want = args.platform or "tpu"
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.scale is not None:
        if want == "tpu":
            raise SystemExit("benchmark: --scale is for the rehearsal (--platform cpu) only")
        config["scale_factor"] = args.scale
    # the program takes the compile cache where this variable says; the path
    # is fixed inside the checkout because it is part of the cache's key
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CACHE, "xla"))
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)

    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: jax found no device: {e}", file=sys.stderr)
        return 2
    if devs[0].platform != want or len(devs) < cell["chips"]:
        print(f"benchmark: needs {cell['chips']} {want} device(s); jax reports {len(devs)} x {devs[0].platform!r}"
              + ("" if want == "cpu" else " (the rehearsal is --platform cpu --scale 0.01)"), file=sys.stderr)
        return 2
    compiles = {"n": 0, "s": 0.0}

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    from harness import check, spans, trace_reduce
    from harness.traffic import Mix, Writer, run_window

    # -- set-up: data ---------------------------------------------------------
    gen = importlib.import_module(f"generators.{config['generator']}")
    mix = Mix(cell["traffic"], args.seed)
    tables = gen.generate(args.seed, config)
    rows = {t: len(cols[0]) for t, cols in tables.items()}
    log(f"generated {rows}")

    import tidb_tpu
    from tidb_tpu.executor.load import bulk_load
    from tidb_tpu.server import Client, Server

    db = tidb_tpu.open(region_split_keys=int(config["store"]["region_split_keys"]))
    for name in config["load_order"]:
        db.execute(config["tables"][name]["ddl"])
        n = len(tables[name][0])
        batch = int(config["store"]["load_batch_rows"])  # a deployment loads in batches; regions split as they grow
        for lo in range(0, n, batch):
            bulk_load(db, name, [c[lo : lo + batch] for c in tables[name]])
    # the reference keeps only the columns some template of this cell reads
    read_cols: dict[str, set] = {}
    for tpl in mix.templates.values():
        for t, cs in tpl.tables.items():
            read_cols.setdefault(t, set()).update(cs)
    columns = {
        t: {c: col for c, col in zip(gen.COLUMNS[t], tables[t]) if c in read_cols[t]} for t in read_cols
    }
    del tables
    gc.collect()
    log(f"loaded into {len(db.store.regions())} regions")

    # -- set-up: serve, connect, warm -------------------------------------------
    rec = spans.Recorder()
    spans.install(rec)
    server = Server(db)
    port = server.start()

    def connect():
        c = Client(port=port, db="test")
        for sql in config["session"]:
            c.query(sql)
        return c

    clients = [connect() for _ in range(mix.clients)]
    writer = None
    if mix.writer:
        txns = gen.refresh_transactions(args.seed, config, int(mix.writer["max_transactions"]))
        writer = Writer(Client(port=port, db="test"), txns, mix.writes_per_statement(float(config["scale_factor"])), rec)
        # a read BEFORE the first write builds the device's base block; the
        # set-up transactions then sit in the delta, where the window's will
        # join them, and the warm-up below compiles the delta kernel variants
        for tpl in mix.templates.values():
            clients[0].query(tpl.first_text)  # not texts[0]: the seed orders those, and this one compiles
        log("base blocks built by a first read")
        for i in range(int(mix.writer["setup_transactions"])):
            writer.commit_next()
            if i == 0:
                log("first write transaction acknowledged")
        log(f"writer committed {len(writer.log)} transactions in set-up")
    thread_of_client = {}
    for k, c in enumerate(clients):
        for tpl in mix.templates.values():
            for text in tpl.texts:
                c.query(text)
        cop, mpp = rec.drain()  # a statement shows cop tasks, gathers or both: either names the thread
        thread_of_client[k] = (cop or mpp)[-1]["thread"] if cop or mpp else None
        log(f"client {k} warmed ({compiles['n']} compiles so far, {compiles['s']:.1f}s)")
    for c in clients:  # a second pass: nothing may be left to compile or to cache
        for tpl in mix.templates.values():
            c.query(tpl.texts[0])
    rec.drain()
    setup_written = len(writer.log) if writer else 0
    gc.collect()

    # -- the window ---------------------------------------------------------------
    trace_dir = os.path.join(CACHE, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        rec.annotate = True
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles_before = compiles["n"]
    setup_s = time.time() - T_PROCESS
    log(f"window opens (setup_s {setup_s:.1f})")
    statements, t0, t1 = run_window(mix, clients, writer, args.seconds, rec)
    if args.trace:
        jax.profiler.stop_trace()
        rec.annotate = False
    compiles_in_window = compiles["n"] - compiles_before
    log(f"window closed: {len(statements)} statements in {t1 - t0:.2f}s"
        + (f", {len(writer.log) - setup_written} write transactions" if writer else ""))

    log(f"{len(db.store.regions())} regions at the close")
    stats = [d.memory_stats() or {} for d in devs[: cell["chips"]]]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    cop, mpp = rec.drain()
    log(f"{len(cop)} cop spans, {len(mpp)} gathers (ndev {sorted({g['ndev'] for g in mpp if g['raised'] is None})}, "
        f"{sum(g['compiles'] or 0 for g in mpp)} programs built, {sum(1 for g in mpp if g['retries'])} re-planned, "
        f"{sum(1 for g in mpp if g['raised'])} gave up)")
    write_log = writer.log if writer else []
    for c in clients + ([writer.conn] if writer else []):
        c.close()
    server.close()
    del db, server
    gc.collect()

    # -- correct? -----------------------------------------------------------------
    written = [t["rows"] for t in (writer.transactions[: len(write_log)] if writer else [])]
    cop_by_stmt = attach(statements, cop, thread_of_client)
    mpp_by_stmt = attach(statements, mpp, thread_of_client)
    answers = check.Answers(mix, columns, written)
    checks = check.judge(statements, cop_by_stmt, mpp_by_stmt, answers, write_log, config, cell["chips"])
    if writer and writer.errors:
        log(f"writer errors: {writer.errors[:3]}")
    for s in statements:
        if s["error"]:
            log(f"statement failed: {s['error']}")
            break
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log("judged")
    controls = (check.controls(statements, cop_by_stmt, mpp_by_stmt, answers, write_log, config, cell["chips"])
                if args.control else None)

    # -- metrics ------------------------------------------------------------------
    kept = [i for i, s in enumerate(statements) if s["error"] is None]
    ok = [statements[i] for i in kept]
    ctx = types.SimpleNamespace(  # what a metric reader may read
        cell=cell, config=config, mix=mix, rows=rows, statements=ok,
        cop_by_stmt=[cop_by_stmt[i] for i in kept], mpp_by_stmt=[mpp_by_stmt[i] for i in kept], cop=cop, mpp=mpp,
        window=(t0, t1), window_s=t1 - t0, setup_s=setup_s,
        write_log=[w for w in write_log[setup_written:] if not w.get("failed")],
        compiles_in_window=compiles_in_window, trace=None, trace_window=None, device_kind=devs[0].device_kind, platform=devs[0].platform, here=HERE,
    )
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": len(statements), "failed": sum(1 for s in statements if s["error"])}
    breakdown = None
    if args.trace:
        path = trace_reduce.newest_xplane(trace_dir)
        red = trace_reduce.reduce_file(path, devs[0].platform) if path else trace_reduce.Reduced()
        # on the trace's clock the window runs from the first statement
        # span's start to the last one's end
        stmt_spans = red.spans.get("stmt", [])
        w_lo = stmt_spans[0][0] if stmt_spans else 0.0
        w_hi = max((b for _, b in stmt_spans), default=t1 - t0)
        ctx.trace, ctx.trace_window = red, (w_lo, w_hi)
        device["busy_s"] = red.busy_s(w_lo, w_hi)
        device["window_s"] = w_hi - w_lo
        breakdown = {
            "device_ops": red.top_ops(w_lo, w_hi, 10),
            "idle_gaps": red.idle_by_span(
                w_lo, w_hi, [("exec", "device_exec"), ("cop", "cop"), ("mpp", "mpp_gather"), ("stmt", "frontend"), ("writer", "writer")],
                "between-statements")[:10],
        }
        log(f"trace reduced: {path} lines {sorted(set(red.lines_seen))[:12]}")
        result["metrics"] = read_metrics("layer_metrics", metric_names(bench, "per_layer", cell["name"]), ctx)
    else:
        result["metrics"] = read_metrics("end_to_end", metric_names(bench, "end_to_end", cell["name"]), ctx)
    result["device"] = device
    if breakdown:
        result["breakdown"] = breakdown
    if args.platform or args.scale is not None:
        result["rehearsal"] = True
    if controls is not None:
        result["controls"] = controls
        log(f"controls: {controls}")
    result["checks"] = checks
    for name, c in checks.items():
        print(f"benchmark: check {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    return run(parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
