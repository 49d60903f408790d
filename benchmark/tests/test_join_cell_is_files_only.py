"""A later PR adds a cell whose statements read several tables and are
answered by an MPP gather as NEW FILES and new BENCHMARK.json entries,
editing nothing that exists. Shown on a copy of the benchmark, in the manner
of `test_new_cell_is_files_only.py`:

  (i)   `orders JOIN lineitem` with Q3's filters, group-by and limit, in a
        cell of its own: `correct`, one gather a statement, no cop span;
  (ii)  TPC-H Q3 as `queries/q3.json` has it: every answer right, and on the
        program as it stands NOT `correct`, `not_on_device` = every statement
        (its joins, aggregate and TopN run in the host executor over `tpu`
        cop readers). The control for the rule `"answered_by": "mpp"`: when a
        later PR moves Q3 onto the gather this half is turned round, not
        deleted;
  (iii) a template over several tables beside a writer is refused at start-up.

    python3 benchmark/tests/test_join_cell_is_files_only.py <directory>

builds the copy with cell (i) at the configuration's own size in <directory>
(for a chip run of a join cell that is committed nowhere).
"""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

# cell (i)'s statement: Q3 without `customer`; its reference is Q3's own join and TopN
OL_REFERENCE = '''"""Q3's join of orders and lineitem, its group-by and its ten rows, without the customer filter."""
from reference import q3
from reference.common import date_text, day_of

TABLES = ("orders", "lineitem")
rows, same = q3.rows, q3.same


def bind(drawn):
    return {"date": date_text(day_of(1995, 3, drawn["day"]))}


def state(cols, drawn, control=False):
    day = day_of(1995, 3, drawn["day"])
    return q3.top(cols["orders"], cols["lineitem"], cols["orders"]["o_orderdate"] < day, day, control)
'''
OL_TEMPLATE = {
    "name": "q3_ol", "answered_by": "mpp",
    "tables": {"orders": ["o_orderkey", "o_orderdate", "o_shippriority"],
               "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]},
    "sql": "SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue, o_orderdate, o_shippriority"
           " FROM orders, lineitem WHERE l_orderkey = o_orderkey AND o_orderdate < DATE '{date}' AND l_shipdate > DATE '{date}'"
           " GROUP BY l_orderkey, o_orderdate, o_shippriority ORDER BY revenue DESC, o_orderdate LIMIT 10",
    "params": {"day": [1, 31]}, "pool": [{"day": 15}, {"day": 1}, {"day": 31}, {"day": 8}],
}
READERS = {  # per-layer metrics of this PR's own, read from the gather's records
    "mpp_gathers_per_stmt": ("count", '''"""MPP gathers per statement."""
UNIT = "count"


def read(ctx):
    return sum(len(g) for g in ctx.mpp_by_stmt) / len(ctx.statements) if ctx.statements else None
'''),
    "mpp_gather_p50_ms": ("ms", '''"""The median gather, `MPPGatherExec.execute` entered to returned."""
import statistics

UNIT = "ms"


def read(ctx):
    walls = [g["t1"] - g["t0"] for mine in ctx.mpp_by_stmt for g in mine]
    return 1e3 * statistics.median(walls) if walls else None
'''),
}


def build(root, scale=None, chips=1) -> dict:
    """A copy of the benchmark under ``root`` with the join cells added as new
    files and entries (``scale``: a small configuration of its own; ``chips``:
    what the cells ask for). Returns the files that were there, for `unedited`."""
    root = os.fspath(root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"), ignore=shutil.ignore_patterns("__pycache__", "test_*", "recorded_*"))
    os.symlink(os.path.join(ROOT, "tidb_tpu"), os.path.join(root, "tidb_tpu"))
    before = {}
    for folder, _, names in os.walk(os.path.join(root, "benchmark")):
        for n in names:
            with open(os.path.join(folder, n), "rb") as f:
                before[os.path.join(folder, n)] = f.read()

    def write(path, text):
        assert not os.path.exists(os.path.join(root, path)), path  # new files only
        with open(os.path.join(root, path), "w") as f:
            f.write(text)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = "tpch_sf2"
    if scale is not None:
        with open(os.path.join(root, "benchmark/configs/tpch_sf2.json")) as f:
            cfg = json.load(f)
        cfg.update(name="tiny_join", scale_factor=scale)
        write("benchmark/configs/tiny_join.json", json.dumps(cfg))
        bench["configs"].append({"name": "tiny_join", "source": "test", "file": "benchmark/configs/tiny_join.json",
                                 "reduced": ["scale_factor", "tables"], "why": "test"})
        config = "tiny_join"
    write("benchmark/queries/q3_ol.json", json.dumps(OL_TEMPLATE))
    write("benchmark/reference/q3_ol.py", OL_REFERENCE)
    for name, (_, text) in READERS.items():
        write(f"benchmark/layer_metrics/{name}.py", text)
    mixes = {"q3ol_1c": {"cycle": ["q3_ol"], "writer": None}, "q3_1c": {"cycle": ["q3"], "writer": None},
             "rf1_q3": {"cycle": ["q6", "q3"], "writer": {"stream": "rf1", "orders_per_refresh_per_sf": 1500, "statements_per_refresh": 22,
                                                         "setup_transactions": 2, "max_transactions": 50}}}
    for name, mix in mixes.items():
        write(f"benchmark/traffic/{name}.json", json.dumps(dict(mix, name=name, loop="closed", clients=1, think_ms=0)))
        bench["workloads"].append({"name": f"{config}.{name}", "config": config, "traffic": name, "chips": chips, "why": "test"})
    for name, (unit, _) in READERS.items():
        bench["per_layer"].append({"name": name, "unit": unit, "better": "lower", "source": "program_span", "layer": "mpp gather",
                                   "moves": "stmt_per_s", "workloads": [f"{config}.q3ol_1c", f"{config}.q3_1c"]})
    write("BENCHMARK.json", json.dumps(bench))
    return before


def unedited(before: dict) -> bool:
    for path, data in before.items():
        with open(path, "rb") as f:
            if f.read() != data:
                return False
    return True


def drive(root, workload: str, trace: int, seconds: str = "3", fault: str = None, devices: int = 1) -> subprocess.CompletedProcess:
    """One rehearsal of ``workload`` in the copy, on ``devices`` CPU devices
    (as many as the cell's `chips`: a gather takes every device it finds);
    with ``fault``, through the copy's `tests/faults.py`."""
    entry = [os.path.join(root, "benchmark/tests/faults.py"), fault] if fault else [os.path.join(root, "benchmark/run.py")]
    cmd = [sys.executable, *entry, "--workload", workload, "--seed", "2147483777",
           "--seconds", seconds, "--trace", str(trace), "--platform", "cpu"]
    env = dict(os.environ, XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(cmd, capture_output=True, text=True, timeout=900, cwd=root, env=env)


def result(p: subprocess.CompletedProcess) -> dict:
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_two_table_gather_cell_needs_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    before = build(root, scale=0.02)
    line = result(drive(root, "tiny_join.q3ol_1c", 1))
    assert line["correct"] is True and line["attempted"] >= 4, line
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["metrics"]["mpp_gathers_per_stmt"]["value"] == 1.0  # one gather a statement
    assert 0 < line["metrics"]["mpp_gather_p50_ms"]["value"] < 1e3 * line["device"]["window_s"]
    assert "cop_regions_per_task" not in line["metrics"]  # and no cop task: its reader finds no span to read
    assert any(label == "mpp_gather" for label, _ in line["breakdown"]["idle_gaps"])  # the bench:mpp span is in the trace
    assert unedited(before)


def test_q3_is_answered_right_and_today_off_the_device(tmp_path):
    root = tmp_path / "checkout"
    before = build(root, scale=0.002)  # the host cross join: customer x orders pairs, 900,000 at this scale
    line = result(drive(root, "tiny_join.q3_1c", 0, seconds="2"))
    checks = {k: c["value"] for k, c in line["checks"].items()}
    assert line["attempted"] >= 2 and checks["answers_wrong"] == 0 and checks["statements_failed"] == 0
    # TURN ROUND, do not delete, when the program answers Q3 through the gather:
    # then `correct` is True and not_on_device 0
    assert line["correct"] is False and checks["not_on_device"] == line["attempted"]
    assert unedited(before)


def test_tables_template_beside_a_writer_is_refused_at_start_up(tmp_path):
    root = tmp_path / "checkout"
    build(root, scale=0.002)
    p = drive(root, "tiny_join.rf1_q3", 0)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "templates over several tables (q3) beside a writer" in p.stderr


if __name__ == "__main__":
    build(sys.argv[1])
    print(f"join cells added under {sys.argv[1]}: tpch_sf2.q3ol_1c, tpch_sf2.q3_1c")
