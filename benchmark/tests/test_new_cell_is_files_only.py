"""A later PR adds a cell, a configuration, a traffic mix, a statement
template and a per-layer metric as NEW FILES and new BENCHMARK.json entries,
editing nothing that exists. Shown on a copy of the benchmark: the files are
added there, the harness is run as it stands, and the new cell reports the
new metric."""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

COUNT_REFERENCE = '''"""SELECT COUNT(*), SUM(l_quantity) over rows with at least {quantity}."""
from reference.common import dec_text, exact_sum

TABLE = "lineitem"


def bind(drawn):
    return {"quantity": str(drawn["quantity"])}


def state(cols, drawn, control=False):
    q = cols["l_quantity"][cols["l_quantity"] >= drawn["quantity"] * 100]
    return {(): (len(q), exact_sum(q))}


def rows(st):
    n, total = st[()]
    return [(str(n), dec_text(total, 2) if n else None)]
'''

STATEMENTS_READER = '''"""A per-layer metric of this PR's own: cop spans per statement."""
UNIT = "count"


def read(ctx):
    return sum(len(c) for c in ctx.cop_by_stmt) / len(ctx.statements) if ctx.statements else None
'''


def test_new_cell_needs_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "tidb_tpu"), root / "tidb_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}

    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    cfg = json.loads((root / "benchmark/configs/tpch_sf2.json").read_text())
    cfg.update(name="tiny_static", scale_factor=0.01)
    (root / "benchmark/configs/tiny_static.json").write_text(json.dumps(cfg))
    (root / "benchmark/queries/count_big.json").write_text(json.dumps({
        "name": "count_big", "table": "lineitem", "reads": ["l_quantity"],
        "sql": "SELECT COUNT(*), SUM(l_quantity) FROM lineitem WHERE l_quantity >= {quantity}",
        "params": {"quantity": [1, 50]}, "pool": [{"quantity": 10}, {"quantity": 40}],
    }))
    (root / "benchmark/reference/count_big.py").write_text(COUNT_REFERENCE)
    (root / "benchmark/traffic/two_counts.json").write_text(json.dumps({
        "name": "two_counts", "loop": "closed", "clients": 2, "cycle": ["count_big", "q6"],
        "think_ms": 0, "writer": None,
    }))
    (root / "benchmark/layer_metrics/cop_spans_per_stmt.py").write_text(STATEMENTS_READER)
    bench["configs"].append({"name": "tiny_static", "source": "test", "file": "benchmark/configs/tiny_static.json",
                             "reduced": ["scale_factor", "tables"], "why": "test"})
    bench["workloads"].append({"name": "tiny_static.two_counts", "config": "tiny_static", "traffic": "two_counts",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "cop_spans_per_stmt", "unit": "count", "better": "lower",
                               "source": "program_span", "layer": "cop dispatch + bind + decode",
                               "moves": "stmt_per_s", "workloads": ["tiny_static.two_counts"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cmd = [sys.executable, str(root / "benchmark/run.py"), "--workload", "tiny_static.two_counts", "--seed", "77",
           "--seconds", "2", "--trace", "1", "--platform", "cpu"]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600, cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 4
    assert line["metrics"]["cop_spans_per_stmt"]["value"] == 1.0
    assert "device_idle_pct" in line["metrics"] and "scan_roofline" not in line["metrics"]  # no chip, no share
    # nothing that existed was edited
    assert all(p.read_bytes() == data for p, data in before.items())
    # and the run left its cache inside that checkout, nowhere else
    assert (root / "_bench_cache").is_dir()


def test_unknown_workload_and_bare_directory_fail_without_a_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    cmd = [sys.executable, str(tmp_path / "benchmark/run.py"), "--workload", "tpch_sf2.q1q6_1c", "--seed", "1",
           "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""  # only BENCHMARK.json and paths: no system under test
    os.symlink(os.path.join(ROOT, "tidb_tpu"), tmp_path / "tidb_tpu")
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=120, cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""  # no TPU: no result, no fallback to the CPU
    assert "needs 1 tpu device" in p.stderr
