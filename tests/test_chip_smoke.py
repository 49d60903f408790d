"""chip_smoke.py's contract, as far as a machine without a chip can hold it
to: the explicit CPU rehearsal passes end to end, and everything that must
make the smoke FAIL does — a CPU it was not told to accept, a device kernel
that raises (the host answers correctly, which is exactly the quiet fallback
the smoke exists to catch), a checkout that is not there."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, timeout=300):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True, text=True,
        timeout=timeout, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_cpu_rehearsal_passes_and_reports():
    r = _run(["--platform", "cpu", "--rows", "65536"])
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the verdict: exactly these keys, the device as jax reports it
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu" and isinstance(verdict["device"]["kind"], str)
    assert isinstance(verdict["device"]["count"], int) and verdict["device"]["count"] >= 1
    # everything else rides the line before it
    assert len(lines) == 2 and lines[0].startswith("SMOKE_REPORT ")
    out = json.loads(lines[0][len("SMOKE_REPORT "):])
    assert out["device"] == verdict["device"]
    assert out["versions"]["jax"] and out["cache_dir"] and out["link"]["dispatch_sync_us"] > 0
    a, b = out["phases"]["store_server"], out["phases"]["embedded"]
    assert set(a["statements"]) == {"q1", "q3_mpp"} and a["device"]["platform"] == "cpu"
    assert {"count", "q6", "q1", "q10_topn", "q3_mpp", "window", "rollup_fused", "groupby_dict200",
            "groupby_orderkey", "point_select", "orders_sum", "q1_after_write",
            "orders_sum_after_write"} == set(b["statements"])
    # the write was read through the delta operand, and per-device memory is reported
    assert b["statements"]["q1_after_write"]["delta_rows"] >= 300
    assert len(b["bytes_in_use"]) == out["device"]["count"]


def test_refuses_a_cpu_it_was_not_told_to_accept():
    r = _run(["--rows", "65536"])
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "needs 'tpu'" in r.stderr
    assert not r.stdout.strip()  # no result line on failure


def test_fails_when_a_device_kernel_raises_although_answers_are_right():
    code = (
        "import sys, runpy\n"
        "from tidb_tpu.kv.kv import StoreType\n"
        "from tidb_tpu.utils import failpoint\n"
        "def boom(region_id, store_type):\n"
        "    if store_type == StoreType.TPU:\n"
        "        raise RuntimeError('injected device kernel failure')\n"
        "failpoint.enable('cop_task_engine', boom)\n"
        f"sys.argv = [{SMOKE!r}, '--child', 'embedded', '--rows', '65536', '--reps', '1',"
        " '--want-platform', 'cpu']\n"
        f"runpy.run_path({SMOKE!r}, run_name='__main__')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert r.returncode != 0
    assert "degraded to the host" in r.stderr and "tidb_tpu_copr_degraded_task_total" in r.stderr
    assert "wrong answer" not in r.stderr  # the host fallback answered correctly
    assert "SMOKE_RESULT" not in r.stdout


def test_fails_alone_in_a_directory(tmp_path):
    lone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run(["--platform", "cpu", "--rows", "1000"], cwd=tmp_path, script=str(lone))
    assert r.returncode != 0 and not r.stdout.strip()
