"""The harness sees a broken timed path, and the controls, as not correct.

Each case drives a whole run off the chip (CPU backend, a tiny scale factor):
`faults.py` plants the fault in the program at run time and then calls the
harness's own entry. Slow (each case loads, warms and runs a window): run by
hand, `python3 -m pytest benchmark/tests -q`; not part of tier-1.
"""

import json
import os
import subprocess
import sys

import pytest
import test_join_cell_is_files_only as join_cell

HERE = os.path.dirname(os.path.abspath(__file__))
STATIC = ["--workload", "tpch_sf2.q1q6_1c", "--scale", "0.02", "--seconds", "2"]
HTAP = ["--workload", "tpch_sf1_htap.rf1_q1q6", "--scale", "0.08", "--seconds", "4"]


def drive(fault: str, cell: list[str], *more: str) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "faults.py"), fault, *cell, "--seed", "2147483659",
           "--trace", "0", "--platform", "cpu", *more]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert list(line)[-1] == "checks"  # the numbers compared come last
    for name, c in line["checks"].items():
        assert f"check {name} = {c['value']} (limit {c['limit']})" in p.stderr
    return line


def test_sound_run_is_correct_and_controls_are_not():
    line = drive("none", STATIC, "--control")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 5
    assert all(c["value"] == 0 for c in line["checks"].values())
    ctl = line["controls"]["float32"]
    assert ctl["correct"] is False and ctl["answers_wrong"] == line["attempted"]


@pytest.mark.parametrize("fault,number", [
    ("altered_answer", "answers_wrong"),
    ("half_rows", "answers_wrong"),
    ("host_engine", "not_on_device"),
])
def test_static_cell_faults_come_out_not_correct(fault, number):
    line = drive(fault, STATIC)
    assert line["correct"] is False
    assert line["checks"][number]["value"] == line["attempted"]
    others = [n for n, c in line["checks"].items() if n != number and c["value"]]
    assert others == []  # each fault is caught by the number that is its to catch


def test_htap_sound_run_and_its_controls():
    line = drive("none", HTAP, "--control")
    assert line["correct"] is True and line["attempted"] >= 6
    assert set(line["checks"]) >= {"answers_stale", "no_delta_read", "writes_failed"}
    assert line["controls"]["float32"]["correct"] is False
    stale = line["controls"]["stale"]
    assert stale["correct"] is False and stale["answers_stale"] >= 1 and stale["answers_wrong"] == 0
    assert line["metrics"]["write_txn_per_s"]["value"] > 0


def test_htap_stale_snapshot_comes_out_not_correct():
    line = drive("stale_snapshot", HTAP)
    assert line["correct"] is False
    assert line["checks"]["answers_stale"]["value"] >= 1
    assert line["checks"]["answers_wrong"]["value"] == 0


@pytest.mark.parametrize("chips", [1, 4])
def test_gathers_on_fewer_devices_than_the_cell_asks_for_come_out_not_on_device(tmp_path, chips):
    """No accepted cell has a gather, so the fault runs in the copy with the
    two-table join cell of `test_join_cell_is_files_only.py`, on as many CPU
    devices as the cell's `chips`. One chip: none is left, the gather gives up
    and the host executor answers. Four: the gather runs on three."""
    root = tmp_path / "checkout"
    join_cell.build(root, scale=0.02, chips=chips)
    if chips > 1:  # the sound drive first: a gather over the cell's four devices is on the device
        line = join_cell.result(join_cell.drive(root, "tiny_join.q3ol_1c", 0, devices=chips))
        assert line["correct"] is True and line["device"]["count"] == chips and line["attempted"] >= 3
    line = join_cell.result(join_cell.drive(root, "tiny_join.q3ol_1c", 0, fault="mpp_fewer_devices", devices=chips))
    assert line["correct"] is False and line["attempted"] >= 3
    assert line["checks"]["not_on_device"]["value"] == line["attempted"]
    assert line["checks"]["answers_wrong"]["value"] == 0 and line["checks"]["statements_failed"]["value"] == 0
