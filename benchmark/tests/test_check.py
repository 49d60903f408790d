"""The comparison's rules, without a run: what `not_on_device` holds a
statement to (cop tasks, MPP gathers, `answered_by`), that an accepted cell's
statements are judged as PR 27's harness judged them, how records are
attached to statements, and the two forms a template names its tables in."""

import gzip
import json
import os
import types

import pytest

from generators import tpch
from harness import check
from harness.traffic import Mix, Template
from layer_metrics import scan_roofline
from run import attach

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

TPU = {"engine": "tpu", "degraded": False, "h2d_bytes": 0, "delta_rows": 0, "merges": 0}
HOST = dict(TPU, engine="host")
GATHER = {"raised": None, "store": "", "ndev": 1, "retries": 0, "compiles": 0, "stage_bytes": [], "shards": []}


@pytest.mark.parametrize("tasks,gathers,answered_by,chips,on", [
    ([TPU, TPU], [], None, 1, True),  # Q1, Q6: today's rule, letter for letter
    ([TPU, HOST], [], None, 1, False),
    ([TPU, dict(TPU, degraded=True)], [], None, 1, False),
    ([], [], None, 1, False),  # nothing to show: today's `not tasks`
    ([], [GATHER], None, 1, True),  # (c) a gather is evidence too
    ([], [GATHER], "mpp", 1, True),
    ([TPU], [GATHER], "mpp", 1, True),  # a pushed-down aggregate under the gather
    ([HOST], [GATHER], "mpp", 1, False),  # (a) holds beside a gather
    ([TPU], [], "mpp", 1, False),  # (d) tpu readers, the join in the host executor
    ([TPU], [dict(GATHER, raised="MPPRetryExhausted", ndev=None, store=None)], "mpp", 1, False),  # (b) gave up: re-planned for the host
    ([TPU], [dict(GATHER, raised="MPPRetryExhausted", ndev=None, store=None)], None, 1, False),
    ([], [dict(GATHER, ndev=1)], "mpp", 4, False),  # (b) fewer devices than the cell's chips
    ([], [dict(GATHER, ndev=8)], "mpp", 4, False),  # or more
    ([], [dict(GATHER, ndev=4)], "mpp", 4, True),
    ([], [dict(GATHER, store="hybrid")], "mpp", 1, False),  # not the local mesh alone
    ([], [dict(GATHER, store="10.0.0.7:4000")], "mpp", 1, False),
    ([], [GATHER, dict(GATHER, ndev=0)], None, 1, False),  # every gather, not one of them
])
def test_on_device_rule(tasks, gathers, answered_by, chips, on):
    assert check.on_device(tasks, gathers, answered_by, chips) is on


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_judge_pr27.json.gz"), "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("run", ["static", "htap"])
@pytest.mark.parametrize("variant", ["as_recorded", "doctored"])
def test_accepted_cells_are_judged_as_pr27_judged_them(recorded, run, variant):
    """Rehearsed runs of two accepted cells (CPU, small scale) as PR 27's
    harness recorded them, and the same with faults written into the records
    (a host task, a degraded task, a statement with no task, an altered
    answer, a failed statement, two statements sent "after" writes they lack):
    today's `judge` gives the numbers PR 27's `judge` gave, gather lists empty."""
    rec = recorded[run]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == rec["workload"])
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    config["scale_factor"] = rec["scale"]
    mix = Mix(cell["traffic"], rec["seed"])
    tables = tpch.generate(rec["seed"], config)
    columns = {"lineitem": dict(zip(tpch.COLUMNS["lineitem"], tables["lineitem"]))}
    written = []
    if rec["write_log"]:  # drawn as the run drew them: the whole stream, of which the log's length was written
        stream = tpch.refresh_transactions(rec["seed"], config, int(mix.writer["max_transactions"]))
        written = [t["rows"] for t in stream[: len(rec["write_log"])]]
    pre = "" if variant == "as_recorded" else "doctored_"
    statements, cops = rec[pre + "statements"], rec[pre + "cop_by_stmt"]
    got = check.judge(statements, cops, [[] for _ in statements], check.Answers(mix, columns, written), rec["write_log"], config, cell["chips"])
    assert got == rec["judged_by_pr27"][variant]
    if variant == "doctored":
        assert got["not_on_device"]["value"] == 3 and got["answers_wrong"]["value"] == 1


def test_records_attach_by_thread_and_interval():
    statements = [{"client": 0, "t0": 1.0, "t1": 2.0}, {"client": 1, "t0": 1.5, "t1": 2.5}, {"client": 0, "t0": 2.1, "t1": 3.0}]
    gathers = [{"thread": 7, "t0": 1.1, "t1": 1.9}, {"thread": 9, "t0": 1.6, "t1": 2.4}, {"thread": 7, "t0": 2.2, "t1": 2.6},
               {"thread": 7, "t0": 2.7, "t1": 2.9}, {"thread": 7, "t0": 0.2, "t1": 0.4}]  # the last: warm-up, before any statement
    got = attach(statements, gathers, {0: 7, 1: 9})
    assert [[g["t0"] for g in mine] for mine in got] == [[1.1], [1.6], [2.2, 2.7]]
    assert attach(statements, [], {0: 7, 1: 9}) == [[], [], []]


def fake_mix(**tables_by_template):
    return types.SimpleNamespace(templates={n: types.SimpleNamespace(tables=t) for n, t in tables_by_template.items()})


def test_least_bytes_sums_over_the_tables_a_statement_reads():
    with open(os.path.join(BENCH, "domains.json")) as f:
        domains = json.load(f)
    rows = {"lineitem": 1000, "orders": 250, "customer": 25}
    one = Template("q6", 1, 0)
    three = Template("q3", 1, 0)
    assert one.tables == {"lineitem": ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]} and not one.joined
    assert set(three.tables) == {"customer", "orders", "lineitem"} and three.joined and three.answered_by == "mpp"
    mix = types.SimpleNamespace(templates={"q6": one, "q3": three})
    q6_bytes = 1000 * (2 + 1 + 1 + 3)
    q3_bytes = 25 * (3 + 1) + 250 * (3 + 3 + 2 + 1) + 1000 * (3 + 3 + 1 + 2)
    stmts = [{"template": "q6"}, {"template": "q3"}, {"template": "q3"}]
    assert scan_roofline.least_bytes(mix, rows, stmts, domains) == q6_bytes + 2 * q3_bytes
    # a column without a width is an error, never 0 bytes
    with pytest.raises(KeyError, match="orders.o_totalprice"):
        scan_roofline.least_bytes(fake_mix(t={"orders": ["o_orderkey", "o_totalprice"]}), rows, [], domains)
    with pytest.raises(KeyError, match="part.p_size"):
        scan_roofline.least_bytes(fake_mix(t={"part": ["p_size"]}), rows, [], domains)


def test_reference_gets_its_tables_in_the_form_it_names():
    columns = {"lineitem": {"l": 1}, "orders": {"o": 2}, "customer": {"c": 3}, "part": {"p": 4}}
    assert Template("q1", 1, 0).ref_columns(columns) == {"l": 1}
    assert Template("q3", 1, 0).ref_columns(columns) == {"customer": {"c": 3}, "orders": {"o": 2}, "lineitem": {"l": 1}}
