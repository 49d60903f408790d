"""The comparison that decides `correct`: every answer of the window against
the plain reference, and the configuration's guarantees as far as a run can
show them. Each number compared is returned beside its limit."""

from __future__ import annotations

from decimal import Decimal, InvalidOperation

from reference.common import merge_states


def norm(rows) -> list:
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


def same_rows(got, want) -> bool:
    """Row for row; a numeric cell may differ in text only ('12.50' = '12.5'),
    never in value; strings and NULLs compare exactly."""
    if not isinstance(got, list) or len(got) != len(want):
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


class Answers:
    """Reference answers per (template, pool index), over the base columns
    and, where the configuration has writes, over every prefix of the
    acknowledged transactions (the writer is one session, so they are ordered)."""

    def __init__(self, mix, columns: dict, written: list[dict], control: bool = False):
        self.mix = mix
        self.columns = columns
        self.written = written
        self.control = control
        self._states: dict = {}  # (template, pool) -> the state after 0, 1, 2, ... transactions
        self._rows: dict = {}

    def rows(self, template: str, pool: int, prefix: int = 0) -> list:
        key = (template, pool, prefix)
        if key not in self._rows:
            tpl = self.mix.templates[template]
            self._rows[key] = norm(tpl.ref.rows(self._state(template, pool, prefix)))
        return self._rows[key]

    def _state(self, template: str, pool: int, prefix: int):
        tpl = self.mix.templates[template]
        ref, drawn = tpl.ref, tpl.drawn[pool]
        states = self._states.setdefault((template, pool), [])
        if not states:
            states.append(ref.state(tpl.ref_columns(self.columns), drawn, control=self.control))
        while len(states) <= prefix:  # only one-table templates stand beside a writer (traffic.Mix)
            step = ref.state(tpl.ref_columns(self.written[len(states) - 1]), drawn, control=self.control)
            states.append(merge_states(states[-1], step))
        return states[prefix]

    def same(self, got: list, template: str, pool: int, prefix: int = 0) -> bool:
        """Is ``got`` (normed rows) this statement's answer? Row for row
        against `rows`, unless the reference judges for itself (`same(got,
        state)`: a statement whose text leaves the order of tied rows open)."""
        ref = self.mix.templates[template].ref
        if hasattr(ref, "same"):
            return ref.same(got, self._state(template, pool, prefix))
        return same_rows(got, self.rows(template, pool, prefix))


def prefix_bounds(s: dict, write_log: list[dict]) -> tuple[int, int]:
    """Of the writer's ordered transactions, how many a statement's answer
    MUST hold (acknowledged before it was sent) and how many it MAY hold
    (COMMIT sent before its answer arrived)."""
    lo = sum(1 for w in write_log if w["t_ack"] <= s["t0"])
    hi = sum(1 for w in write_log if w["t_commit_sent"] < s["t1"] or w.get("failed"))
    return lo, max(lo, hi)


def on_device(tasks: list[dict], gathers: list[dict], answered_by, chips: int) -> bool:
    """A statement ran on the device iff (a) every cop task of it was answered
    by the `tpu` engine, undegraded; (b) every MPP gather of it ran on exactly
    the cell's ``chips`` devices of the local mesh and did not raise (a gather
    that gives up, MPPRetryExhausted, leaves the statement to the host
    executor); (c) it shows at least one of either; (d) where its template says
    `"answered_by": "mpp"`, at least one gather: readers that were `tpu` cop
    tasks under a join, aggregate and TopN in the host executor are not it."""
    if any(t["engine"] != "tpu" or t["degraded"] for t in tasks):
        return False
    if any(g["raised"] is not None or g["store"] != "" or g["ndev"] != chips for g in gathers):
        return False
    if not tasks and not gathers:
        return False
    return bool(gathers) or answered_by != "mpp"


def judge(statements, cop_by_stmt, mpp_by_stmt, answers: Answers, write_log: list[dict], config: dict, chips: int) -> dict:
    """name -> {"value", "limit"}; `correct` is every value within its limit."""
    writes = bool(config.get("writes"))
    wrong = stale = failed = off_device = no_delta = 0
    for s, cops, gathers in zip(statements, cop_by_stmt, mpp_by_stmt):
        if s["error"] is not None:
            failed += 1
            continue
        got = norm(s["rows"])
        if writes:
            lo, hi = prefix_bounds(s, write_log)
            if not any(answers.same(got, s["template"], s["pool"], k) for k in range(lo, hi + 1)):
                if any(answers.same(got, s["template"], s["pool"], k) for k in range(0, lo)):
                    stale += 1
                else:
                    wrong += 1
        elif not answers.same(got, s["template"], s["pool"]):
            wrong += 1
        tasks = [t for c in cops for t in c["tasks"]]
        if not on_device(tasks, gathers, answers.mix.templates[s["template"]].answered_by, chips):
            off_device += 1
        # through the delta layer: a task carried a delta as its operand, or (the delta
        # past its capacity) folded it into the base on the way, as the program does
        if writes and config["writes"].get("expect_delta_reads") and not any(t["delta_rows"] > 0 or t["merges"] > 0 for t in tasks):
            no_delta += 1
    out = {
        "answers_wrong": {"value": wrong, "limit": 0},
        "statements_failed": {"value": failed, "limit": 0},
        "not_on_device": {"value": off_device, "limit": 0},
    }
    if writes:
        out["answers_stale"] = {"value": stale, "limit": 0}
        out["writes_failed"] = {"value": sum(1 for w in write_log if w.get("failed")), "limit": 0}
        if config["writes"].get("expect_delta_reads"):
            out["no_delta_read"] = {"value": no_delta, "limit": 0}
    return out


def controls(statements, cop_by_stmt, mpp_by_stmt, answers: Answers, write_log: list[dict], config: dict, chips: int) -> dict:
    """The controls, each put in the program's place and judged like it:
    `float32`, the reference computed in float32 (breaks "exact answers");
    `stale`, the exact reference without the last transaction acknowledged
    before each statement was sent (breaks read-your-acknowledged-writes).
    Either must come out not correct."""
    low = Answers(answers.mix, answers.columns, answers.written, control=True)
    out = {}
    for name in ["float32"] + (["stale"] if config.get("writes") else []):
        stood_in = []
        for s in statements:
            lo = prefix_bounds(s, write_log)[0] if write_log else 0
            src, k = (low, lo) if name == "float32" else (answers, max(lo - 1, 0))
            stood_in.append(dict(s, rows=src.rows(s["template"], s["pool"], k), error=None))
        verdict = judge(stood_in, cop_by_stmt, mpp_by_stmt, answers, write_log, config, chips)
        out[name] = {k: v["value"] for k, v in verdict.items() if k.startswith("answers")}
        out[name]["correct"] = all(v["value"] <= v["limit"] for v in verdict.values())
    return out
