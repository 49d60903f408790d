"""The one general traffic generator. A traffic mix is a data file
(`traffic/<name>.json`): how many clients, the cycle of statement templates
each repeats (client k starts k places into it), think time, and the volume
of a refresh stream beside them. Templates are data too (`queries/<name>.json`:
text and the ranges of its substitution parameters); what a template MEANS
(how drawn parameters become literals, and its answer) is its reference's,
`reference/<name>.py`. A template names what it reads in one of two forms:
`table` + `reads` (one table; its reference gives `TABLE` and gets that
table's columns) or `tables` (`{table: [columns]}`; its reference gives
`TABLES` and gets `{table: {column: array}}`). `Template.tables` and
`Template.ref_columns` are the one place that knows both.

Closed loops: a client sends its next statement when the last one's final row
has arrived. A template's parameter sets are fixed in its file (the program
compiles one kernel per distinct literal, so sets drawn per seed would compile
anew in every run); the seed gives the order in which they are used, so every
seed does the same work in another order.
"""

from __future__ import annotations

import importlib
import json
import os
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


class Template:
    def __init__(self, name: str, seed: int, stream: int):
        self.name = name
        self.spec = load_json("queries", name)
        self.ref = importlib.import_module(f"reference.{name}")
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 100 + stream]))
        fixed = self.spec["pool"]
        for d in fixed:
            for p, (lo, hi) in self.spec["params"].items():
                if not lo <= d[p] <= hi:
                    raise ValueError(f"queries/{name}.json: {p}={d[p]} outside the spec's [{lo}, {hi}]")
        # the same parameter sets for every seed, in the seed's order
        self.drawn = [fixed[i] for i in rng.permutation(len(fixed))]
        self.texts = [self.spec["sql"].format(**self.ref.bind(d)) for d in self.drawn]
        # one text that is the same for every seed (each literal is a kernel of its own)
        self.first_text = self.spec["sql"].format(**self.ref.bind(fixed[0]))
        # what it reads, {table: [columns]}, from either form of the file
        self.joined = hasattr(self.ref, "TABLES")
        self.tables: dict[str, list[str]] = (
            {t: list(cs) for t, cs in self.spec["tables"].items()} if "tables" in self.spec
            else {self.spec["table"]: list(self.spec["reads"])}
        )
        ref_tables = list(self.ref.TABLES) if self.joined else [self.ref.TABLE]
        if sorted(ref_tables) != sorted(self.tables) or self.joined != ("tables" in self.spec):
            raise ValueError(f"queries/{name}.json reads {sorted(self.tables)} but reference/{name}.py is over {sorted(ref_tables)}"
                             " (`tables` goes with TABLES, `table` + `reads` with TABLE)")
        # "mpp": every statement must show an MPP gather (check.judge); absent: cop tasks and/or gathers
        self.answered_by = self.spec.get("answered_by")
        if self.answered_by not in (None, "mpp"):
            raise ValueError(f"queries/{name}.json: answered_by is {self.answered_by!r}; only \"mpp\" is known")

    def ref_columns(self, columns: dict) -> dict:
        """Of ``columns`` ({table: {column: array}}), what the reference's
        `state` takes: one table's columns, or the tables' by name."""
        if self.joined:
            return {t: columns[t] for t in self.ref.TABLES}
        return columns[self.ref.TABLE]


class Mix:
    def __init__(self, name: str, seed: int):
        self.spec = load_json("traffic", name)
        if self.spec["loop"] != "closed":
            raise ValueError(f"traffic {name}: only closed loops are implemented, got {self.spec['loop']!r}")
        self.clients = int(self.spec["clients"])
        self.cycle = list(self.spec["cycle"])
        self.templates = {t: Template(t, seed, i) for i, t in enumerate(sorted(set(self.cycle)))}
        self.writer = self.spec.get("writer")
        joined = sorted(t for t, tpl in self.templates.items() if tpl.joined)
        if self.writer and joined:
            # a join's state is not a sum over row sets (`merge_states`), so it cannot
            # be brought up to date one acknowledged transaction at a time
            raise ValueError(f"traffic {name}: templates over several tables ({', '.join(joined)}) beside a writer are not"
                             " implemented: the reference cannot add a refresh stream's rows to a join's answer")

    def writes_per_statement(self, scale_factor: float) -> float:
        """The refresh stream's volume: one refresh function of
        `orders_per_refresh_per_sf` x SF orders to each query stream of
        `statements_per_refresh` statements (TPC-H clauses 2.6.2, 5.3.4)."""
        w = self.writer
        return float(w["orders_per_refresh_per_sf"]) * scale_factor / float(w["statements_per_refresh"])

    def schedule(self, client: int):
        """Endless (template, pool index, text) for one client: the cycle
        from the client's own offset, each template's pool in turn."""
        used = {t: client for t in self.templates}
        pos = client
        while True:
            t = self.cycle[pos % len(self.cycle)]
            tpl = self.templates[t]
            k = used[t] % len(tpl.texts)
            yield t, k, tpl.texts[k]
            used[t] += 1
            pos += 1


def run_window(mix: Mix, clients: list, writer, seconds: float, rec):
    """Drive every client (and the writer) for ``seconds``; a statement in
    flight at the deadline is waited for and counts, so the window is as long
    as its last answer takes. Returns (statements, t_start, t_end)."""
    statements: list[list[dict]] = [[] for _ in clients]
    think_s = float(mix.spec.get("think_ms", 0)) / 1e3
    start = threading.Barrier(len(clients) + (1 if writer else 0) + 1)
    stop = threading.Event()
    t_box = {}

    def client_loop(k: int, conn) -> None:
        sched = mix.schedule(k)
        out = statements[k]
        start.wait()
        deadline = t_box["t0"] + seconds
        while time.perf_counter() < deadline:
            t, idx, text = next(sched)
            rec_s = {"client": k, "template": t, "pool": idx, "rows": None, "error": None}
            if writer:
                writer.statement_sent()
            rec_s["t0"] = time.perf_counter()
            try:
                with rec.span("stmt"):
                    rec_s["rows"] = conn.query(text)
            except Exception as e:  # a failed statement is counted, not fatal
                rec_s["error"] = f"{type(e).__name__}: {e}"
            rec_s["t1"] = time.perf_counter()
            out.append(rec_s)
            if think_s:
                time.sleep(think_s)

    threads = [threading.Thread(target=client_loop, args=(k, c), name=f"bench-client-{k}") for k, c in enumerate(clients)]
    if writer:
        threads.append(threading.Thread(target=writer.loop, args=(start, stop), name="bench-writer"))
    for th in threads:
        th.start()
    t_box["t0"] = time.perf_counter()
    start.wait()
    for th in threads[: len(clients)]:
        th.join()
    t_end = time.perf_counter()
    stop.set()
    for th in threads[len(clients):]:
        th.join()
    flat = sorted((s for per in statements for s in per), key=lambda s: s["t0"])
    return flat, t_box["t0"], t_end


class Writer:
    """One write session, the refresh stream: transaction after transaction
    (BEGIN, the statements, COMMIT), each sent when the one before is
    acknowledged. It has no pace of its own, only a volume: `per_statement`
    transactions to each analytic statement the clients have sent, so it runs
    ahead to its share and then waits for the next statement. Keeps for each
    transaction when BEGIN was sent, when COMMIT was sent and when it was
    acknowledged."""

    def __init__(self, conn, transactions: list[dict], per_statement: float, rec):
        self.conn = conn
        self.transactions = transactions
        self.per_statement = per_statement
        self.rec = rec
        self.log: list[dict] = []
        self.errors: list[str] = []
        self._due = threading.Condition()
        self._sent = 0
        self._before = 0  # transactions of set-up, outside the volume

    def statement_sent(self) -> None:
        with self._due:
            self._sent += 1
            self._due.notify()

    def _owed(self) -> bool:
        return len(self.log) - self._before < int(self._sent * self.per_statement)

    def commit_next(self) -> None:
        txn = self.transactions[len(self.log)]
        entry = {"t_begin": time.perf_counter()}
        with self.rec.span("writer"):
            self.conn.query("BEGIN")
            for sql in txn["sql"]:
                self.conn.query(sql)
            entry["t_commit_sent"] = time.perf_counter()
            self.conn.query("COMMIT")
        entry["t_ack"] = time.perf_counter()
        self.log.append(entry)

    def loop(self, start, stop) -> None:
        self._before = len(self.log)
        start.wait()
        while not stop.is_set() and len(self.log) < len(self.transactions):
            with self._due:
                if not self._owed():
                    self._due.wait(0.05)  # woken by the next statement; the timeout only sees `stop`
                    continue
            try:
                self.commit_next()
            except Exception as e:
                self.errors.append(f"{type(e).__name__}: {e}")
                # the transaction's fate is unknown: count it as written-maybe
                self.log.append({"t_begin": time.perf_counter(), "t_commit_sent": 0.0, "t_ack": float("inf"), "failed": True})
