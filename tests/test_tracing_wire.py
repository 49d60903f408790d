"""The statement outside its cop task (ISSUE 37, OBSERVABILITY.md "Spans on
the device's clock"): spans at the wire (``conn.command``, ``conn.write``),
in the session (``statement`` says ``type`` and ``ast``; ``stmt.finish``) and
round the executor (``executor.build``, ``result.rows``), all through the one
seam, all under the statement's ``stmt`` id.

What must hold: under a live profiler session a statement served over the wire
writes each of them with its id and they nest strictly; a connection's wait for
its client is under no span; off, every new site gets the one shared null
context and builds no Tracer; TRACE shows the new children."""

import glob

import pytest

import tidb_tpu
from tidb_tpu.server import Client, Server
from tidb_tpu.utils import tracing

NEW_SPANS = ["conn.command", "conn.write", "executor.build", "result.rows", "stmt.finish"]
TEXT = "SELECT f, SUM(v), COUNT(*) FROM t WHERE k < 5 GROUP BY f"


@pytest.fixture(scope="module")
def wire():
    db = tidb_tpu.open(region_split_keys=200)
    db.execute("CREATE TABLE t (id INT PRIMARY KEY, k INT, v DECIMAL(10,2), f CHAR(1))")
    for lo in range(0, 600, 100):
        db.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i % 7},{i}.50,'{'AB'[i % 2]}')" for i in range(lo, lo + 100)))
    server = Server(db)
    port = server.start()
    c = Client(port=port, db="test")
    c.query("SET tidb_isolation_read_engines = 'tpu'")
    c.query(TEXT.replace("< 5", "< 4"))  # the programs are compiled before anything is timed
    yield db, port, c
    c.close()
    server.close()


@pytest.fixture(scope="module")
def profiled(wire, tmp_path_factory):
    """A text seen for the first time, the same text again, the text on a
    second connection and a ping, served under a `jax.profiler` session: the
    `tidb:` events as dicts of their stats plus `_t0`, `_t1` (ns) and `_line`."""
    import jax
    from jax.profiler import ProfileData

    _, port, c = wire
    d = str(tmp_path_factory.mktemp("prof"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        first = c.query(TEXT)
        assert c.query(TEXT) == first and len(first) == 2
        assert c.ping()
        other = Client(port=port, db="test")
        other.query("SET tidb_isolation_read_engines = 'tpu'")
        assert other.query(TEXT) == first
        other.close()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
    events: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    e = dict(ev.stats)
                    e.update(_t0=ev.start_ns, _t1=ev.start_ns + ev.duration_ns, _line=(plane.name, li))
                    events.setdefault(ev.name[len(tracing.PREFIX):], []).append(e)
    for evs in events.values():
        evs.sort(key=lambda e: e["_t0"])
    return events


def _selects(events):
    """The three servings of TEXT, in order: first connection twice, then the second."""
    out = [e for e in events["statement"] if e.get("type") == "Select"]
    assert len(out) == 3, events["statement"]
    return out


def _inside(inner, outer):
    return outer["_t0"] <= inner["_t0"] and inner["_t1"] <= outer["_t1"]


@pytest.mark.parametrize("name", NEW_SPANS)
def test_a_served_statement_writes_each_new_span_with_its_id(profiled, name):
    for st in _selects(profiled):
        mine = [e for e in profiled[name] if e.get("stmt") == st["stmt"]]
        assert len(mine) == 1, (name, st["stmt"], profiled[name])
        assert mine[0]["_line"] == st["_line"]  # the connection's own thread


@pytest.mark.parametrize("outer,inner", [
    ("conn.command", "statement"), ("conn.command", "conn.write"), ("statement", "plan"),
    ("statement", "execute"), ("execute", "executor.build"), ("execute", "cop.task"),
    ("statement", "result.rows"), ("statement", "stmt.finish"),
])
def test_the_spans_of_a_statement_nest(profiled, outer, inner):
    for st in _selects(profiled):
        (o,) = [e for e in profiled[outer] if e.get("stmt") == st["stmt"]]
        kids = [e for e in profiled[inner] if e.get("stmt") == st["stmt"]]
        assert kids and all(_inside(k, o) for k in kids), (outer, inner, st["stmt"])


def test_the_statements_spans_follow_one_another(profiled):
    for st in _selects(profiled):
        one = {n: next(e for e in profiled[n] if e.get("stmt") == st["stmt"])
               for n in ["plan", "execute", "result.rows", "stmt.finish", "conn.write"]}
        order = ["plan", "execute", "result.rows", "stmt.finish"]
        for a, b in zip(order, order[1:]):
            assert one[a]["_t1"] <= one[b]["_t0"], (a, b)
        assert st["_t1"] <= one["conn.write"]["_t0"]  # the response is written once the statement has ended


def test_statement_says_its_class_and_how_its_text_met_the_cache(profiled):
    first, second, other = _selects(profiled)
    assert (first["ast"], second["ast"]) == ("parse", "session")
    assert other["ast"] in ("instance", "parse")  # a fresh session: the instance's lane, where it is on
    assert len([e for e in profiled["parse"] if e.get("stmt") == first["stmt"]]) == 1
    assert not [e for e in profiled["parse"] if e.get("stmt") == second["stmt"]]
    sets = [e for e in profiled["statement"] if e.get("type") == "SetVariable"]
    assert sets and all(e["ast"] == "parse" for e in sets)  # never cached: not a Select
    assert {e["cache"] for e in profiled["plan"]} <= {"hit", "miss"}


def test_conn_command_says_what_it_served_and_the_wait_is_under_no_span(profiled):
    cmds = profiled["conn.command"]
    assert [e["cmd"] for e in cmds] == ["query", "query", "ping", "query", "query"]
    for st in _selects(profiled):
        (e,) = [x for x in cmds if x.get("stmt") == st["stmt"]]
        assert st["stmt"] == f"c{int(e['conn'])}.{st['stmt'].split('.')[1]}"
        assert int(e["rows"]) == 2 and int(e["bytes_out"]) > 50
        (w,) = [x for x in profiled["conn.write"] if x.get("stmt") == st["stmt"]]
        assert w["_t1"] <= e["_t1"]
    ping = cmds[2]
    assert "stmt" not in ping and "rows" not in ping and int(ping["bytes_out"]) == 11  # header + OK packet
    mine = [e for e in cmds if e["conn"] == cmds[0]["conn"]]
    assert len(mine) == 3 and len({int(e["conn"]) for e in cmds}) == 2
    for a, b in zip(mine, mine[1:]):
        assert a["_t1"] < b["_t0"]  # the client's turn lies between two commands, not inside one


@pytest.mark.parametrize("name", ["statement"] + NEW_SPANS)
def test_seam_off_every_new_site_gets_the_shared_null_context(wire, monkeypatch, name):
    _, _, c = wire

    class Boom(tracing.Tracer):
        def __init__(self, *a, **k):
            raise AssertionError("Tracer constructed with tracing off")

    got: list[tuple] = []
    real = tracing.region

    def region(n, *a, **k):
        r = real(n, *a, **k)
        got.append((n, r))
        return r

    monkeypatch.setattr(tracing, "Tracer", Boom)
    monkeypatch.setattr(tracing, "region", region)
    assert not tracing.profiling()
    assert len(c.query(TEXT)) == 2
    mine = [r for n, r in got if n == name]
    assert mine and all(r is tracing._NULL for r in mine), (name, got)


def test_trace_shows_the_new_children(wire):
    db, _, _ = wire
    s = db.session()
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    rows = s.query("TRACE " + TEXT)
    names = [(len(label) - len(label.lstrip(" "))) // 2 * " " + label.lstrip(" └─") for label, _, _ in rows]
    assert names[0] == "select" and " plan" in names and " execute" in names
    i = names.index(" execute")
    assert names[i + 1] == "  executor.build"  # the first child of `execute`, as deep as its cop tasks
    assert any(n.startswith("  cop.") for n in names[i + 2:])
    assert names[-1] == " result.rows"
