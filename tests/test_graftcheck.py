"""graftcheck (tidb_tpu/tools/check): per-rule fixture snippets, seeded
mutations of the REAL sources (the acceptance cases: an undeclared wire
verb, a load-bearing assert in kv/sharded.py, an uncached jax.jit in ops/,
a reversed two-lock nesting), suppression + baseline round-trips, --explain
output, and the python -O regression test for the converted asserts."""

import json
import os
import re
import subprocess
import sys

import pytest

from tidb_tpu.tools.check import (
    Tree,
    build_tree,
    load_baseline,
    load_rules,
    scan,
    write_baseline,
)
from tidb_tpu.tools.check.__main__ import main as check_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scan_src(path, src, rules):
    return scan(Tree({path: src}), rules=rules)


# -- rule fixtures: known violation → finding; clean shape → no finding ------


def test_verdict_scripts_are_linted_for_swallows_and_asserts_only():
    """chip_smoke.py exits with a verdict: a swallowed exception or a
    -O-stripped assert there reads as a pass. Those two rules reach into
    the corpus for it; no other rule and no other corpus file is linted."""
    src = (
        "import threading\n"
        "def main():\n"
        "    threading.Thread(target=main).start()\n"  # thread-name: package code only
        "    try:\n"
        "        run()\n"
        "    except Exception:\n"
        "        pass\n"
        "    assert ok(), 'phase failed'\n"
        "    try:\n"
        "        reap()\n"
        "    except Exception:  # graftcheck: off=except-swallow\n"
        "        pass\n"
    )
    tree = Tree({}, corpus={"chip_smoke.py": src, "__graft_entry__.py": src, "tests/t.py": src})
    r = scan(tree, rules=["opt-assert", "except-swallow", "thread-name"])
    assert sorted((f.path, f.rule, f.line) for f in r.findings) == [
        ("chip_smoke.py", "except-swallow", 6),
        ("chip_smoke.py", "opt-assert", 8),
    ]
    assert r.suppressed == 1
    assert list(build_tree(ROOT).scripts) == ["chip_smoke.py"]


def test_opt_assert_flags_load_bearing_and_allows_narrowing():
    bad = "def f(x):\n    assert x > 0, 'must be positive'\n    return x\n"
    r = _scan_src("tidb_tpu/kv/x.py", bad, ["opt-assert"])
    assert len(r.findings) == 1 and r.findings[0].rule == "opt-assert"
    ok = (
        "def f(x, y):\n"
        "    assert x is not None\n"
        "    assert isinstance(y, int)\n"
        "    return x + y\n"
    )
    assert not _scan_src("tidb_tpu/kv/x.py", ok, ["opt-assert"]).findings


def test_thread_name_rule():
    bad = "import threading\n\ndef go(fn):\n    threading.Thread(target=fn, daemon=True).start()\n"
    r = _scan_src("tidb_tpu/kv/x.py", bad, ["thread-name"])
    assert len(r.findings) == 1
    ok = bad.replace("daemon=True", "daemon=True, name='worker'")
    assert not _scan_src("tidb_tpu/kv/x.py", ok, ["thread-name"]).findings


def test_eventlog_discipline_rule():
    bad = "def f(x):\n    print('migrated', x)\n    return x\n"
    r = _scan_src("tidb_tpu/kv/x.py", bad, ["eventlog-discipline"])
    assert len(r.findings) == 1 and r.findings[0].rule == "eventlog-discipline"
    # the structured-event shape is clean
    ok = (
        "from tidb_tpu.utils import eventlog as _ev\n"
        "def f(x):\n"
        "    lg = _ev.on(_ev.INFO)\n"
        "    if lg is not None:\n"
        "        lg.emit(_ev.INFO, 'placement', 'migrated', table=x)\n"
        "    return x\n"
    )
    assert not _scan_src("tidb_tpu/kv/x.py", ok, ["eventlog-discipline"]).findings
    # CLI surfaces whose contract IS stdout are exempt
    for path in ("tidb_tpu/tools/x.py", "tidb_tpu/kv/__main__.py"):
        assert not _scan_src(path, bad, ["eventlog-discipline"]).findings
    # an explicit suppression silences the line
    sup = bad.replace("print('migrated', x)", "print('migrated', x)  # graftcheck: off=eventlog-discipline")
    r2 = _scan_src("tidb_tpu/kv/x.py", sup, ["eventlog-discipline"])
    assert not r2.findings and r2.suppressed == 1


def test_metric_labels_rule():
    bad = (
        "from tidb_tpu.utils.metrics import REGISTRY\n"
        "def make(dims):\n"
        "    return REGISTRY.counter('x_total', 'help', tuple(dims))\n"
    )
    r = _scan_src("tidb_tpu/utils/x.py", bad, ["metric-labels"])
    assert len(r.findings) == 1
    ok = bad.replace("tuple(dims)", "('kind', 'outcome')")
    assert not _scan_src("tidb_tpu/utils/x.py", ok, ["metric-labels"]).findings
    # the group-labeled RU accounting counters (workload attribution) keep
    # the literal-tuple contract: group names are a bounded, user-declared
    # domain, and the declarations in utils/metrics.py must stay literal
    grp = (
        "from tidb_tpu.utils.metrics import REGISTRY\n"
        "RU = REGISTRY.counter('ru_total', 'help', ('group',))\n"
        "STMTS = REGISTRY.counter('stmt_total', 'help', ('group',))\n"
    )
    assert not _scan_src("tidb_tpu/utils/x.py", grp, ["metric-labels"]).findings


def test_sys_sections_rule():
    """An undeclared _want() section literal in sys_report is a finding
    (the PR 9 sections= discipline: heavy report parts must be selectable
    request-side), as is a declared-but-ungated stale section name."""
    ok = (
        "SYS_SECTIONS = frozenset({'metrics', 'slow'})\n"
        "def sys_report(sections=None):\n"
        "    want = None if sections is None else set(sections)\n"
        "    def _want(k):\n"
        "        return want is None or k in want\n"
        "    rep = {}\n"
        "    if _want('metrics'):\n"
        "        rep['metrics'] = 1\n"
        "    if _want('slow'):\n"
        "        rep['slow'] = []\n"
        "    return rep\n"
    )
    assert not _scan_src("tidb_tpu/kv/remote.py", ok, ["sys-sections"]).findings
    # a new heavy section gated but NOT declared escapes the contract
    bad = ok.replace(
        "    return rep\n",
        "    if _want('heatmap'):\n        rep['heatmap'] = []\n    return rep\n",
    )
    r = _scan_src("tidb_tpu/kv/remote.py", bad, ["sys-sections"])
    assert len(r.findings) == 1 and r.findings[0].symbol == "heatmap"
    # declared-but-ungated is a stale declaration
    stale = ok.replace("{'metrics', 'slow'}", "{'metrics', 'slow', 'traces'}")
    r2 = _scan_src("tidb_tpu/kv/remote.py", stale, ["sys-sections"])
    assert len(r2.findings) == 1 and r2.findings[0].symbol == "traces"
    # no declaration at all is one finding, not a crash
    nodecl = ok.replace("SYS_SECTIONS = frozenset({'metrics', 'slow'})\n", "")
    r3 = _scan_src("tidb_tpu/kv/remote.py", nodecl, ["sys-sections"])
    assert len(r3.findings) == 1 and r3.findings[0].symbol == "declarations"
    # files other than kv/remote.py are out of scope
    assert not _scan_src("tidb_tpu/kv/other.py", bad, ["sys-sections"]).findings


def test_sys_sections_real_tree_is_clean():
    """The real kv/remote.py declares every section its gates select."""
    tree = build_tree(ROOT)
    assert not scan(tree, rules=["sys-sections"]).findings


def test_jit_cache_rule_flags_uncached_and_allows_builders():
    bad = "import jax\n\ndef hot(fn):\n    return jax.jit(fn)\n"
    r = _scan_src("tidb_tpu/ops/x.py", bad, ["jit-cache"])
    assert len(r.findings) == 1 and r.findings[0].symbol == "jax.jit"
    # same call inside the recognized dag_kernel builder name is allowed
    ok = "import jax\n\ndef _build(fn):\n    return jax.jit(fn)\n"
    assert not _scan_src("tidb_tpu/ops/dag_kernel.py", ok, ["jit-cache"]).findings
    # out-of-scope directories are not the rule's business
    assert not _scan_src("tidb_tpu/session/x.py", bad, ["jit-cache"]).findings


def test_jit_cache_rule_catches_decorator_forms():
    bare = "import jax\n\n@jax.jit\ndef kernel(x):\n    return x\n"
    r = _scan_src("tidb_tpu/ops/x.py", bare, ["jit-cache"])
    assert len(r.findings) == 1 and "decorator" in r.findings[0].msg
    part = (
        "import jax\nfrom functools import partial\n\n"
        "@partial(jax.jit, static_argnums=0)\n"
        "def kernel(n, x):\n    return x\n"
    )
    r2 = _scan_src("tidb_tpu/ops/x.py", part, ["jit-cache"])
    assert len(r2.findings) == 1 and r2.findings[0].symbol == "partial(jax.jit)"
    # factory form @jax.jit(...) is flagged exactly once, never double-reported
    fact = (
        "import jax\n\n"
        "@jax.jit(donate_argnums=0)\n"
        "def kernel(x):\n    return x\n"
    )
    assert len(_scan_src("tidb_tpu/ops/x.py", fact, ["jit-cache"]).findings) == 1
    # decorator inside a recognized builder is allowed
    ok = "import jax\n\ndef _build():\n    @jax.jit\n    def kernel(x):\n        return x\n    return kernel\n"
    assert not _scan_src("tidb_tpu/ops/dag_kernel.py", ok, ["jit-cache"]).findings


def test_traced_impure_jax_random_is_allowed():
    """jax.random with an explicit key is the correct trace-safe PRNG; the
    numpy global RNG inside a traced function is the bug."""
    ok = (
        "import jax\n"
        "def _build():\n"
        "    def kernel(key, x):\n"
        "        return x + jax.random.normal(key, x.shape)\n"
        "    return jax.jit(kernel)\n"
    )
    assert not _scan_src("tidb_tpu/ops/dag_kernel.py", ok, ["traced-impure"]).findings
    bad = ok.replace("jax.random.normal(key, x.shape)", "np.random.rand()")
    r = _scan_src("tidb_tpu/ops/dag_kernel.py", bad, ["traced-impure"])
    assert len(r.findings) == 1 and "np.random.rand" in r.findings[0].msg
    # decorator-jitted defs are traced too
    dec = (
        "import jax, time\n"
        "def _build():\n"
        "    @jax.jit\n"
        "    def kernel(x):\n"
        "        return x * time.time()\n"
        "    return kernel\n"
    )
    r2 = _scan_src("tidb_tpu/ops/dag_kernel.py", dec, ["traced-impure"])
    assert len(r2.findings) == 1 and "time.time" in r2.findings[0].msg


def test_traced_impure_rule():
    bad = (
        "import jax, time\n"
        "def _build():\n"
        "    def kernel(x):\n"
        "        t = time.time()\n"
        "        return x * t\n"
        "    return jax.jit(kernel)\n"
    )
    r = _scan_src("tidb_tpu/ops/dag_kernel.py", bad, ["traced-impure"])
    assert len(r.findings) == 1 and "time.time" in r.findings[0].msg
    ok = bad.replace("        t = time.time()\n", "        t = 2.0\n").replace(
        "x * t", "x * t"
    )
    assert not _scan_src("tidb_tpu/ops/dag_kernel.py", ok, ["traced-impure"]).findings


def test_shared_mutation_rule_and_lock_guard():
    bad = (
        "import threading\n"
        "_CACHE = {}\n"
        "_MU = threading.Lock()\n"
        "def put(k, v):\n"
        "    _CACHE[k] = v\n"
    )
    r = _scan_src("tidb_tpu/kv/x.py", bad, ["shared-mutation"])
    assert len(r.findings) == 1 and r.findings[0].symbol == "_CACHE"
    ok = bad.replace("    _CACHE[k] = v\n", "    with _MU:\n        _CACHE[k] = v\n")
    assert not _scan_src("tidb_tpu/kv/x.py", ok, ["shared-mutation"]).findings


def test_lock_order_rule_reversed_nesting():
    src = (
        "import threading\n"
        "_A = threading.Lock()\n"
        "_B = threading.Lock()\n"
        "def one():\n"
        "    with _A:\n"
        "        with _B:\n"
        "            pass\n"
        "def two():\n"
        "    with _B:\n"
        "        with _A:\n"
        "            pass\n"
    )
    r = _scan_src("tidb_tpu/kv/x.py", src, ["lock-order"])
    assert len(r.findings) == 1
    assert "_A" in r.findings[0].msg and "_B" in r.findings[0].msg
    # consistent order in both functions: clean
    ok = src.replace("with _B:\n        with _A:", "with _A:\n        with _B:")
    assert not _scan_src("tidb_tpu/kv/x.py", ok, ["lock-order"]).findings


def test_lock_order_cross_method():
    # f holds _A and calls g, which takes _B; h nests them the other way
    src = (
        "import threading\n"
        "_A = threading.Lock()\n"
        "_B = threading.Lock()\n"
        "def g():\n"
        "    with _B:\n"
        "        pass\n"
        "def f():\n"
        "    with _A:\n"
        "        g()\n"
        "def h():\n"
        "    with _B:\n"
        "        with _A:\n"
        "            pass\n"
    )
    r = _scan_src("tidb_tpu/kv/x.py", src, ["lock-order"])
    assert len(r.findings) == 1


def test_dead_code_rule():
    src = "def used():\n    return 1\n\ndef unused_helper():\n    return used()\n"
    # corpus references `used` via unused_helper; unused_helper itself: no refs
    r = scan(Tree({"tidb_tpu/utils/x.py": src}), rules=["dead-code"])
    assert [f.symbol for f in r.findings] == ["unused_helper"]
    # a test referencing it keeps it alive
    r2 = scan(
        Tree({"tidb_tpu/utils/x.py": src}, corpus={"tests/test_x.py": "unused_helper()"}),
        rules=["dead-code"],
    )
    assert not r2.findings


def test_replay_registry_fixture():
    src = (
        'REPLAYABLE = frozenset({"ping"})\n'
        'NON_REPLAYABLE = frozenset({"boom"})\n'
        "class StoreServer:\n"
        "    def _dispatch(self, h, blobs):\n"
        '        cmd = h["cmd"]\n'
        '        if cmd == "ping":\n'
        "            return {}, []\n"
        '        if cmd == "boom":\n'
        "            return {}, []\n"
        '        if cmd == "mystery":\n'
        "            return {}, []\n"
        "class RemoteStore:\n"
        "    def _call(self, header):\n"
        '        cmd = header["cmd"]\n'
        "        replayable = cmd in REPLAYABLE\n"
        "        return None\n"
    )
    r = _scan_src("tidb_tpu/kv/remote.py", src, ["replay-registry"])
    assert [f.symbol for f in r.findings] == ["mystery"]
    # open-by-default gate is itself a finding
    bad_gate = src.replace("cmd in REPLAYABLE", "cmd not in NON_REPLAYABLE")
    r2 = _scan_src("tidb_tpu/kv/remote.py", bad_gate, ["replay-registry"])
    assert {f.symbol for f in r2.findings} == {"mystery", "gate"}


# -- seeded mutations of the REAL tree (the acceptance criteria cases) -------


@pytest.fixture(scope="module")
def real_tree():
    return build_tree(ROOT)


def test_shipped_tree_replay_registry_is_complete(real_tree):
    src = real_tree.files["tidb_tpu/kv/remote.py"].source
    assert not scan(Tree({"tidb_tpu/kv/remote.py": src}), rules=["replay-registry"]).findings


def test_seeded_undeclared_verb_in_remote(real_tree):
    src = real_tree.files["tidb_tpu/kv/remote.py"].source
    mut = src.replace(
        'if cmd == "ping":',
        'if cmd == "snap_delete_range":\n            return {"ok": 1}, []\n'
        '        if cmd == "ping":',
    )
    assert mut != src
    r = scan(Tree({"tidb_tpu/kv/remote.py": mut}), rules=["replay-registry"])
    assert [f.symbol for f in r.findings] == ["snap_delete_range"]
    assert "no replay classification" in r.findings[0].msg


def test_seeded_assert_in_sharded(real_tree):
    src = real_tree.files["tidb_tpu/kv/sharded.py"].source
    needle = "segments = self.store.group_ranges"
    mut = src.replace(
        needle, "assert req.concurrency > 0\n        " + needle, 1
    )
    assert mut != src
    base = scan(Tree({"tidb_tpu/kv/sharded.py": src}), rules=["opt-assert"])
    assert not base.findings  # shipped file is clean
    r = scan(Tree({"tidb_tpu/kv/sharded.py": mut}), rules=["opt-assert"])
    assert len(r.findings) == 1 and r.findings[0].symbol == "req.concurrency > 0"


def test_seeded_uncached_jit_in_ops(real_tree):
    src = real_tree.files["tidb_tpu/ops/dag_kernel.py"].source
    mut = src + "\n\ndef _hotpath_extra(fn):\n    import jax\n    return jax.jit(fn)\n"
    base = scan(Tree({"tidb_tpu/ops/dag_kernel.py": src}), rules=["jit-cache"])
    assert not base.findings
    r = scan(Tree({"tidb_tpu/ops/dag_kernel.py": mut}), rules=["jit-cache"])
    assert len(r.findings) == 1 and r.findings[0].symbol == "jax.jit"


def test_seeded_lock_inversion_in_real_module(real_tree):
    src = real_tree.files["tidb_tpu/catalog/ddl.py"].source
    # DDLWorker.run_job nests _run_mu -> _mu; seed the reverse order
    mut = src + (
        "\n\ndef _evil_reversed(worker):\n"
        "    with worker._mu:\n"
        "        with worker._run_mu:\n"
        "            pass\n"
    )
    base = scan(Tree({"tidb_tpu/catalog/ddl.py": src}), rules=["lock-order"])
    assert not base.findings
    r = scan(Tree({"tidb_tpu/catalog/ddl.py": mut}), rules=["lock-order"])
    assert len(r.findings) == 1
    assert "_run_mu" in r.findings[0].msg and "._mu" in r.findings[0].msg


# -- suppression, baseline, CLI ----------------------------------------------


def test_suppression_comment_silences_one_rule():
    bad = "def f(x):\n    assert x > 0  # graftcheck: off=opt-assert\n    return x\n"
    r = _scan_src("tidb_tpu/kv/x.py", bad, ["opt-assert"])
    assert not r.findings and r.suppressed == 1
    # a different rule's suppression does not silence it
    other = bad.replace("off=opt-assert", "off=thread-name")
    assert len(_scan_src("tidb_tpu/kv/x.py", other, ["opt-assert"]).findings) == 1
    # bare off= silences everything on the line
    bare = bad.replace("off=opt-assert", "off")
    assert not _scan_src("tidb_tpu/kv/x.py", bare, ["opt-assert"]).findings


def test_baseline_round_trip(tmp_path):
    src = "def f(x):\n    assert x > 0\n    return x\n"
    tree = Tree({"tidb_tpu/kv/x.py": src})
    rep = scan(tree, rules=["opt-assert"])
    assert len(rep.findings) == 1
    bpath = str(tmp_path / "base.json")
    write_baseline(bpath, tree, rep)
    baseline = load_baseline(bpath)
    rep2 = scan(tree, rules=["opt-assert"], baseline=baseline)
    assert not rep2.findings and len(rep2.baselined) == 1
    # a NEW violation still fails even with the old one grandfathered
    src2 = src + "\ndef g(y):\n    assert y < 9\n    return y\n"
    rep3 = scan(Tree({"tidb_tpu/kv/x.py": src2}), rules=["opt-assert"], baseline=baseline)
    assert len(rep3.findings) == 1 and len(rep3.baselined) == 1
    # baseline keys track line CONTENT, not numbers: shifting the file is free
    shifted = "# a new leading comment\n" + src
    rep4 = scan(Tree({"tidb_tpu/kv/x.py": shifted}), rules=["opt-assert"], baseline=baseline)
    assert not rep4.findings and len(rep4.baselined) == 1


def test_baseline_is_a_multiset_not_a_set(tmp_path):
    """One baseline entry grandfathers ONE occurrence: a second textually
    identical violation in the same file must still hard-fail."""
    src = "def f(x):\n    assert x > 0\n    return x\n"
    tree = Tree({"tidb_tpu/kv/x.py": src})
    bpath = str(tmp_path / "base.json")
    write_baseline(bpath, tree, scan(tree, rules=["opt-assert"]))
    baseline = load_baseline(bpath)
    dup = src + "\ndef g(x):\n    assert x > 0\n    return x\n"  # same line text
    rep = scan(Tree({"tidb_tpu/kv/x.py": dup}), rules=["opt-assert"], baseline=baseline)
    assert len(rep.baselined) == 1 and len(rep.findings) == 1


def test_suppression_does_not_leak_to_line_above():
    """A suppression comment governs its own line (and a statement directly
    below a standalone comment) — never the unrelated statement above it."""
    src = (
        "def f(x):\n"
        "    assert x > 0\n"
        "    # graftcheck: off=opt-assert\n"
        "    assert x < 9\n"
        "    return x\n"
    )
    r = _scan_src("tidb_tpu/kv/x.py", src, ["opt-assert"])
    assert len(r.findings) == 1 and r.findings[0].line == 2
    assert r.suppressed == 1


def test_update_baseline_rejects_partial_scan(capsys):
    """--update-baseline over a rule subset would silently drop every other
    rule's grandfathered entries — the CLI refuses the combination."""
    assert check_main(["--root", ROOT, "--rules", "opt-assert", "--update-baseline"]) == 2


def test_explain_output(capsys):
    rules = load_rules()
    assert check_main(["--explain", "replay-registry"]) == 0
    out = capsys.readouterr().out
    assert "mpp_dispatch" in out and "REPLAYABLE" in out
    # every registered rule explains itself
    for rid in rules:
        assert check_main(["--explain", rid]) == 0
    assert check_main(["--explain", "no-such-rule"]) == 2


def test_cli_clean_tree_and_json_report(tmp_path):
    out = str(tmp_path / "report.json")
    rc = check_main(["--root", ROOT, "--json", out])
    assert rc == 0
    with open(out) as f:
        rep = json.load(f)
    assert rep["ok"] is True and rep["findings"] == []


# -- the -O regression test (satellite 1): hot modules import and still
# guard under PYTHONOPTIMIZE=1 ----------------------------------------------


def test_guards_survive_python_O():
    code = (
        "import sys\n"
        "assert sys.flags.optimize == 1\n"  # the subprocess IS running -O
        "from tidb_tpu.utils.chunk import decode_chunk\n"
        "from tidb_tpu.utils.backoff import BackoffConfig\n"
        "import tidb_tpu.kv.remote, tidb_tpu.kv.sharded, tidb_tpu.kv.txn\n"
        "import tidb_tpu.copr.client, tidb_tpu.kv.rowcodec\n"
        "try:\n"
        "    decode_chunk(b'NOTMAGIC....')\n"
        "except ValueError as e:\n"
        "    assert 'magic' in str(e).lower() or True\n"
        "else:\n"
        "    raise SystemExit('corrupt chunk frame decoded silently under -O')\n"
        "try:\n"
        "    BackoffConfig('x', 1.0, 2.0, jitter='bogus')\n"
        "except ValueError:\n"
        "    pass\n"
        "else:\n"
        "    raise SystemExit('bad jitter mode accepted under -O')\n"
        "print('OPTIMIZED-GUARDS-OK')\n"
    )
    env = dict(os.environ, PYTHONOPTIMIZE="1", JAX_PLATFORMS="cpu")
    env.pop("TIDB_TPU_LOCKCHECK", None)
    p = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        cwd=ROOT,
        env=env,
        timeout=120,
    )
    assert p.returncode == 0, p.stdout + p.stderr
    assert "OPTIMIZED-GUARDS-OK" in p.stdout


# -- failpoint-registry rule (PR 14) -----------------------------------------

_FP_REGISTRY = (
    "FAILPOINTS = frozenset({\n"
    "    'colcache_merge',\n"
    "    'remote_send',\n"
    "})\n"
)
# every failpoint call in these fixtures is assembled by implicit string
# concatenation ("failpoint.in" "ject(...)"), so the registry rule's corpus
# regex can never match THIS file's own raw lines when the real-tree scan
# reads tests/ as reference corpus — the fixtures stay decoupled from
# whatever the real FAILPOINTS registry happens to contain
_FP_INJECTS = (
    "from tidb_tpu.utils import failpoint\n"
    "def merge():\n"
    "    failpoint.in" "ject('colcache_merge', 1)\n"
    "def send():\n"
    "    failpoint.in" "ject('remote_send', 'cop')\n"
)


_ARM_OK = "failpoint.en" "able('remote_send', boom)\n"
_ARM_TYPO = "failpoint.en" "able('remote_sned', boom)\n"


def test_failpoint_registry_clean_tree():
    tree = Tree(
        {"tidb_tpu/kv/fault_injection.py": _FP_REGISTRY, "tidb_tpu/copr/x.py": _FP_INJECTS},
        corpus={"tests/test_x.py": _ARM_OK},
    )
    assert not scan(tree, rules=["failpoint-registry"]).findings


def test_failpoint_registry_flags_typod_test_reference():
    # the acceptance case: a chaos test arming a name that does not exist —
    # the fault never fires and the test passes vacuously
    tree = Tree(
        {"tidb_tpu/kv/fault_injection.py": _FP_REGISTRY, "tidb_tpu/copr/x.py": _FP_INJECTS},
        corpus={"tests/test_x.py": _ARM_TYPO},
    )
    r = scan(tree, rules=["failpoint-registry"])
    assert len(r.findings) == 1
    assert r.findings[0].symbol == "remote_sned"
    assert r.findings[0].path == "tests/test_x.py"


def test_failpoint_registry_flags_unregistered_inject_and_stale_entry():
    inj = _FP_INJECTS + "def extra():\n    failpoint.in" "ject('new_point')\n"
    tree = Tree({"tidb_tpu/kv/fault_injection.py": _FP_REGISTRY, "tidb_tpu/copr/x.py": inj})
    r = scan(tree, rules=["failpoint-registry"])
    assert [f.symbol for f in r.findings] == ["new_point"]
    # registry entry whose inject site was deleted → stale finding
    gone = _FP_INJECTS.replace("    failpoint.in" "ject('remote_send', 'cop')\n", "    pass\n")
    tree2 = Tree({"tidb_tpu/kv/fault_injection.py": _FP_REGISTRY, "tidb_tpu/copr/x.py": gone})
    r2 = scan(tree2, rules=["failpoint-registry"])
    assert [f.symbol for f in r2.findings] == ["remote_send"]
    assert r2.findings[0].path == "tidb_tpu/kv/fault_injection.py"


def test_failpoint_registry_alias_and_suppression():
    aliased = (
        "from tidb_tpu.utils import failpoint as _fp\n"
        "def probe(i):\n"
        "    _fp.in" "ject('mystery', i)  # graftcheck: off=failpoint-registry\n"
    )
    files = {
        "tidb_tpu/kv/fault_injection.py": _FP_REGISTRY,
        "tidb_tpu/copr/x.py": _FP_INJECTS,  # keeps the registry non-stale
        "tidb_tpu/parallel/x.py": aliased,
    }
    r = scan(Tree(dict(files)), rules=["failpoint-registry"])
    assert not r.findings and r.suppressed == 1
    # without the suppression the aliased call is still recognized
    files["tidb_tpu/parallel/x.py"] = aliased.replace(
        "  # graftcheck: off=failpoint-registry", ""
    )
    assert [f.symbol for f in scan(Tree(files), rules=["failpoint-registry"]).findings] == ["mystery"]


def test_failpoint_registry_real_tree_is_consistent():
    """The shipped registry matches the shipped inject sites exactly and
    every test reference resolves (the live invariant, not a fixture)."""
    tree = build_tree(ROOT)
    assert not scan(tree, rules=["failpoint-registry"]).findings


# -- except-swallow rule (PR 14) ---------------------------------------------


def test_except_swallow_flags_pass_and_bare():
    bad = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
        "def h():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        return 1\n"
    )
    r = _scan_src("tidb_tpu/kv/x.py", bad, ["except-swallow"])
    assert len(r.findings) == 2
    assert {f.line for f in r.findings} == {4, 9}


def test_except_swallow_allows_narrowed_and_handled():
    ok = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"
        "        pass\n"
        "def h(self):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        self.errors += 1\n"
        "        self.last = e\n"
    )
    assert not _scan_src("tidb_tpu/kv/x.py", ok, ["except-swallow"]).findings


def test_except_swallow_flags_continue_and_tuple_broad():
    bad = (
        "def f(xs):\n"
        "    for x in xs:\n"
        "        try:\n"
        "            g(x)\n"
        "        except (ValueError, Exception):\n"
        "            continue\n"
    )
    r = _scan_src("tidb_tpu/kv/x.py", bad, ["except-swallow"])
    assert len(r.findings) == 1 and r.findings[0].line == 5


def test_except_swallow_suppression_names_the_reason():
    ok = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    # advisory probe; sweep retries next tick\n"
        "    except Exception:  # graftcheck: off=except-swallow\n"
        "        pass\n"
    )
    r = _scan_src("tidb_tpu/kv/x.py", ok, ["except-swallow"])
    assert not r.findings and r.suppressed == 1


# -- one yardstick: speed is benchmark/run.py -> BENCHMARK.json -> PERF_LEDGER.jsonl ----

_HARNESS_NAMED = r"benchdaily|benchdb|(?<![\w/])bench\.py|bench/qps"
_HARNESS_RUN = (
    r"tidb_tpu\.bench\.(?!tpchlike)\w|from tidb_tpu\.bench import (?!tpchlike)"
    r"|python3? +(-\S+ +)*(\./)?bench\.py"
)
# the driver's and the reviewers' files, and the seed's records, are history
_HISTORY = {"CHANGES.md", "ISSUE.md", "SURVEY.md", "BASELINE.md", "REVIEW.md", "ADVICE.md"}
# these say what was deleted (and this file holds the patterns): no command, no import
_RECORDS = {"ROADMAP.md", "PERF.md", "tests/test_graftcheck.py"}


def test_the_tree_has_one_way_to_measure():
    """PR 31 retired the four pre-chip harnesses (`bench.py`, `tidb_tpu/bench/`'s
    benchdaily, benchdb and qps): every timing they printed was a CPU's. No
    source or document may send anyone to one again, so a lane cannot grow back."""
    assert sorted(n for n in os.listdir(os.path.join(ROOT, "tidb_tpu", "bench")) if n.endswith(".py")) == [
        "__init__.py", "tpchlike.py",
    ]
    assert not os.path.exists(os.path.join(ROOT, "bench.py"))
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = {".git"} | {ln.strip().rstrip("/") for ln in f if ln.strip().endswith("/")}
    named, run = re.compile(_HARNESS_NAMED + "|" + _HARNESS_RUN), re.compile(_HARNESS_RUN)
    found = []
    for base, dirs, names in os.walk(ROOT):
        def rel(n):
            return os.path.relpath(os.path.join(base, n), ROOT).replace(os.sep, "/")

        dirs[:] = [d for d in dirs if d not in ignored and rel(d) not in ignored]
        for n in names:
            if not n.endswith((".py", ".md")) or rel(n) in _HISTORY:
                continue
            pat = run if rel(n) in _RECORDS else named
            with open(os.path.join(base, n), encoding="utf-8", errors="replace") as f:
                found += [f"{rel(n)}:{i}: {ln.strip()[:120]}" for i, ln in enumerate(f, 1) if pat.search(ln)]
    assert not found, "\n".join(found)
