"""GC rules: compile-churn control (``jit-cache``) and traced-function
purity (``traced-impure``).

The incident class: PR 10's grouped MIN/MAX "compile bomb" — a kernel
shape that re-entered XLA compilation per query walled tier-1 for 27+
minutes; PR 10 fixed it by forcing every fragment program through ONE
power-of-two-bucketed cache. A single uncached ``jax.jit``/``shard_map``
call site quietly reintroduces that class: it compiles per CALL (or per
closure identity), invisible on toy shapes, catastrophic at production
shapes. So in the device-code directories every jit/shard_map call must
live inside a recognized program-cache builder — the funnels whose callers
key compiles structurally.

Purity: anything traced must be a pure function of its operands.
``time.*``/``random.*`` calls inside a traced function bake ONE value in
at trace time and never move again (the PR 4 first-call probe exists
precisely because timing must happen OUTSIDE the kernel); iterating a set
gives hash-order-dependent program structure, which silently changes the
compile key across runs.
"""

from __future__ import annotations

import ast

from tidb_tpu.tools.check.core import Finding, Tree, call_name, rule

JIT_RULE = "jit-cache"
PURE_RULE = "traced-impure"

# directories whose code runs on (or builds programs for) the device
SCOPE_PREFIXES = ("tidb_tpu/ops/", "tidb_tpu/parallel/")
SCOPE_FILES = ("tidb_tpu/copr/tpu_engine.py",)

# the blessed program-cache funnels: (path suffix → enclosing function
# names allowed to call jax.jit / shard_map). Extend this mapping — and
# STATIC_ANALYSIS.md — when adding a new cached builder; the point is that
# adding an UNCACHED call site is loud.
CACHE_HELPERS = {
    "tidb_tpu/ops/dag_kernel.py": {"_build"},  # keyed by _COMPILE_CACHE in get_kernel
    "tidb_tpu/ops/window_kernel.py": {"_build"},  # keyed by _CACHE in get_window_fn
    "tidb_tpu/parallel/mpp.py": {"build_dist_pipeline"},  # keyed by _MPP_FN_CACHE
}

_JIT_NAMES = {"jax.jit", "jit", "shard_map", "jax.shard_map"}


def _in_scope(path: str) -> bool:
    return path.startswith(SCOPE_PREFIXES) or path in SCOPE_FILES


def _is_jit_name(name: str) -> bool:
    return name in _JIT_NAMES or name.endswith(".jit") or name.endswith(".shard_map")


def _jit_decorator(dec: ast.expr):
    """The decorator spellings of a compile site: bare ``@jax.jit``,
    factory ``@jax.jit(...)``, and ``@partial(jax.jit, ...)``. Returns the
    matched dotted name or None."""
    if isinstance(dec, (ast.Name, ast.Attribute)):
        name = call_name(dec)
        return name if _is_jit_name(name) else None
    if isinstance(dec, ast.Call):
        fname = call_name(dec.func)
        if _is_jit_name(fname):
            return fname
        if fname.rsplit(".", 1)[-1] == "partial":
            for a in dec.args:
                aname = call_name(a)
                if _is_jit_name(aname):
                    return f"partial({aname})"
    return None


def _func_stack(tree: ast.Module):
    """Yield (call_node, [enclosing FunctionDef names]) for every Call and
    (funcdef, enclosing-chain) for every FunctionDef (decorator checks)."""
    calls = []
    defs = []

    def walk(node, chain):
        for child in ast.iter_child_nodes(node):
            nxt = chain
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.append((child, chain))
                nxt = chain + [child.name]
            elif isinstance(child, ast.Call):
                calls.append((child, chain))
            walk(child, nxt)

    walk(tree, [])
    return calls, defs


@rule(
    JIT_RULE,
    "jax.jit / shard_map only inside recognized program-cache builders",
    """
In ops/, parallel/, and copr/tpu_engine.py every jax.jit / shard_map
call site must sit inside one of the recognized program-cache builders
(dag_kernel._build via get_kernel's _COMPILE_CACHE, window_kernel._build
via get_window_fn, and mpp.build_dist_pipeline via gather's
_MPP_FN_CACHE). A jit call anywhere else compiles per call site invocation
— the PR 10 compile-bomb class, where one uncached fragment shape walled
tier-1 for 27+ minutes and every same-shape query re-paid a full XLA mesh
compile. Fix: route the program through an existing cached builder, or
build a new structurally-keyed cache and register its builder in
rules_compile.CACHE_HELPERS (and STATIC_ANALYSIS.md) so the funnel stays
explicit.
""",
)
def check_jit(tree: Tree) -> list:
    out: list[Finding] = []
    for sf in tree.targets():
        if not _in_scope(sf.path):
            continue
        allowed = set()
        for suffix, names in CACHE_HELPERS.items():
            if sf.path.endswith(suffix):
                allowed = names
                break
        calls, defs = _func_stack(sf.tree)
        for call, chain in calls:
            name = call_name(call.func)
            if _is_jit_name(name) and not (set(chain) & allowed):
                out.append(
                    Finding(
                        JIT_RULE,
                        sf.path,
                        call.lineno,
                        f"{name}(...) outside a recognized program-cache builder "
                        "— every compile must flow through a structurally-keyed "
                        "cache (see STATIC_ANALYSIS.md jit-cache)",
                        symbol=name,
                    )
                )
        # the decorator spellings compile too: @jax.jit / @partial(jax.jit)
        # on a def is a per-closure compile site exactly like the call form
        for fn, chain in defs:
            for dec in fn.decorator_list:
                if isinstance(dec, ast.Call) and _is_jit_name(call_name(dec.func)):
                    continue  # @jax.jit(...) factory form: the Call loop saw it
                name = _jit_decorator(dec)
                if name is not None and not (set(chain) & allowed):
                    out.append(
                        Finding(
                            JIT_RULE,
                            sf.path,
                            dec.lineno,
                            f"@{name} on {fn.name!r} outside a recognized "
                            "program-cache builder — decorator-jitted defs "
                            "compile per closure like the call form",
                            symbol=name,
                        )
                    )
    return out


_TIME_CALLS = {
    "time.time",
    "time.perf_counter",
    "time.monotonic",
    "time.time_ns",
    "time.perf_counter_ns",
}


def _traced_functions(tree: ast.Module):
    """FunctionDefs traced by jax: passed by name to jit/shard_map*, or
    decorated with @jax.jit/@partial(jax.jit), plus everything nested
    inside them."""
    wanted = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = call_name(node.func)
            if _is_jit_name(name):
                for a in node.args:
                    if isinstance(a, ast.Name):
                        wanted.add(a.id)
    traced = []

    def walk(node, inside):
        for child in ast.iter_child_nodes(node):
            nxt = inside
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                decorated = any(_jit_decorator(d) for d in child.decorator_list)
                nxt = inside or child.name in wanted or decorated
                if nxt:
                    traced.append(child)
            walk(child, nxt)

    walk(tree, False)
    return traced


@rule(
    PURE_RULE,
    "no wall-clock / RNG / set-iteration inside traced functions",
    """
A function handed to jax.jit / shard_map executes ONCE at trace time; its
Python-level side effects are baked into the compiled program. time.time()
inside a kernel returns the timestamp of the first trace forever (why the
PR 4 compile probe times around the kernel, never in it); random.* bakes
one draw; iterating a set makes program STRUCTURE depend on hash order, so
the same query can produce a different compile key across processes —
cache misses that look like nondeterministic compile churn. Fix: hoist
clocks/RNG to the host side and pass results as operands; iterate sorted()
or a tuple instead of a set.
""",
)
def check_pure(tree: Tree) -> list:
    out: list[Finding] = []
    for sf in tree.targets():
        if not _in_scope(sf.path):
            continue
        for fn in _traced_functions(sf.tree):
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    name = call_name(node.func)
                    # stdlib random and numpy's global RNG bake one draw in
                    # at trace time; jax.random with an explicit key is the
                    # CORRECT trace-safe PRNG and must not be flagged
                    if name in _TIME_CALLS or name.split(".")[0] == "random" or (
                        name.startswith(("np.random.", "numpy.random."))
                    ):
                        out.append(
                            Finding(
                                PURE_RULE,
                                sf.path,
                                node.lineno,
                                f"{name}() inside traced function {fn.name!r} is "
                                "evaluated once at trace time — hoist to the host "
                                "and pass as an operand",
                                symbol=f"{fn.name}:{name}",
                            )
                        )
                elif isinstance(node, (ast.For, ast.comprehension)):
                    it = node.iter
                    if isinstance(it, ast.Set) or (
                        isinstance(it, ast.Call)
                        and isinstance(it.func, ast.Name)
                        and it.func.id in ("set", "frozenset")
                    ):
                        out.append(
                            Finding(
                                PURE_RULE,
                                sf.path,
                                getattr(node, "lineno", it.lineno),
                                f"set iteration inside traced function {fn.name!r}: "
                                "program structure depends on hash order — iterate "
                                "sorted() or a tuple",
                                symbol=f"{fn.name}:set-iter",
                            )
                        )
    return out
