"""The fused DAG kernel: one jitted XLA computation per (DAG, batch shape).

Reference parity: unistore's fused closure executor (closure_exec.go:165) —
but where that is a row-at-a-time Go loop, this compiles the whole operator
chain into a single XLA program over padded columnar batches:

- Selection = vectorized predicate eval → row mask (no compaction: dynamic
  shapes would defeat XLA; masked lanes ride along).
- HashAgg: NO scatter anywhere (XLA lowers segment_sum to scatter-add, which
  serializes on TPU — ~100ms per call on 2M rows, measured). Small dense key
  domains → (B, n) equality-mask fused reductions on the VPU; otherwise a
  multi-lane stable sort by real group keys (masked rows to the end), then
  scatter-free segmented reductions: cumsum deltas and segmented associative
  scans gathered at boundaries located by searchsorted. Deterministic and
  collision-free (sorts real keys, not hashes).
- TopN = the same lexicographic sort with MySQL NULL placement, then a
  static-width head slice.
- All shapes static: inputs padded to power-of-two buckets, group outputs
  capped at ``agg_cap`` (kernel reports true group count; the caller re-runs
  with a bigger cap on overflow — the recompile-storm guard from SURVEY §7).

Numeric policy: compute in int64/float64 (x64 enabled; TPU emulates i64 as
pairs), but STORAGE narrows — device-cached lanes whose min/max fit int32
ship as int32 and upcast on first use (tpu_engine._narrowed), and group-bys
with proven value magnitudes ride the int8 MXU dot (ops/mxu_groupby.py,
byte-limb exact accumulation) instead of emulated VPU reductions.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from tidb_tpu.copr import dagpb
from tidb_tpu.expression.expr import AggDesc, EvalBatch, _ft_from_pb, eval_expr, expr_from_pb
from tidb_tpu.types import TypeKind
from tidb_tpu.utils import tracing as _tracing

MAX_RANGES = 8
_I64_MAX = np.iinfo(np.int64).max
_I64_MIN = np.iinfo(np.int64).min
# dense path does B*n work per agg lane; past this many buckets the int8
# MXU dot (≤ mxu_groupby.MAX_B) or the lex-sort path takes over
_DENSE_EQMASK_MAX = 32


def _dense_b_total(doms) -> int:
    b = 1
    for dm in doms:
        b *= dm + 1
    return b


def _mxu_aggs_ok(aggs, arg_bounds=()) -> bool:
    """The MXU grouped-sum paths cover COUNT/SUM lanes whose values are
    provably < 2^45 (exact limb accumulation). The magnitude proof itself
    lives in :func:`_pair_bound` — the SAME function the dot path uses to
    plan its limbs, so the gate and the kernel can never disagree on which
    lanes are bounded. Anything else takes the eqmask/sort path."""
    for i, a in enumerate(aggs):
        kinds = a.partial_kinds
        if all(pk == "count" for pk in kinds):
            continue  # value lane unused (zeros)
        for pk in kinds:
            if pk == "count":
                continue
            if pk != "sum":
                return False  # min/max/first_row: no matmul form
            if a.arg is None:
                return False
            b = _pair_bound(a, arg_bounds[i] if i < len(arg_bounds) else None)
            if b is None or max(abs(int(b[0])), abs(int(b[1]))) >= (1 << 45):
                return False
    return True


class _DeviceWarnSink:
    """Collects TRACED warning counts during a kernel trace — the device
    analog of stmtctx.AppendWarning. Each (code, msg) site contributes one
    traced scalar; the kernel packs them into its meta row as extra outputs
    and the engine converts nonzero counts back into session warnings.
    Counts are per-row over valid lanes; rows a later mask drops may be
    included (MySQL itself is loose about warning multiplicity)."""

    def __init__(self):
        self.items: list = []  # [(code, msg, traced_count)]

    def add_traced(self, code: int, msg: str, cnt) -> None:
        self.items.append((code, msg, cnt))

    def __call__(self, level, code, msg):  # host-style calls inside a trace
        # concrete (trace-time constant) warnings: count 1 per call
        self.items.append((code, msg, 1))


@dataclass
class CompiledKernel:
    fn: Callable  # (handles, cols, ranges, nvalid) -> packed buffer(s); mapped (m > 1): (slots, ranges, nvalid)
    kind: str  # "rows" | "agg"
    out_n: int  # static output row capacity
    agg_cap: int
    # _lanes is written exactly at trace time (atomic tuple swap — concurrent
    # traces of the same DAG compute identical values, so last-writer-wins is
    # safe) and read after fn() returns, by which point a trace has completed
    _lanes: dict
    # what the XLA module is named after (`jit_<family>` on a trace's module
    # line): the DAG's shape, never its literals — see :func:`kernel_family`
    family: str = "cop"
    # regions one call answers: above 1 the MAPPED program (``get_kernel``),
    # whose buffers come back stacked ``(m, ...)``, one packed result a region
    m: int = 1

    @property
    def lane_loc(self):  # per-output ("i"|"f", row index) into packed buffer(s)
        return self._lanes["loc"]

    @property
    def valid_loc(self):  # per-output row index of the valid lane (int buffer)
        return self._lanes["vloc"]

    @property
    def warn_specs(self):  # [(code, msg, meta_slot)] packed at meta[slot]
        return self._lanes.get("warns", ())


_COMPILE_CACHE: dict[tuple, CompiledKernel] = {}
_CACHE_MU = _tracing.TracedLock("kernel_cache", threading.Lock())

# executor type → (its short name in a kernel family, its named scope)
_STAGE = {
    dagpb.SELECTION: ("sel", "selection"), dagpb.AGGREGATION: ("agg", "agg"), dagpb.STREAM_AGG: ("sagg", "agg"),
    dagpb.TOPN: ("topn", "topn"), dagpb.LIMIT: ("limit", "limit"), dagpb.PROJECTION: ("proj", "projection"),
    dagpb.WINDOW: ("win", "window"),
}


class _Stages(contextlib.ExitStack):
    """A kernel's stages as ``jax.named_scope``s (``scan.mask``,
    ``delta.fold``, ``selection``, ``agg``, ``topn``, ``window``, ``pack``
    ...): calling it ends the stage before and begins the named one; leaving
    the ``with`` ends the last. Scopes are metadata on the operations — a
    profiler trace names them, the compiler fuses as before."""

    def __call__(self, name: str) -> None:
        import jax

        self.close()
        self.enter_context(jax.named_scope(name))


def kernel_family(dag: dagpb.DAGRequest, nb: int = 1, delta_cap: int = 0, m: int = 1) -> str:
    """The name a cop program carries in a profiler trace and in the
    ``exec.dispatch`` span: ``cop_<executors after the scan>_g<group-by
    keys>[_d][_b<blocks>][_m<regions>]`` — ``cop_sel_agg_g0`` (Q6),
    ``cop_sel_agg_g2`` (Q1), ``..._d`` read through a delta, ``..._b4`` four
    fused blocks of one region, ``..._m48`` the mapped program that answers 48
    regions in one call (``get_kernel``). From the DAG's shape only: every
    literal and padded size of one statement template lands in one family, so
    a reduction can sum a family's time; the tokens after ``cop_`` stay
    executor names, ``g<n>``, ``d``, ``b<n>``, ``m<n>``."""
    parts = ["cop"] + ([_STAGE.get(ex.tp, ("x",))[0] for ex in dag.executors[1:]] or ["scan"])
    groups = [len(ex.group_by) for ex in dag.executors[1:] if ex.tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG)]
    if groups:
        parts.append(f"g{groups[-1]}")
    if delta_cap:
        parts.append("d")
    if nb > 1:
        parts.append(f"b{nb}")
    if m > 1:
        parts.append(f"m{m}")
    return "_".join(parts)


# persistent XLA compile cache, used when JAX_COMPILATION_CACHE_DIR is unset:
# ONE fixed directory inside the checkout (gitignored). The directory is part
# of the cache key, so it is derived from the package path — never /tmp, a
# pid or a time — and every process of this checkout finds the same entries.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "_xla_cache"
)


def _ensure_x64():
    import jax

    jax.config.update("jax_enable_x64", True)
    if getattr(_ensure_x64, "_cc_done", False):
        return
    # kernel compiles are the dominant cold-start cost (chip_smoke.py reports
    # compile seconds per run), so they persist on disk. Where the operator
    # placed the cache with JAX_COMPILATION_CACHE_DIR, jax reads the variable
    # itself and this code sets no directory at all.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    _ensure_x64._cc_done = True


def get_kernel(
    dag: dagpb.DAGRequest,
    n_pad: int,
    agg_cap: int,
    nb: int = 1,
    full_scan: bool = False,
    delta_cap: int = 0,
    m: int = 1,
) -> CompiledKernel:
    """The compiled program of one (DAG, padded shape), built once.

    ``full_scan``: the caller proved every entry row is inside the
    requested ranges — the kernel skips the 8-range handle mask (8 emulated
    int64 compares per row, pure overhead on the typical analytic scan).

    ``delta_cap``: nonzero compiles the DELTA variant — the kernel takes a
    bounded extra operand of committed row changes (sorted touched handles,
    per-scan-column lanes, tombstone flags) padded to exactly ``delta_cap``
    rows, masks superseded/deleted base rows and unions the fresh ones. The
    cap is a fixed config constant, so varying delta SIZES reuse one compile
    — the compile-cache keying is otherwise unchanged.

    ``m``: above 1 the MAPPED program, one call for ``m`` regions of one
    padded shape (the batch cop task, ``tpu_engine._exec_single``):
    ``fn(slots, ranges, nvalid)``. ``slots`` is what ``m`` single-region calls
    take, region by region — ``((handles, ((data, valid), ...)), ...)``, the
    regions' own device-LRU arrays: nothing is kept stacked in HBM — with
    ``None`` where the program need not read an array: the handles of a full
    scan, the validity of a lane that holds no NULL in any of the regions (the
    caller's proof; the program takes such a lane as valid throughout, the
    rows past ``nvalid`` being masked as ever). Beside them ``(m, MAX_RANGES,
    2)`` ranges and ``(m,)`` ``nvalid``. The program stacks each lane ``(m,
    n_pad)`` (a temporary while it runs) and maps the single-region body over
    the leading region axis (``jax.vmap``: the body is traced once, so what
    grows with ``m`` is the stack alone: one copy a lane and slot). Out come
    the packed buffers stacked ``(m, ...)``: one partial result a region, the
    rows ``m`` calls of the ``m=1`` program give. A slot with ``nvalid`` 0
    scans nothing (padding up the engine's ladder of counts). No delta
    operand, no blocks: a region that needs either is a task of its own."""
    key = (dag.fingerprint(), n_pad, agg_cap, nb, full_scan, delta_cap, m)
    with _CACHE_MU:
        k = _COMPILE_CACHE.get(key)
    if k is None:
        k = _build(dag, n_pad, agg_cap, nb, full_scan, delta_cap, m)
        _arm_compile_probe(k)
        with _CACHE_MU:
            _COMPILE_CACHE[key] = k
    return k


def _arm_compile_probe(k: "CompiledKernel") -> None:
    """Attribute first-call jit compile time — keyed exactly like the kernel
    cache above, so 'cold' means the same thing to the compile metric and to
    dispatch routing. jax compiles lazily at the first invocation, so the
    probe times that call (compile + the first dispatch; execution itself is
    asynchronous and near-free in the measurement) into the active task's
    ExecDetails sidecar + the process histogram, then UNHOOKS itself — warm
    dispatches run the raw jitted callable with zero probe cost."""
    import threading as _th
    import time as _t

    inner = k.fn
    claim = _th.Lock()
    state = {"claimed": False}

    def first_call(*args, **kwargs):
        # exactly ONE dispatcher claims the compile measurement: a cold
        # multi-region fan-out has every worker enter here before the first
        # finishes, and each would otherwise observe (and charge its
        # sidecar) the full compile wall N times over
        with claim:
            mine = not state["claimed"]
            state["claimed"] = True
        if not mine:
            return inner(*args, **kwargs)
        from tidb_tpu.utils import execdetails as _ed
        from tidb_tpu.utils import metrics as _m

        t0 = _t.perf_counter()
        with _tracing.region("jit-compile"):
            out = inner(*args, **kwargs)
        dt = _t.perf_counter() - t0
        k.fn = inner  # warm path: no wrapper left behind
        det = _ed.current_cop()
        if det is not None:
            det.compile_ms += dt * 1000.0
        _m.COP_COMPILE_SECONDS.observe(dt)
        return out

    k.fn = first_call


def _build(
    dag: dagpb.DAGRequest, n_pad: int, agg_cap: int, nb: int = 1, full_scan: bool = False, delta_cap: int = 0,
    m: int = 1,
) -> CompiledKernel:
    _ensure_x64()
    import jax
    import jax.numpy as jnp

    D = delta_cap
    if m > 1 and (nb > 1 or D):
        raise ValueError("a mapped program takes whole clean regions: no blocks, no delta operand")
    executors = dag.executors
    scan = executors[0]
    if D and any(ex.tp == dagpb.WINDOW for ex in executors[1:]):
        # windows tie-break by row position inside window_core; the engine
        # merges the delta first instead of shipping it (tpu_engine gates)
        raise ValueError("window DAG cannot take a delta operand")
    # pre-parse expression trees (host-side, once per compile)
    parsed: list[Any] = []
    for ex in executors[1:]:
        if ex.tp == dagpb.SELECTION:
            parsed.append([expr_from_pb(c) for c in ex.conditions])
        elif ex.tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG):
            parsed.append(
                (
                    [expr_from_pb(g) for g in ex.group_by],
                    [AggDesc.from_pb(a) for a in ex.aggs],
                    ex.agg_mode,
                )
            )
        elif ex.tp == dagpb.TOPN:
            parsed.append(([(expr_from_pb(p), d) for p, d in ex.order_by], ex.limit))
        elif ex.tp == dagpb.PROJECTION:
            parsed.append([expr_from_pb(e) for e in ex.exprs])
        elif ex.tp == dagpb.WINDOW:
            from types import SimpleNamespace

            from tidb_tpu.ops.window_core import derive_specs

            funcs_ir = [
                SimpleNamespace(
                    name=f["name"],
                    args=[expr_from_pb(a) for a in f["args"]],
                    ftype=_ft_from_pb(f["ft"]),
                )
                for f in ex.win_funcs
            ]
            fr = ex.frame
            res = derive_specs(
                funcs_ir,
                whole_partition=fr == "whole",
                rows_frame=fr == "rows_cur",
                frame=tuple(fr[1:]) if isinstance(fr, tuple) else None,
                # order-key strings were legalized to sorted-dict codes by
                # the binder, so codes ARE order-comparable here
                order_is_string=False,
            )
            if res is None:
                raise ValueError("window shape not device-supported (planner gate missed)")
            parsed.append(
                (
                    [expr_from_pb(p) for p in ex.partition_by],
                    [(expr_from_pb(p), d) for p, d in ex.order_by],
                    res[0],
                    res[1],
                    funcs_ir,
                    [tuple(b) if b is not None else None for b in ex.sort_bounds] or None,
                )
            )
        else:
            parsed.append(None)

    n_total = n_pad * nb
    n_eff = n_total + D  # rows in the computation once the delta unions in
    agg_is_last = bool(executors[1:]) and executors[-1].tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG)
    topn_like = [ex for ex in executors[1:] if ex.tp in (dagpb.TOPN, dagpb.LIMIT)]
    out_n = n_eff
    if agg_is_last:
        out_n = agg_cap
    elif topn_like:
        # tight power-of-two (floor 32, not the global 1024 batch bucket):
        # the top_k K is a compile-shape constant, and small K is what lets
        # the hierarchical top_k keep per-row candidate sets tiny
        lim = max(ex.limit for ex in topn_like)
        out_n = min(n_eff, max(32, 1 << max(lim - 1, 0).bit_length()))

    def _bcast(d, n):
        d = jnp.asarray(d)
        return jnp.broadcast_to(d, (n,)) if d.ndim == 0 else d

    def _vmask(v, n):
        if v is None:
            return jnp.ones(n, dtype=bool)
        if v is False:
            return jnp.zeros(n, dtype=bool)
        v = jnp.asarray(v)
        return jnp.broadcast_to(v, (n,)) if v.ndim == 0 else v

    def _lex_perm(lanes):
        perm = jnp.argsort(lanes[-1], stable=True)
        for lane in reversed(lanes[:-1]):
            perm = perm[jnp.argsort(lane[perm], stable=True)]
        return perm

    # ---- MXU grouped-agg helpers (shared by the concat path and the
    # per-block fused path) ------------------------------------------------
    def _gvals_for(group_exprs, gnar, batch_b, batch_nw_b, nn):
        gvals_b = []
        for gi_, g in enumerate(group_exprs):
            src = batch_nw_b if gi_ < len(gnar) and gnar[gi_] else batch_b
            d, v, _ = eval_expr(g, src, jnp)
            d = _bcast(d, nn)
            v = _vmask(v, nn)
            gvals_b.append((jnp.where(v, d, 0), v))
        return gvals_b

    def _mxu_seg(gvals_b, doms, mask_b, nn, B):
        # int32 bucket arithmetic when every key lane is narrow (B is tiny)
        seg_dtype = (
            jnp.int32
            if gvals_b and all(d.dtype == jnp.int32 for d, _ in gvals_b)
            else jnp.int64
        )
        seg = jnp.zeros(nn, dtype=seg_dtype)
        stride = 1
        strides = []
        for (d, v), dom in zip(reversed(gvals_b), reversed(doms)):
            adj = jnp.where(v, d, dom)  # NULLs → extra bucket
            seg = seg + adj * stride
            strides.append(stride)
            stride *= dom + 1
        strides = list(reversed(strides))  # align with gvals order
        return jnp.where(mask_b, seg, B), strides

    def _mxu_pairs(aggs, arg_bounds, arg_narrow, batch_b, batch_nw_b, mask_b, nn):
        pairs = []
        pair_bounds = []
        lane_of_agg = []
        _zero64 = jnp.zeros(nn, dtype=jnp.int64)
        _arg_memo: dict = {}  # SUM(x) + AVG(x) share one lane set
        for ai, a in enumerate(aggs):
            count_only = all(pk == "count" for pk in a.partial_kinds)
            if a.arg is not None:
                nw = ai < len(arg_narrow) and arg_narrow[ai]
                memo_key = repr(a.arg.to_pb())
                got = _arg_memo.get(memo_key)
                if got is None:
                    d0, v0, _ = eval_expr(a.arg, batch_nw_b if nw else batch_b, jnp)
                    d0 = _bcast(d0, nn)
                    # proven-narrow args keep their int32 lanes: the limb
                    # build then shifts native int32
                    if jnp.issubdtype(d0.dtype, jnp.integer) and d0.dtype != jnp.int32:
                        d0 = d0.astype(jnp.int64)
                    # never-null args share the ONE mask object — the dot
                    # dedups weight columns by identity, and `mask & ones`
                    # per arg would materialize one identical int8 column
                    # per lane
                    w0 = mask_b if v0 is None else mask_b & _vmask(v0, nn)
                    got = (d0, w0)
                    _arg_memo[memo_key] = got
                d, w = got
                # COUNT(x) reads only the weight lane: zero the value so an
                # unbounded arg needs no limb proof
                if count_only:
                    d = _zero64
            else:
                d, w = _zero64, mask_b  # COUNT(*): weight = row mask
            lane_of_agg.append(len(pairs))
            pairs.append((d, w))
            pair_bounds.append(
                (0, 0) if count_only else _pair_bound(a, arg_bounds[ai] if ai < len(arg_bounds) else None)
            )
        occ_lane = len(pairs)
        pairs.append((jnp.zeros(nn, dtype=jnp.int64), mask_b))  # occupancy
        pair_bounds.append((0, 0))
        return pairs, pair_bounds, lane_of_agg, occ_lane

    def _mxu_outputs(counts, sums, lane_of_agg, occ_lane, aggs, mode, doms, strides, B):
        out_data, out_valid = [], []
        for a, li in zip(aggs, lane_of_agg):
            cnt = counts[:, li]
            for pk in a.partial_kinds:
                if pk == "count":
                    out_data.append(cnt)
                    out_valid.append(jnp.ones(B, dtype=bool))
                else:  # sum (gated by _mxu_aggs_ok)
                    out_data.append(sums[:, li])
                    out_valid.append(cnt > 0)
        if mode == dagpb.AGG_COMPLETE:
            out_data, out_valid = _finalize_device(jnp, aggs, out_data, out_valid)
        # group keys decode arithmetically from the bucket index
        bidx = jnp.arange(B)
        occupied = counts[:, occ_lane] > 0
        for dom, st in zip(doms, strides):
            code = (bidx // st) % (dom + 1)
            kv = (code != dom) & occupied
            # invalid lanes must still carry in-range dict codes
            out_data.append(jnp.where(kv, code, 0).astype(jnp.int64))
            out_valid.append(kv)
        order = jnp.argsort(~occupied, stable=True)
        ngroups = occupied.sum()
        out_cap = min(B, agg_cap)
        return (
            [o[order][:out_cap] for o in out_data],
            [o[order][:out_cap] for o in out_valid],
            ngroups,
        )

    def _rollup_layout(ex, group_exprs):
        """Static grouping-set layout for WITH ROLLUP on the MXU dot: the
        prefix sets (g1..gG), ..., (g1), () each own a bucket WINDOW; one
        (G+1)-hot matmul computes them all in a single pass (the Expand
        fusion — the reference replicates rows per set instead,
        cophandler/mpp_exec.go:422-466). Returns None when any key lacks a
        dictionary domain (the binder also gates this)."""
        from tidb_tpu.expression.expr import ColumnRef as _CR

        doms = []
        for g in group_exprs:
            if isinstance(g, _CR) and g.index < len(scan.domains) and scan.domains[g.index] > 0:
                doms.append(scan.domains[g.index])
            else:
                return None
        G = len(doms)
        sets = list(range(G, -1, -1))  # prefix lengths, widest first
        windows = []  # per set: (k, offset, B_k, strides[:k])
        off = 0
        for k in sets:
            stride = 1
            strides = []
            for dom in reversed(doms[:k]):
                strides.append(stride)
                stride *= dom + 1
            strides = list(reversed(strides))
            b_k = stride
            windows.append((k, off, b_k, strides))
            off += b_k
        return {"doms": doms, "G": G, "windows": windows, "B_total": off}

    def _mxu_rollup_segs(layout, gvals, mask_b, nn):
        """Per grouping set: a GLOBAL bucket-id lane (window offset + local
        bucket); dead rows point past every window."""
        B_total = layout["B_total"]
        segs = []
        for k, off, b_k, strides in layout["windows"]:
            seg_dtype = (
                jnp.int32
                if all(d.dtype == jnp.int32 for d, _ in gvals[:k])
                else jnp.int64
            )
            seg = jnp.zeros(nn, dtype=seg_dtype)
            for (d, v), dom, st in zip(gvals[:k], layout["doms"][:k], strides):
                adj = jnp.where(v, d, dom)  # NULL values → their own bucket
                seg = seg + adj * st
            seg = jnp.where(mask_b, seg + off, B_total)
            segs.append((seg.astype(jnp.int32), off, off + b_k))
        return segs

    def _mxu_rollup_outputs(counts, sums, lane_of_agg, occ_lane, aggs, mode, layout):
        """Bucket lanes → [agg outputs, keys (NULL when rolled up), GROUPING
        flags], compacted to occupied buckets."""
        B_total = layout["B_total"]
        doms, G = layout["doms"], layout["G"]
        out_data, out_valid = [], []
        for a, li in zip(aggs, lane_of_agg):
            cnt = counts[:, li]
            for pk in a.partial_kinds:
                if pk == "count":
                    out_data.append(cnt)
                    out_valid.append(jnp.ones(B_total, dtype=bool))
                else:  # sum (gated by _mxu_aggs_ok)
                    out_data.append(sums[:, li])
                    out_valid.append(cnt > 0)
        if mode == dagpb.AGG_COMPLETE:
            out_data, out_valid = _finalize_device(jnp, aggs, out_data, out_valid)
        occupied = counts[:, occ_lane] > 0
        # keys + flags decode per window, concatenated along the bucket axis
        flag_lanes = []  # flags append AFTER all keys
        for j in range(G):
            dparts, vparts, fparts = [], [], []
            for k, off, b_k, strides in layout["windows"]:
                lidx = jnp.arange(b_k)
                if j < k:
                    code = (lidx // strides[j]) % (doms[j] + 1)
                    kv = (code != doms[j]) & occupied[off : off + b_k]
                    dparts.append(jnp.where(kv, code, 0).astype(jnp.int64))
                    vparts.append(kv)
                    fparts.append(jnp.zeros(b_k, dtype=jnp.int64))
                else:  # rolled-up key: NULL, flag 1
                    dparts.append(jnp.zeros(b_k, dtype=jnp.int64))
                    vparts.append(jnp.zeros(b_k, dtype=bool))
                    fparts.append(jnp.ones(b_k, dtype=jnp.int64))
            out_data.append(jnp.concatenate(dparts))
            out_valid.append(vparts[0] if len(vparts) == 1 else jnp.concatenate(vparts))
            flag_lanes.append(jnp.concatenate(fparts))
        for f in flag_lanes:
            out_data.append(f)
            out_valid.append(jnp.ones(B_total, dtype=bool))
        order = jnp.argsort(~occupied, stable=True)
        ngroups = occupied.sum()
        out_cap = min(B_total, agg_cap)
        return (
            [o[order][:out_cap] for o in out_data],
            [o[order][:out_cap] for o in out_valid],
            ngroups,
        )

    def _static_dot_route():
        """Per-block fused routing gate: [scan, selection*, agg-last] DAGs
        whose agg provably rides the int8 MXU dot can skip the nb-block
        concatenation (a pure HBM copy of every lane) and accumulate one
        (B, C) limb matrix per block instead. Mirrors the dynamic routing in
        the agg branch — same domains, same magnitude proofs."""
        from tidb_tpu.expression.expr import ColumnRef as _CR

        from tidb_tpu.ops.mxu_groupby import MAX_B as _DOT_MAX_B

        if nb <= 1 or not agg_is_last or D:
            # the delta variant needs the generic concat path (delta lanes
            # have no per-block shape); merges fold deltas away quickly
            return None
        if any(ex.tp != dagpb.SELECTION for ex in executors[1:-1]):
            return None
        group_exprs, aggs, _mode = parsed[-1]
        if not group_exprs:
            return None
        if any(pk in ("bit_and", "bit_or", "bit_xor") for a in aggs for pk in a.partial_kinds):
            return None
        doms = []
        for g in group_exprs:
            if isinstance(g, _CR) and g.index < len(scan.domains) and scan.domains[g.index] > 0:
                doms.append(scan.domains[g.index])
            else:
                return None
        if not _mxu_aggs_ok(aggs, getattr(executors[-1], "arg_bounds", ())):
            return None
        if getattr(executors[-1], "rollup", False):
            # rollup bucket space = sum over prefix-set windows
            layout = _rollup_layout(executors[-1], group_exprs)
            if layout is None or layout["B_total"] > min(agg_cap, _DOT_MAX_B):
                return None
            return ("rollup", doms)
        bt = _dense_b_total(doms)
        if bt > min(agg_cap, _DOT_MAX_B):
            return None
        if not (bt > _DENSE_EQMASK_MAX or n_total >= (1 << 21)):
            return None
        return ("plain", doms)

    blockwise_doms = _static_dot_route()

    # per-trace device warn sink (holder survives into _pack; kernel() resets
    # it at trace start, so each compile owns exactly its own counts)
    warn_holder: list = []

    def _cur_dws():
        return warn_holder[-1] if warn_holder else None

    def _blockwise_dot(handles_blocks, cols_blocks, ranges, nvalid, stage):
        from tidb_tpu.ops.mxu_groupby import dot_acc, dot_plan, dot_recombine

        group_exprs, aggs, mode = parsed[-1]
        agg_ex = executors[-1]
        arg_bounds = getattr(agg_ex, "arg_bounds", ())
        arg_narrow = getattr(agg_ex, "arg_narrow", ())
        gnar = getattr(agg_ex, "group_narrow", [])
        route_kind, doms = blockwise_doms
        rollup_layout = None
        if route_kind == "rollup":
            rollup_layout = _rollup_layout(agg_ex, parsed[-1][0])
            B = rollup_layout["B_total"]
        else:
            B = _dense_b_total(doms)
        acc = None
        plan = None
        strides = None
        lane_of_agg = occ_lane = n_pairs = None
        for b in range(nb):
            stage("scan.mask")
            live = jnp.arange(n_pad, dtype=jnp.int32) < nvalid.astype(jnp.int32)[b]
            if full_scan:
                mask_b = live
            else:
                hb = handles_blocks[b].astype(jnp.int64)
                m = jnp.zeros(n_pad, dtype=bool)
                for r in range(MAX_RANGES):
                    lo, hi = ranges[r, 0], ranges[r, 1]
                    m = m | ((hb >= lo) & (hb < hi))
                mask_b = m & live
            cols_nw_b = tuple(c[b] for c in cols_blocks)
            cols64_b = tuple(
                (d.astype(jnp.int64) if jnp.issubdtype(d.dtype, jnp.integer) else d, v)
                for d, v in cols_nw_b
            )
            batch_b = EvalBatch(list(cols64_b), [None] * len(cols64_b), n_pad, warn=_cur_dws())
            batch_nw_b = EvalBatch(list(cols_nw_b), [None] * len(cols_nw_b), n_pad, warn=_cur_dws())
            for ex, pre in zip(executors[1:-1], parsed[:-1]):
                stage("selection")
                nok = getattr(ex, "narrow_ok", [])
                for ci_, cond in enumerate(pre):
                    src = batch_nw_b if ci_ < len(nok) and nok[ci_] else batch_b
                    d, v, _ = eval_expr(cond, src, jnp)
                    d = _bcast(d, n_pad)
                    keep = d != 0
                    if v is not None:
                        keep = keep & _vmask(v, n_pad)
                    mask_b = mask_b & keep
            stage("agg")
            gvals_b = _gvals_for(group_exprs, gnar, batch_b, batch_nw_b, n_pad)
            if rollup_layout is not None:
                seg = _mxu_rollup_segs(rollup_layout, gvals_b, mask_b, n_pad)
                strides_b = None
            else:
                seg, strides_b = _mxu_seg(gvals_b, doms, mask_b, n_pad, B)
            pairs, pair_bounds, lane_of_agg, occ_lane = _mxu_pairs(
                aggs, arg_bounds, arg_narrow, batch_b, batch_nw_b, mask_b, n_pad
            )
            if plan is None:
                # one static lane plan serves every block: the pair list is
                # built by identical code per block, so the positional column
                # layout (dedup pattern included) cannot differ
                plan = dot_plan(pairs, pair_bounds)
                strides = strides_b
                n_pairs = len(pairs)
            acc = dot_acc(
                seg if isinstance(seg, list) else seg.astype(jnp.int32), pairs, B, n_pad, plan, acc
            )
        counts, sums = dot_recombine(acc, plan, n_pairs, B)
        if rollup_layout is not None:
            out_data, out_valid, ngroups = _mxu_rollup_outputs(
                counts, sums, lane_of_agg, occ_lane, aggs, mode, rollup_layout
            )
        else:
            out_data, out_valid, ngroups = _mxu_outputs(
                counts, sums, lane_of_agg, occ_lane, aggs, mode, doms, strides, B
            )
        out_len = int(out_data[0].shape[0])
        gslot = jnp.arange(out_len)
        gvalid_slot = gslot < ngroups
        out_valid = [ov & gvalid_slot for ov in out_valid]
        stage("pack")
        offsets = dag.output_offsets or list(range(len(out_data)))
        outs = [(out_data[i], out_valid[i]) for i in offsets]
        return _pack(outs, ngroups, ngroups)

    def _kernel_body(handles, cols, ranges, nvalid, delta, stage):
        n = n_total
        warn_holder.clear()
        warn_holder.append(_DeviceWarnSink())
        if nb > 1 and blockwise_doms is not None:
            # agg-last DAG on the MXU dot: per-block accumulation, no concat
            return _blockwise_dot(handles, cols, ranges, nvalid, stage)
        stage("scan.mask")
        hrank = None
        handles_blocks = handles if nb > 1 else None
        if nb > 1:
            # fused multi-block program (window DAGs: the whole region in one
            # computation, reusing the per-block device LRU arrays); padding
            # is interspersed at each block's tail, masked via per-block counts
            handles = jnp.concatenate(handles)
            cols = tuple(
                (jnp.concatenate([b[0] for b in c]), jnp.concatenate([b[1] for b in c]))
                for c in cols
            )
            # int32 iota: n is static and < 2^31, and the emulated-int64
            # mod/div pair would cost real time at 20M+ rows
            iota = jnp.arange(n, dtype=jnp.int32)
            live = (iota % n_pad) < nvalid.astype(jnp.int32)[iota // n_pad]
        else:
            live = jnp.arange(n, dtype=jnp.int32) < nvalid.astype(jnp.int32)
        if handles is not None:  # None: a mapped program's full scan
            handles = handles.astype(jnp.int64)
        if delta is not None:
            stage("delta.fold")
            dh, dcols, dtomb, dn = delta
            dh = dh.astype(jnp.int64)  # sorted; pads hold int64-max
            # dn = [mask_n, union_lo, union_hi]: every dispatch masks against
            # the WHOLE delta; only rows in [union_lo, union_hi) union in —
            # the caller routes each delta row to the block whose handle span
            # contains it, so blocked outputs stay globally handle-ordered
            d_mask_n = dn[0]
            # 1) suppress superseded base rows: a base row whose handle is in
            # the delta set carries a stale version (updated or deleted) —
            # the delta lane holds the fresh verdict
            pos = jnp.searchsorted(dh, handles)
            posc = jnp.clip(pos, 0, D - 1)
            live = live & ~((dh[posc] == handles) & (posc < d_mask_n))
            # 2) merge ranks: each row's position in ascending-handle order
            # over [live base + delta] — restores the host engine's scan
            # order for tie-breaks, first_row, LIMIT, and row packing. Base
            # rank = live-index + #delta-handles-before; delta rank counts
            # live base handles ≤ it per block (pads → int64-max, harmless).
            if nb > 1:
                nv32 = nvalid.astype(jnp.int32)
                offs = jnp.concatenate(
                    [jnp.zeros(1, jnp.int32), jnp.cumsum(nv32)[:-1].astype(jnp.int32)]
                )
                iota32 = jnp.arange(n, dtype=jnp.int32)
                li = (iota32 % n_pad) + offs[iota32 // n_pad]
                cntb = jnp.zeros(D, dtype=jnp.int32)
                for b in range(nb):
                    hb = handles_blocks[b].astype(jnp.int64)
                    hb = jnp.where(jnp.arange(n_pad) < nv32[b], hb, _I64_MAX)
                    cntb = cntb + jnp.searchsorted(hb, dh, side="right").astype(jnp.int32)
            else:
                nv32 = nvalid.astype(jnp.int32)
                li = jnp.arange(n, dtype=jnp.int32)
                hsrt = jnp.where(jnp.arange(n) < nv32, handles, _I64_MAX)
                cntb = jnp.searchsorted(hsrt, dh, side="right").astype(jnp.int32)
            hrank = jnp.concatenate(
                [li + pos.astype(jnp.int32), jnp.arange(D, dtype=jnp.int32) + cntb]
            )
            # 3) union the fresh rows (tombstones mask only, never union)
            diota = jnp.arange(D)
            dlive = (diota >= dn[1]) & (diota < dn[2]) & ~dtomb
            handles = jnp.concatenate([handles, dh])
            live = jnp.concatenate([live, dlive])
            cols = tuple(
                (jnp.concatenate([d, dd]), jnp.concatenate([v, dv]))
                for (d, v), (dd, dv) in zip(cols, dcols)
            )
            n = n_eff
            stage("scan.mask")
        # HBM lanes may be narrowed (int32 dict codes / bounded values — see
        # tpu_engine._narrowed). TWO views: the default batch upcasts integer
        # lanes to int64 (fused into each consumer); binder-proven narrow
        # expressions evaluate on the raw storage-dtype view instead, where
        # int32 VPU ops run native rather than as emulated int64 pairs
        cols_nw = cols
        cols = tuple(
            (d.astype(jnp.int64) if jnp.issubdtype(d.dtype, jnp.integer) else d, v)
            for d, v in cols_nw
        )
        if full_scan:
            mask = live  # the caller proved range coverage statically
        else:
            # range mask: padded (MAX_RANGES, 2); empty slots have lo >= hi
            mask = jnp.zeros(n, dtype=bool)
            for r in range(MAX_RANGES):
                lo, hi = ranges[r, 0], ranges[r, 1]
                mask = mask | ((handles >= lo) & (handles < hi))
            mask = mask & live  # padding rows are never live
        batch = EvalBatch([(d, v) for d, v in cols], [None] * len(cols), n, warn=_cur_dws())
        # storage-dtype view for binder-proven narrow evals; only valid while
        # ColumnRefs still address scan outputs (the binder stamps flags only
        # then, so stale use is impossible by construction)
        batch_nw = EvalBatch([(d, v) for d, v in cols_nw], [None] * len(cols_nw), n, warn=_cur_dws())
        kind = "rows"
        count = None
        ngroups = None

        for exi, (ex, pre) in enumerate(zip(executors[1:], parsed)):
            stage(_STAGE[ex.tp][1] if ex.tp in _STAGE else ex.tp)
            if ex.tp == dagpb.SELECTION:
                nok = getattr(ex, "narrow_ok", [])
                for ci_, cond in enumerate(pre):
                    src = batch_nw if ci_ < len(nok) and nok[ci_] else batch
                    d, v, _ = eval_expr(cond, src, jnp)
                    d = _bcast(d, n)
                    keep = d != 0
                    if v is not None:
                        keep = keep & _vmask(v, n)
                    mask = mask & keep
            elif ex.tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG):
                group_exprs, aggs, mode = pre
                # dense fast path: every group key is a scan column with a
                # known small domain (dictionary codes) → bucket index is
                # pure arithmetic, no O(n log n) sort. One extra bucket per
                # key holds its NULLs.
                dense_doms = None
                mxu_doms = None
                # bit aggregates reduce with non-additive ops: only the sort
                # path's segmented associative scan handles them
                has_bit = any(
                    pk in ("bit_and", "bit_or", "bit_xor") for a in aggs for pk in a.partial_kinds
                )
                if group_exprs and not has_bit:
                    doms = []
                    for g in group_exprs:
                        from tidb_tpu.expression.expr import ColumnRef as _CR

                        if isinstance(g, _CR) and g.index < len(scan.domains) and scan.domains[g.index] > 0:
                            doms.append(scan.domains[g.index])
                        else:
                            doms = None
                            break
                    # equality-mask reduce costs B*n per agg lane on the VPU
                    # (in emulated x64); the int8 dot_general rides the
                    # systolic array instead — XLA's native MXU mode, no row
                    # cap (chunked int64 accumulation), no block-multiple
                    # constraint. Route to it whenever the magnitude proof
                    # holds, B fits its materialized (B, n) one-hot, and the
                    # batch is big enough to amortize its fixed cost — even
                    # for tiny B, where the eqmask was the round-2 default.
                    # The lex-sort path covers everything else, B > MAX_B
                    # included (a hand-tiled pallas kernel held 64 < B <= 512
                    # until Mosaic compiled it to WRONG sums on a v5e under
                    # jax 0.9.0, 2026-09-26 — deleted rather than hidden)
                    if doms:
                        from tidb_tpu.ops.mxu_groupby import MAX_B as _DOT_MAX_B

                        bt = _dense_b_total(doms)
                        dot_fits = bt <= min(agg_cap, _DOT_MAX_B) and _mxu_aggs_ok(
                            aggs, getattr(ex, "arg_bounds", ())
                        )
                        if dot_fits and (bt > _DENSE_EQMASK_MAX or n >= (1 << 21)):
                            mxu_doms = doms
                        elif bt <= min(agg_cap, _DENSE_EQMASK_MAX):
                            dense_doms = doms

                gnar = getattr(ex, "group_narrow", [])
                gvals = []
                for gi_, g in enumerate(group_exprs):
                    src = batch_nw if gi_ < len(gnar) and gnar[gi_] else batch
                    d, v, _ = eval_expr(g, src, jnp)
                    d = _bcast(d, n)
                    v = _vmask(v, n)
                    gvals.append((jnp.where(v, d, 0), v))
                if getattr(ex, "rollup", False):
                    # WITH ROLLUP: one (G+1)-hot MXU dot computes every
                    # grouping set in this same pass (the binder gated
                    # domains/bounds; anything it missed falls back to host)
                    from tidb_tpu.copr.binder import UnsupportedForDevice
                    from tidb_tpu.ops.mxu_groupby import MAX_B as _DOT_MAX_B
                    from tidb_tpu.ops.mxu_groupby import dot_acc, dot_plan, dot_recombine

                    layout = _rollup_layout(ex, group_exprs)
                    if (
                        layout is None
                        or layout["B_total"] > _DOT_MAX_B
                        or not _mxu_aggs_ok(aggs, getattr(ex, "arg_bounds", ()))
                    ):
                        raise UnsupportedForDevice("device rollup needs dict-domain keys + bounded sums")
                    segs = _mxu_rollup_segs(layout, gvals, mask, n)
                    pairs, pair_bounds, lane_of_agg, occ_lane = _mxu_pairs(
                        aggs, getattr(ex, "arg_bounds", ()), getattr(ex, "arg_narrow", ()), batch, batch_nw, mask, n
                    )
                    plan_ = dot_plan(pairs, pair_bounds)
                    acc_r = dot_acc(segs, pairs, layout["B_total"], n, plan_)
                    counts, sums = dot_recombine(acc_r, plan_, len(pairs), layout["B_total"])
                    out_data, out_valid, ngroups = _mxu_rollup_outputs(
                        counts, sums, lane_of_agg, occ_lane, aggs, mode, layout
                    )
                    out_len = int(out_data[0].shape[0])
                    gslot = jnp.arange(out_len)
                    gvalid_slot = gslot < ngroups
                    out_valid = [ov & gvalid_slot for ov in out_valid]
                    batch = EvalBatch(
                        [(d, v) for d, v in zip(out_data, out_valid)], [None] * len(out_data), out_len, warn=_cur_dws()
                    )
                    batch_nw = batch
                    mask = gvalid_slot
                    kind = "agg"
                    hrank = None  # rows rebuilt: scan alignment gone
                    continue
                # dense/MXU bucket arithmetic runs int32 when every key lane
                # is narrow (B is tiny, so the products always fit)
                seg_dtype = (
                    jnp.int32
                    if gvals and all(d.dtype == jnp.int32 for d, _ in gvals)
                    else jnp.int64
                )

                # TPU reduction policy: NO scatter anywhere. XLA lowers
                # segment_sum to scatter-add, which serializes on TPU
                # (~100ms per call on 2M rows, measured). Instead:
                #   dense path  — (B, n) equality-mask fused reductions (VPU)
                #   sort path   — lex sort, then cumsum deltas / segmented
                #                 associative scans gathered at segment
                #                 boundaries found by searchsorted
                pos = jnp.arange(n, dtype=jnp.int32)  # n < 2^31 always

                def _collect_aggs(eval_arg, reducers, first_pos, first_pos_c, ones_n):
                    # shared per-partial-kind switch for both reduction paths;
                    # reducers(d, v) returns the path's reduce callables
                    out_data, out_valid = [], []
                    for a in aggs:
                        d, v = eval_arg(a)
                        red = reducers(d, v)
                        cnt = red["count"]()
                        for pk in a.partial_kinds:
                            if pk == "count":
                                out_data.append(cnt)
                                out_valid.append(jnp.ones(ones_n, dtype=bool))
                            elif pk == "sum":
                                isf = a.arg is not None and a.arg.ftype.kind == TypeKind.FLOAT
                                out_data.append(red["sumf"]() if isf else red["sum"]())
                                out_valid.append(cnt > 0)
                            elif pk == "sumsq":
                                out_data.append(red["sumsq"]())
                                out_valid.append(cnt > 0)
                            elif pk in ("min", "max"):
                                if d.dtype == jnp.float64:
                                    sentinel = jnp.inf if pk == "min" else -jnp.inf
                                else:
                                    sentinel = _I64_MAX if pk == "min" else _I64_MIN
                                out_data.append(red[pk](sentinel))
                                out_valid.append(cnt > 0)
                            elif pk in ("bit_and", "bit_or", "bit_xor"):
                                out_data.append(red[pk]())
                                out_valid.append(jnp.ones(ones_n, dtype=bool))
                            elif pk == "first_row":
                                out_data.append(d[first_pos_c])
                                out_valid.append(v[first_pos_c] & (first_pos < n))
                    return out_data, out_valid

                if (dense_doms is not None or not gvals) and not has_bit:
                    doms = dense_doms if dense_doms is not None else []
                    B = 1
                    for dm in doms:
                        B *= dm + 1
                    seg = jnp.zeros(n, dtype=seg_dtype)
                    stride = 1
                    for (d, v), dom in zip(reversed(gvals), reversed(doms)):
                        adj = jnp.where(v, d, dom)  # NULLs → extra bucket
                        seg = seg + adj * stride
                        stride *= dom + 1
                    onehot = seg[None, :] == jnp.arange(B, dtype=seg.dtype)[:, None]  # (B, n)
                    livem = onehot & mask[None, :]
                    occupancy = livem.sum(axis=1)
                    live = occupancy > 0
                    if hrank is not None:
                        # first_row/key must pick the LOWEST-HANDLE row of the
                        # group (the host engine's scan order), not the lowest
                        # position — delta rows sit at the tail positionally
                        minr = jnp.where(livem, hrank[None, :], n).min(axis=1)
                        first_pos = jnp.where(
                            livem & (hrank[None, :] == minr[:, None]), pos[None, :], n
                        ).min(axis=1)
                    else:
                        first_pos = jnp.where(livem, pos[None, :], n).min(axis=1)
                    first_pos_c = jnp.clip(first_pos, 0, n - 1)

                    def eval_arg(a):
                        if a.arg is not None:
                            d, v, _ = eval_expr(a.arg, batch, jnp)
                            return _bcast(d, n), _vmask(v, n)
                        return jnp.ones(n, dtype=jnp.int64), jnp.ones(n, dtype=bool)

                    def reducers(d, v):
                        wm = livem & v[None, :]
                        return {
                            "count": lambda: wm.sum(axis=1),
                            "sum": lambda: jnp.where(wm, d[None, :], 0).sum(axis=1),
                            "sumf": lambda: jnp.where(wm, d[None, :] * 1.0, 0.0).sum(axis=1),
                            "sumsq": lambda: jnp.where(wm, (d[None, :] * 1.0) ** 2, 0.0).sum(axis=1),
                            "min": lambda s: jnp.where(wm, d[None, :], s).min(axis=1),
                            "max": lambda s: jnp.where(wm, d[None, :], s).max(axis=1),
                        }

                    out_data, out_valid = _collect_aggs(eval_arg, reducers, first_pos, first_pos_c, B)
                    if mode == dagpb.AGG_COMPLETE:
                        out_data, out_valid = _finalize_device(jnp, aggs, out_data, out_valid)
                    for g, (gd, gv) in zip(group_exprs, gvals):
                        out_data.append(gd[first_pos_c])
                        out_valid.append(gv[first_pos_c] & (first_pos < n))
                    # compact live buckets to the front; outputs stay B-sized
                    # (dense B is static and can't overflow, so there is no
                    # reason to pad to agg_cap — smaller device→host packets)
                    if gvals:
                        order = jnp.argsort(~live, stable=True)
                        ngroups = live.sum()
                    else:
                        order = jnp.arange(B)  # scalar agg: always one group
                        ngroups = jnp.asarray(1, dtype=jnp.int64)
                    out_cap = min(B, agg_cap)
                    out_data = [o[order][:out_cap] for o in out_data]
                    out_valid = [o[order][:out_cap] for o in out_valid]
                elif mxu_doms is not None:
                    # MXU path: one-hot int8 matmul grouped COUNT/SUM on the
                    # systolic array, exact via byte-limb accumulation
                    # (ops/mxu_groupby.py)
                    from tidb_tpu.ops.mxu_groupby import grouped_sums_dot

                    B = _dense_b_total(mxu_doms)
                    seg, strides = _mxu_seg(gvals, mxu_doms, mask, n, B)
                    arg_bounds = getattr(ex, "arg_bounds", ())
                    arg_narrow = getattr(ex, "arg_narrow", ())
                    pairs, pair_bounds, lane_of_agg, occ_lane = _mxu_pairs(
                        aggs, arg_bounds, arg_narrow, batch, batch_nw, mask, n
                    )
                    counts, sums = grouped_sums_dot(
                        seg.astype(jnp.int32), pairs, B, n, pair_bounds
                    )

                    out_data, out_valid, ngroups = _mxu_outputs(
                        counts, sums, lane_of_agg, occ_lane, aggs, mode, mxu_doms, strides, B
                    )
                else:
                    lanes = [~mask]
                    for d, v in gvals:
                        lanes.append(~v)  # NULL group lane
                        lanes.append(d)
                    if hrank is not None:
                        # least-significant handle-order lane: intra-group
                        # order (first_row, key pick) matches the host scan
                        lanes.append(hrank)
                    perm = _lex_perm(lanes)
                    sm = mask[perm]
                    first = jnp.arange(n) == 0
                    diff = jnp.zeros(n, dtype=bool)
                    for d, v in gvals:
                        ds, vs = d[perm], v[perm]
                        diff = diff | jnp.concatenate([jnp.zeros(1, bool), ds[1:] != ds[:-1]])
                        diff = diff | jnp.concatenate([jnp.zeros(1, bool), vs[1:] != vs[:-1]])
                    boundary = sm & (first | diff)
                    seg = jnp.clip(jnp.cumsum(boundary) - 1, 0, None)
                    ngroups = boundary.sum()
                    ks = jnp.arange(agg_cap)
                    # seg is nondecreasing → group k spans
                    # [searchsorted(seg,k,left), searchsorted(seg,k,right))
                    starts = jnp.searchsorted(seg, ks)
                    starts_c = jnp.clip(starts, 0, n - 1)
                    ends_c = jnp.clip(jnp.searchsorted(seg, ks, side="right") - 1, 0, n - 1)
                    slot_live = ks < ngroups
                    first_pos = jnp.where(slot_live, starts, n)
                    first_pos_c = starts_c

                    def _csum_delta(x):
                        cs = jnp.cumsum(x)
                        lo = jnp.where(starts_c > 0, cs[jnp.maximum(starts_c - 1, 0)], 0)
                        return jnp.where(slot_live, cs[ends_c] - lo, 0)

                    seg_ps = jax.lax.cummax(
                        jnp.where(boundary, jnp.arange(n, dtype=jnp.int32), -1)
                    )

                    def _seg_scan_red(x, op):
                        # log-doubling segmented running reduce — the generic
                        # associative_scan combinator compiles pathologically
                        # on TPU at scale (see window_core._seg_running)
                        from tidb_tpu.ops.window_core import _seg_running

                        r = _seg_running(jax, jnp, x, seg_ps, op, n)
                        return r[ends_c]

                    def _seg_extreme(w, d, which):
                        # grouped extreme by order statistics (see
                        # seg_value_sorted): invalid rows sink under a +max
                        # sentinel, so min = the group's start slot, max =
                        # start + valid_count - 1
                        from tidb_tpu.ops.window_core import seg_value_sorted

                        if jnp.issubdtype(d.dtype, jnp.floating):
                            pos = jnp.inf
                        else:
                            pos = jnp.iinfo(d.dtype).max
                        lane2 = seg_value_sorted(jnp, jnp.where(w, d, pos), seg)
                        if which == "min":
                            return jnp.where(slot_live, lane2[starts_c], 0)
                        cw = _csum_delta(w.astype(jnp.int64))
                        last = jnp.clip(starts + cw - 1, 0, n - 1)
                        return jnp.where(slot_live, lane2[last], 0)

                    def eval_arg(a):
                        if a.arg is not None:
                            d, v, _ = eval_expr(a.arg, batch, jnp)
                            return _bcast(d, n)[perm], _vmask(v, n)[perm]
                        return jnp.ones(n, dtype=jnp.int64), jnp.ones(n, dtype=bool)

                    def reducers(d, v):
                        w = sm & v
                        return {
                            "count": lambda: _csum_delta(w.astype(jnp.int64)),
                            "sum": lambda: _csum_delta(jnp.where(w, d, 0)),
                            "sumf": lambda: _csum_delta(jnp.where(w, d * 1.0, 0.0)),
                            "sumsq": lambda: _csum_delta(jnp.where(w, (d * 1.0) ** 2, 0.0)),
                            "min": lambda s: _seg_extreme(w, d, "min"),
                            "max": lambda s: _seg_extreme(w, d, "max"),
                            "bit_and": lambda: _seg_scan_red(jnp.where(w, d, -1), jnp.bitwise_and),
                            "bit_or": lambda: _seg_scan_red(jnp.where(w, d, 0), jnp.bitwise_or),
                            "bit_xor": lambda: _seg_scan_red(jnp.where(w, d, 0), jnp.bitwise_xor),
                        }

                    out_data, out_valid = _collect_aggs(eval_arg, reducers, first_pos, first_pos_c, agg_cap)
                    if mode == dagpb.AGG_COMPLETE:
                        out_data, out_valid = _finalize_device(jnp, aggs, out_data, out_valid)
                    for g, (gd, gv) in zip(group_exprs, gvals):
                        out_data.append(gd[perm][first_pos_c])
                        out_valid.append(gv[perm][first_pos_c] & (first_pos < n))
                out_len = int(out_data[0].shape[0]) if out_data else agg_cap
                gslot = jnp.arange(out_len)
                gvalid_slot = gslot < ngroups
                out_valid = [ov & gvalid_slot for ov in out_valid]
                # rebuild batch in case more executors follow
                batch = EvalBatch([(d, v) for d, v in zip(out_data, out_valid)], [None] * len(out_data), out_len, warn=_cur_dws())
                batch_nw = batch  # lanes rebuilt: the storage-dtype view is stale
                mask = gvalid_slot
                kind = "agg"
                hrank = None  # rows rebuilt: scan alignment gone
            elif ex.tp == dagpb.TOPN:
                order, limit = pre
                cur_n = batch.n
                # single-key fast path: two lax.top_k candidate pulls (value
                # rows, NULL rows) + an exact lex sort over the tiny 2K
                # candidate set. O(n) instead of a full multi-lane stable
                # argsort over the padded table (which at 10M+ rows costs
                # seconds to run and minutes to compile under x64 emulation).
                # Gated on key kinds whose physical values can never equal the
                # int64 sentinel (scaled decimals, dates, dict codes) or are
                # floats (MySQL stores no ±inf), so sentinel collisions are
                # impossible.
                _TOPK_KINDS = (
                    TypeKind.DECIMAL,
                    TypeKind.DATE,
                    TypeKind.DATETIME,
                    TypeKind.DURATION,
                    TypeKind.STRING,
                    TypeKind.FLOAT,
                )
                if len(order) == 1 and out_n <= 4096 and order[0][0].ftype.kind in _TOPK_KINDS:
                    e, desc = order[0]
                    d, v, _ = eval_expr(e, batch, jnp)
                    d = _bcast(d, cur_n)
                    v = _vmask(v, cur_n)
                    K = min(out_n, cur_n)
                    isf = jnp.issubdtype(d.dtype, jnp.floating)
                    d0 = jnp.where(v, d, 0)  # NULL keys zero, like the slow path
                    if desc:
                        key = d0
                    else:
                        # monotone-reversing: negate floats, complement ints
                        # (~d avoids INT64_MIN overflow)
                        key = -d0 if isf else ~d0
                    sent = -jnp.inf if isf else jnp.iinfo(jnp.int64).min
                    vkey = jnp.where(mask & v, key, sent)
                    # NOTE: TPU top_k does NOT break value ties by lowest
                    # index (CPU does) — the exact candidate sort below
                    # restores index order among retained ties. With binder-
                    # stamped value bounds the row index packs INTO the key,
                    # so even a tie group overflowing the K-candidate window
                    # selects exactly the host's stable-sort rows; without
                    # bounds (floats, expressions) boundary-overflow ties
                    # remain engine-unspecified, as MySQL allows
                    b0 = ex.sort_bounds[0] if getattr(ex, "sort_bounds", None) else None
                    if b0 is not None and not isf:
                        lo_, hi_ = int(b0[0]), int(b0[1])
                        span = hi_ - lo_ + 2
                        if span * (cur_n + 1) <= (1 << 62):
                            code = jnp.clip(d - lo_ + 1, 1, span - 1)
                            rank_code = code if desc else span - code
                            # delta variant: ties rank by merged handle order
                            # (the host scan order), not raw row position
                            pidx = hrank if hrank is not None else jnp.arange(cur_n)
                            vkey = jnp.where(
                                mask & v,
                                rank_code * cur_n + (cur_n - 1 - pidx),
                                jnp.iinfo(jnp.int64).min,
                            )
                    _, idx_val = _hier_top_k(jax, jnp, vkey, K)
                    # NULL rows deterministically in first-index order: the
                    # key encodes the (unique) row position, so ties cannot
                    # arise for the hardware top_k to scramble. int32: row
                    # positions always fit, and int32 top_k runs native
                    pos_n = hrank if hrank is not None else jnp.arange(cur_n, dtype=jnp.int32)
                    _, idx_null = _hier_top_k(jax, jnp, jnp.where(mask & ~v, -pos_n, jnp.iinfo(jnp.int32).min), K)
                    cand = jnp.concatenate([idx_val, idx_null])
                    # liveness is per-source: a top_k slot past the true count
                    # points at an arbitrary row and must not leak through
                    live_c = jnp.concatenate([(mask & v)[idx_val], (mask & ~v)[idx_null]])
                    if desc:
                        tier = jnp.concatenate([jnp.zeros(K, jnp.int64), jnp.ones(K, jnp.int64)])
                    else:  # ASC: NULLs first
                        tier = jnp.concatenate([jnp.ones(K, jnp.int64), jnp.zeros(K, jnp.int64)])
                    ckey = jnp.where(live_c, key[cand], 0)
                    # final lane: global row index (merged handle rank in the
                    # delta variant) — ties come out in scan order, matching
                    # the host engine's stable sort
                    tie = hrank[cand] if hrank is not None else cand
                    perm2 = _lex_perm([~live_c, tier, -ckey if isf else ~ckey, tie])
                    head = cand[perm2[:K]]
                    batch = EvalBatch(
                        [(_bcast(d2, cur_n)[head], _vmask(v2, cur_n)[head]) for d2, v2 in batch.cols],
                        batch.dicts,
                        K,
                        warn=_cur_dws(),
                    )
                    batch_nw = batch  # lanes rebuilt: storage-dtype view stale
                    count = jnp.minimum(limit, mask.sum())
                    mask = jnp.arange(K) < count
                    kind = "rows"
                    hrank = None  # rows rebuilt: scan alignment gone
                    continue
                lanes = [~mask]
                for e, desc in order:
                    d, v, _ = eval_expr(e, batch, jnp)
                    d = _bcast(d, cur_n)
                    v = _vmask(v, cur_n)
                    if desc:
                        lanes.append(~v)  # NULLs last
                        dd = jnp.where(v, d, 0)
                        # ints: bitwise complement (monotone-reversing, no
                        # INT64_MIN overflow); floats: negate
                        lanes.append(-dd if jnp.issubdtype(dd.dtype, jnp.floating) else ~dd)
                    else:
                        lanes.append(v)  # NULLs first
                        lanes.append(jnp.where(v, d, 0))
                if hrank is not None:
                    # delta variant: stable-sort ties break by merged handle
                    # order (the host engine's scan order), not row position
                    lanes.append(hrank)
                perm = _lex_perm(lanes)
                head_n = min(out_n, cur_n)
                head = perm[:head_n]
                batch = EvalBatch(
                    [(_bcast(d, cur_n)[head], _vmask(v, cur_n)[head]) for d, v in batch.cols],
                    batch.dicts,
                    head_n,
                    warn=_cur_dws(),
                )
                batch_nw = batch  # lanes rebuilt: storage-dtype view stale
                count = jnp.minimum(limit, mask.sum())
                mask = jnp.arange(head_n) < count
                kind = "rows"
                hrank = None  # rows rebuilt: scan alignment gone
            elif ex.tp == dagpb.LIMIT:
                cur_n = batch.n
                # first `head_n` live rows in index order — O(n), no full
                # sort. The key encodes the unique row position (TPU top_k
                # scrambles ties, so an all-ones mask key would be wrong);
                # int32 since row positions always fit. Delta variant: "first"
                # means lowest merged handle rank, matching the host scan.
                lim_pos = hrank if hrank is not None else jnp.arange(cur_n, dtype=jnp.int32)
                _, head = _hier_top_k(
                    jax,
                    jnp,
                    jnp.where(mask, -lim_pos, jnp.iinfo(jnp.int32).min),
                    min(out_n, cur_n),
                )
                batch = EvalBatch(
                    [(_bcast(d, cur_n)[head], _vmask(v, cur_n)[head]) for d, v in batch.cols],
                    batch.dicts,
                    len(head),
                    warn=_cur_dws(),
                )
                batch_nw = batch  # lanes rebuilt: storage-dtype view stale
                count = jnp.minimum(ex.limit, mask.sum())
                mask = jnp.arange(len(head)) < count
                kind = "rows"
                hrank = None  # rows rebuilt: scan alignment gone
            elif ex.tp == dagpb.PROJECTION:
                cur_n = batch.n
                new_cols = []
                for e in pre:
                    d, v, _ = eval_expr(e, batch, jnp)
                    new_cols.append((_bcast(d, cur_n), _vmask(v, cur_n)))
                batch = EvalBatch(new_cols, [None] * len(new_cols), cur_n, warn=_cur_dws())
                batch_nw = batch  # lanes rebuilt: the storage-dtype view is stale
            elif ex.tp == dagpb.WINDOW:
                from tidb_tpu.ops.window_core import window_program

                part_exprs, order_pairs, frame_tag, specs, funcs_ir, bounds = pre

                def lane(e):
                    d, v, _ = eval_expr(e, batch, jnp)
                    return (_bcast(d, n), _vmask(v, n))

                part_lanes = [lane(e) for e in part_exprs]
                order_lanes = [lane(e) for e, _ in order_pairs]
                arg_lanes = []
                for f, sp in zip(funcs_ir, specs):
                    # None (not a zeros pair) for no-arg funcs: arg lanes ride
                    # the window sort as payloads, and dead payloads would
                    # inflate the variadic sort for nothing
                    arg_lanes.append(lane(f.args[0]) if sp[1] else None)
                base_cols = [(_bcast(d, n), _vmask(v, n)) for d, v in batch.cols]
                nxt = executors[2 + exi].tp if 2 + exi < len(executors) else None
                agg_next = nxt in (dagpb.AGGREGATION, dagpb.STREAM_AGG)
                # only base columns the rest of the DAG actually reads ride
                # the sort — every extra payload operand inflates the
                # variadic sort's compile time (minutes at 20M rows)
                used: set[int] = set()
                if agg_next:
                    from tidb_tpu.planner.optimizer import _expr_cols as _cols_of

                    g_exprs, a_descs, _mode = parsed[exi + 1]
                    for e in g_exprs:
                        _cols_of(e, used)
                    for a in a_descs:
                        if a.arg is not None:
                            _cols_of(a.arg, used)
                    used = {i for i in used if i < len(base_cols)}
                ship = sorted(used)
                outs, perm, sm, base_sorted = window_program(
                    jax,
                    jnp,
                    mask=mask,
                    part_lanes=part_lanes,
                    order_lanes=order_lanes,
                    order_descs=[d for _, d in order_pairs],
                    frame_tag=frame_tag,
                    specs=specs,
                    arg_lanes=arg_lanes,
                    n=n,
                    bounds=bounds,
                    # base columns ride the sort when the consumer keeps
                    # sorted order — d[perm] gathers cost ~0.5s each at 21M
                    extra_lanes=[base_cols[i] for i in ship] if agg_next else [],
                )
                if agg_next:
                    # an aggregation consumes rows order-free: keep everything
                    # in sorted order and skip the inverse-permutation sort;
                    # unread positions keep their (unsorted) lanes — the agg
                    # never evaluates them
                    new_cols = list(base_cols)
                    for pos, col_pair in zip(ship, base_sorted):
                        new_cols[pos] = col_pair
                    new_cols = new_cols + list(outs)
                    mask = sm
                else:
                    inv = jnp.argsort(perm)
                    new_cols = base_cols + [(d[inv], v[inv]) for d, v in outs]
                batch = EvalBatch(new_cols, list(batch.dicts) + [None] * len(outs), n, warn=_cur_dws())
                batch_nw = batch  # lanes rebuilt: the storage-dtype view is stale

        # final packaging; ngroups travels out so the caller can detect
        # agg-cap overflow even when agg is not the last executor
        stage("pack")
        og = ngroups if ngroups is not None else jnp.asarray(-1, dtype=jnp.int64)
        offsets = dag.output_offsets or list(range(len(batch.cols)))
        if kind == "agg":
            outs = [(batch.cols[i][0], batch.cols[i][1]) for i in offsets]
            return _pack(outs, ngroups, og)
        cur_n = batch.n
        if count is None:
            # compact selected rows to the front; the delta variant restores
            # ascending-handle order (the host engine's scan order) — delta
            # rows sit at the tail positionally but not logically
            if hrank is not None:
                perm = _lex_perm([~mask, hrank])
            else:
                perm = jnp.argsort(~mask, stable=True)
            count = mask.sum()
            outs = [
                (_bcast(d, cur_n)[perm][:out_n], _vmask(v, cur_n)[perm][:out_n]) for d, v in batch.cols
            ]
            outs = [outs[i] for i in offsets]
            return _pack(outs, jnp.minimum(count, out_n), og)
        outs = [(_bcast(d, cur_n), _vmask(v, cur_n)) for d, v in batch.cols]
        outs = [outs[i] for i in offsets]
        return _pack(outs, count, og)

    # Device round trips through the host↔TPU link dominate end-to-end query
    # latency (each transfer is a full RTT), so the kernel packs everything —
    # count, ngroups, and all (data, valid) lanes — into ONE int64 buffer
    # (row 0 = [count, ngroups]). Float lanes can't ride it (the TPU x64
    # rewriter has no 64-bit bitcast), so they go in a second float64 buffer
    # emitted only when a query actually produces float outputs.
    lanes_holder: dict = {}

    def _pack(outs, count, og):
        loc: list = []
        vloc: list = []
        ilanes: list = []
        flanes: list = []
        dws = _cur_dws()
        witems = list(dws.items) if dws is not None else []
        L = max((int(d.shape[0]) if d.ndim else 1) for d, _ in outs) if outs else 2
        # meta row: [count, ngroups, warn counts...] — device warnings ride
        # the SAME packed transfer as the data (no extra fetch round trip)
        L = max(L, 2 + len(witems))
        meta = jnp.zeros(L, dtype=jnp.int64)
        meta = meta.at[0].set(jnp.asarray(count, dtype=jnp.int64))
        meta = meta.at[1].set(jnp.asarray(og, dtype=jnp.int64))
        for wi, (_code, _msg, cnt) in enumerate(witems):
            meta = meta.at[2 + wi].set(jnp.asarray(cnt, dtype=jnp.int64))
        lanes_holder["warns"] = tuple(
            (code, msg, 2 + wi) for wi, (code, msg, _c) in enumerate(witems)
        )
        ilanes.append(meta)
        for d, v in outs:
            d = jnp.asarray(d)
            d = jnp.broadcast_to(d, (L,)) if d.ndim == 0 else d
            if d.shape[0] < L:  # meta row needs ≥2 slots; short lanes pad
                d = jnp.pad(d, (0, L - d.shape[0]))
            if jnp.issubdtype(d.dtype, jnp.floating):
                loc.append(("f", len(flanes)))
                flanes.append(d.astype(jnp.float64))
            else:
                loc.append(("i", len(ilanes)))
                ilanes.append(d.astype(jnp.int64))
            vv = jnp.ones(L, dtype=bool) if v is None else jnp.asarray(v)
            vv = jnp.broadcast_to(vv, (L,)) if vv.ndim == 0 else vv
            if vv.shape[0] < L:
                vv = jnp.pad(vv, (0, L - vv.shape[0]))
            vloc.append(len(ilanes))
            ilanes.append(vv.astype(jnp.int64))
        lanes_holder.update({"loc": tuple(loc), "vloc": tuple(vloc)})
        if flanes:
            return jnp.stack(ilanes), jnp.stack(flanes)
        return jnp.stack(ilanes)

    import jax

    if D:
        def kernel(handles, cols, ranges, nvalid, dh, dcols, dtomb, dn):
            with _Stages() as stage:
                return _kernel_body(handles, cols, ranges, nvalid, (dh, dcols, dtomb, dn), stage)
    elif m > 1:
        def one_region(operands):
            handles, cols, ranges, nvalid = operands
            cols = tuple((d, jnp.ones(n_pad, dtype=bool) if v is None else v) for d, v in cols)
            with _Stages() as stage:
                return _kernel_body(handles, cols, ranges, nvalid, None, stage)

        def kernel(slots, ranges, nvalid):
            # the m regions' arrays → one (m, n_pad) array a lane (None stays
            # None): ONE concatenate whatever m is, no Python loop around the body
            hs, lanes = jax.tree.map(lambda *lane: jax.lax.concatenate(lane, 0).reshape(m, n_pad), *slots)
            # the stack is a copy either way; behind a barrier the compiler does
            # not try the m-operand concatenates inside every consumer's fusion
            # (compile 11.4 s → 3.9 s for Q1 at 48 x 262,144 rows, a v5e described in the sandbox)
            hs, lanes = jax.lax.optimization_barrier((hs, lanes))
            # vmap, not lax.map: measured on the v5e at 48 x 262,144 rows, Q1's
            # 13.8 ms against 24.8 (a loop of 48 runs of the body) and 13.7 as 46 calls
            return jax.vmap(one_region)((hs, lanes, ranges, nvalid))
    else:
        def kernel(handles, cols, ranges, nvalid):
            with _Stages() as stage:
                return _kernel_body(handles, cols, ranges, nvalid, None, stage)

    family = kernel_family(dag, nb, D, m)
    kernel.__name__ = kernel.__qualname__ = family  # the XLA module is jit_<family>
    jitted = jax.jit(kernel)
    return CompiledKernel(jitted, "agg" if agg_is_last else "rows", out_n, agg_cap, lanes_holder, family, m)


def _hier_top_k(jax, jnp, vals, K: int):
    """Hierarchical top_k: XLA's flat top_k over tens of millions of elements
    runs a near-full sort (~87ms/20M int64 measured); per-row top_k on a
    (R, C) reshape + a small second-level reduce is ~5x faster. Exact: each
    row keeps min(K, C) winners, and a single row can contribute at most K
    rows to the global top-K (when K > C the row keeps everything).
    Returns (values, GLOBAL indices) like lax.top_k."""
    n = int(vals.shape[0])
    R = min(16384, n // max(2 * K, 128))
    if n < (1 << 21) or R < 8:
        return jax.lax.top_k(vals, K)
    C = n // R
    main, tail = vals[: R * C], vals[R * C :]
    v, i = jax.lax.top_k(main.reshape(R, C), min(K, C))
    gi = (i.astype(jnp.int32) + (jnp.arange(R, dtype=jnp.int32) * C)[:, None]).reshape(-1)
    v2 = jnp.concatenate([v.reshape(-1), tail])
    g2 = jnp.concatenate([gi, jnp.arange(R * C, n, dtype=jnp.int32)])
    vf, sel = jax.lax.top_k(v2, K)
    return vf, g2[sel]


def _pair_bound(a, b):
    """(lo, hi) magnitude proof for one agg's value lane — the binder's
    corner bounds when stamped, else the conservative ftype envelope the
    MXU gate (_mxu_aggs_ok) admitted."""
    if b is not None:
        return (int(b[0]), int(b[1]))
    ft = a.arg.ftype if a.arg is not None else None
    if ft is None:
        return (0, 0)  # count(*): zeros lane
    if ft.kind == TypeKind.DECIMAL and 0 < ft.length <= 13:
        m = 10 ** ft.length
        return (-m, m)
    if ft.kind == TypeKind.DATE:
        return (0, 1 << 23)
    return None  # int32 dtype envelope inside grouped_sums_dot


def _finalize_device(jnp, aggs, state_data, state_valid):
    """Collapse partial lanes → final values, on device (complete mode)."""
    out_d, out_v = [], []
    i = 0
    for a in aggs:
        if a.name == "avg":
            cnt, s = state_data[i], state_data[i + 1]
            i += 2
            denom = jnp.maximum(cnt, 1)
            if a.ftype.kind == TypeKind.DECIMAL:
                num = s * (10**4)
                q = jnp.sign(num) * ((jnp.abs(num) + denom // 2) // denom)
                out_d.append(q)
            else:
                out_d.append(s / denom)
            out_v.append(cnt > 0)
        elif a.name in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
            cnt, s, sq = state_data[i], state_data[i + 1], state_data[i + 2]
            i += 3
            scale = 10.0 ** a.arg.ftype.scale if a.arg.ftype.kind == TypeKind.DECIMAL else 1.0
            nf = cnt * 1.0
            sv = s / scale
            sqv = sq / (scale * scale)
            mean = sv / jnp.maximum(nf, 1)
            varp = jnp.maximum(sqv / jnp.maximum(nf, 1) - mean * mean, 0.0)
            if a.name.endswith("_samp"):
                v = varp * nf / jnp.maximum(nf - 1, 1)
                ok = cnt > 1
            else:
                v, ok = varp, cnt > 0
            out_d.append(jnp.sqrt(v) if a.name.startswith("stddev") else v)
            out_v.append(ok)
        else:
            out_d.append(state_data[i])
            out_v.append(state_valid[i])
            i += 1
    return out_d, out_v
