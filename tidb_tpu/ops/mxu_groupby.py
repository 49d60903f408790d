"""Grouped COUNT/SUM as one XLA int8 matmul on the MXU.

This path hands XLA ONE ``dot_general(onehot_i8, limbs_i8) -> int32`` per
≤8M-row chunk — the native int8 systolic-array mode — and recombines limbs
exactly in int64:

- values bias to non-negative by their proven lower bound (binder bounds, or
  the int32 dtype envelope) and split into 8-bit limbs, each re-biased by
  -128 into [-128, 127] so full bytes ride SIGNED int8; a per-bucket
  occupancy column undoes the -128 bias exactly at recombination. The limb
  count per lane follows the proven RANGE, so a DECIMAL(12,2) column costs
  3 limb columns while a dict code costs 1 — the "narrow the compute lanes"
  discipline (ref: per-width column handling, pkg/util/chunk/column.go:74).
- int32 accumulation is exact while chunk_rows * 128 < 2^31 → chunks of 2^23
  rows (128 * 2^23 = 2^30), summed across chunks in int64. No f32 rounding
  anywhere.
- COUNT rides a shared 0/1 weight column per distinct validity mask; pairs
  sharing (value, weight) share limb columns, and constant lanes (COUNT's
  zeros) carry none.

Exact for any |value| < 2^62 (int64 bias); lanes with no usable bound get
the full 10-limb int64 split, still exact but wider.
"""

from __future__ import annotations

import functools

_CHUNK = 1 << 23  # int32 accumulator headroom: 255 * 2^23 < 2^31
_LIMB_BITS = 8  # biased to [-128, 127] so full bytes ride SIGNED int8
_LIMB_MASK = (1 << _LIMB_BITS) - 1
_LIMB_BIAS = 1 << (_LIMB_BITS - 1)
MAX_B = 64  # onehot is materialized (B, chunk) int8 — keep it < ~512MB


def _scoped(fn):
    """Trace ``fn`` under ``jax.named_scope("mxu_groupby")``, so that its
    operations carry that name in a profiler trace (metadata only)."""

    @functools.wraps(fn)
    def inner(*args, **kwargs):
        import jax

        with jax.named_scope("mxu_groupby"):
            return fn(*args, **kwargs)

    return inner


def rollup_bucket_space(doms) -> int:
    """Total bucket-window space of WITH ROLLUP's prefix grouping sets:
    sum over k of prod(dom_i + 1, i < k). THE single formula both the
    binder's device gate and the kernel's window layout use — drift between
    them would admit DAGs the kernel rejects (or vice versa)."""
    total = 0
    for k in range(len(doms), -1, -1):
        b_k = 1
        for dom in doms[:k]:
            b_k *= dom + 1
        total += b_k
    return total


def _limbs_needed(span: int) -> int:
    n = 1
    while span >> (_LIMB_BITS * n):
        n += 1
    return n


def dot_plan(pairs, bounds):
    """Static lane plan for a pair list: per-lane (bias, limb count, span),
    the dot's column layout, and the w/limb column assignments. Computed from
    ONE batch's pair objects; the layout is positional, so the same plan
    serves every equally-structured batch (the per-block fused kernel)."""
    import jax.numpy as jnp

    L = len(pairs)
    bounds = list(bounds) if bounds is not None else [None] * L

    # lane plan: per pair a bias (proven lo) and limb count from the range
    plans = []
    for (v, _w), b in zip(pairs, bounds):
        if b is not None:
            lo, hi = int(b[0]), int(b[1])
        else:
            # dtype envelope — callers must prove bounds for int64 lanes
            # (v - lo must not wrap int64)
            info = jnp.iinfo(v.dtype)
            lo, hi = int(info.min), int(info.max)
            if hi - lo >= (1 << 62):
                raise ValueError("unbounded int64 lane: prove bounds before the dot path")
        plans.append((lo, _limbs_needed(max(hi - lo, 0)), max(hi - lo, 0)))

    # column layout: [w0, w1, ...] shared per distinct weight lane id, then
    # per pair its limb columns. Dedup: pairs sharing (value id, weight id)
    # read the same limb columns; zero-span lanes (COUNT) read only w.
    col_specs = [("occ",)]  # bucket occupancy: the biased-limb corrector
    w_col_of = []
    w_ids: dict[int, int] = {}
    for i, (_v, w) in enumerate(pairs):
        wid = id(w)
        if wid not in w_ids:
            w_ids[wid] = len(col_specs)
            col_specs.append(("w", i))
        w_col_of.append(w_ids[wid])
    limb_cols_of: list[list[int]] = []
    lane_ids: dict[tuple, int] = {}
    for i, (lo, nl, _span) in enumerate(plans):
        if plans[i][1] == 1 and bounds[i] is not None and int(bounds[i][0]) == int(bounds[i][1]):
            limb_cols_of.append([])  # constant lane: sum = cnt * lo, no limbs
            continue
        key = (id(pairs[i][0]), id(pairs[i][1]), lo, nl)
        dup = lane_ids.get(key)
        if dup is not None:
            limb_cols_of.append(limb_cols_of[dup])
            continue
        lane_ids[key] = i
        cols_i = []
        for k in range(nl):
            cols_i.append(len(col_specs))
            col_specs.append(("limb", i, k))
        limb_cols_of.append(cols_i)
    return (plans, col_specs, w_col_of, limb_cols_of, len(col_specs))


@_scoped
def dot_acc(seg, pairs, B: int, n: int, plan, acc=None):
    """Accumulate one batch's grouped int8 matmuls into ``acc`` (B, C) int64.
    Chunks internally so the int32 accumulator never overflows."""
    import jax
    import jax.numpy as jnp

    plans, col_specs, _w_col_of, _limb_cols_of, C = plan

    def build_cols(sl):
        cols = []
        shifted = {}
        for spec in col_specs:
            if spec[0] == "occ":
                cols.append(jnp.ones(sl.stop - sl.start, dtype=jnp.int8))
            elif spec[0] == "w":
                cols.append(pairs[spec[1]][1][sl].astype(jnp.int8))
            else:
                _, i, k = spec
                if i not in shifted:
                    v, w = pairs[i]
                    lo, nl, span = plans[i]
                    if (
                        v.dtype == jnp.int32
                        and span < (1 << 31)
                        and -(1 << 31) <= lo
                    ):
                        # narrow input lane + proven span: bias-subtract AND
                        # limb shifts all run NATIVE int32 — no emulated-pair
                        # int64 op ever touches this lane
                        vb = jnp.where(w[sl], v[sl] - jnp.int32(lo), 0)
                    else:
                        vb = jnp.where(w[sl], v[sl].astype(jnp.int64) - lo, 0)
                        if span < (1 << 31):
                            # span proven < 2^31: the limb shifts run in
                            # NATIVE int32 instead of emulated-pair int64
                            vb = vb.astype(jnp.int32)
                    shifted[i] = vb
                cols.append(
                    (((shifted[i] >> (_LIMB_BITS * k)) & _LIMB_MASK) - _LIMB_BIAS).astype(jnp.int8)
                )
        # (C, chunk): the row dimension is the MINOR axis on both operands —
        # a (chunk, C) layout would pad C up to the 128-lane vreg width and
        # turn ~150MB of limb bytes into >1GB of HBM traffic per chunk
        return jnp.stack(cols, axis=0)

    if acc is None:
        acc = jnp.zeros((B, C), dtype=jnp.int64)
    bidx = jnp.arange(B, dtype=jnp.int32)
    # grouping sets: ``seg`` may be a LIST of (seg_lane, lo, hi) windows —
    # each row then belongs to ONE bucket per window and the "one-hot"
    # becomes (n_sets)-hot, computing every grouping set in the SAME matmul
    # with zero row replication (the Expand fusion;
    # ref: cophandler/mpp_exec.go:422-466 replicates rows instead)
    windows = seg if isinstance(seg, list) else [(seg, 0, B)]
    for start in range(0, n, _CHUNK):
        sl = slice(start, min(start + _CHUNK, n))
        hot = [
            (s[sl][None, :] == bidx[lo:hi, None]).astype(jnp.int8)
            for s, lo, hi in windows
        ]
        onehot = hot[0] if len(hot) == 1 else jnp.concatenate(hot, axis=0)
        limbs = build_cols(sl)
        part = jax.lax.dot_general(
            onehot, limbs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
        )
        acc = acc + part.astype(jnp.int64)
    return acc


@_scoped
def dot_recombine(acc, plan, L: int, B: int):
    """(B, C) limb accumulator → exact (counts, sums), both (B, L) int64."""
    import jax.numpy as jnp

    plans, _col_specs, w_col_of, limb_cols_of, _C = plan
    occ = acc[:, 0]  # rows per bucket (w-independent)
    counts, sums = [], []
    for i in range(L):
        cnt = acc[:, w_col_of[i]]
        lo, nl, _span = plans[i]
        s = jnp.zeros(B, dtype=jnp.int64)
        for k, cidx in enumerate(limb_cols_of[i]):
            # un-bias: every bucket-routed row contributed (limb - 128) to
            # this column (w=0 rows carry value 0, still biased), so the
            # exact per-bucket correction is occupancy * 128
            s = s + ((acc[:, cidx] + occ * _LIMB_BIAS) << (_LIMB_BITS * k))
        sums.append(s + cnt * lo)
        counts.append(cnt)
    return jnp.stack(counts, axis=1), jnp.stack(sums, axis=1)


def grouped_sums_dot(seg, pairs, B: int, n: int, bounds=None):
    """Exact grouped COUNT/SUM via one int8 MXU matmul per row chunk.

    seg    : (n,) int32 — bucket per row in [0, B); dead rows >= B.
    pairs  : [(vals int lane, w bool lane)] — w gates each row's contribution.
    bounds : per pair (lo, hi) proven value bounds or None (int64 envelope).
    → (counts int64 (B, L), sums int64 (B, L)).
    """
    plan = dot_plan(pairs, bounds)
    acc = dot_acc(seg, pairs, B, n, plan)
    return dot_recombine(acc, plan, len(pairs), B)
