"""Grouped COUNT/SUM as one XLA int8 matmul on the MXU.

This path hands XLA ONE ``dot_general(onehot_i8, bytes_i8) -> int32`` per
≤8M-row chunk — the native int8 systolic-array mode — and recombines bytes
exactly in int64:

- values bias to non-negative by their proven lower bound (binder bounds, or
  the int32 dtype envelope) and travel as 32-bit WORDS: one word a lane whose
  proven RANGE fits 32 bits (a dict code, a DECIMAL(12,2) price), two past
  it — the "narrow the compute lanes" discipline (ref: per-width column
  handling, pkg/util/chunk/column.go:74). Each word is split into its 4
  bytes, each re-biased by -128 into [-128, 127] so full bytes ride SIGNED
  int8; every weight word's byte 1 (always 0) counts the rows a bucket got,
  which undoes the -128 bias exactly at recombination.
- the dot's second operand is laid out as the MXU reads it: rows as blocks
  of 128, each block's columns side by side — ``(rows / 128, 4 x words,
  128)`` — and a word's 4 byte columns fill one 32-bit sublane of int8's
  packed tile, so it is written whole by one fusion a word, never a byte
  column at a time and then stacked (that relayout cost more than the
  equality-mask reduce it replaced: PR 38, PERF.md).
- int32 accumulation is exact while chunk_rows * 128 < 2^31 → chunks of 2^23
  rows (128 * 2^23 = 2^30), summed across chunks in int64. No f32 rounding
  anywhere.
- COUNT rides a shared 0/1 weight word per distinct validity mask; pairs
  sharing (value, weight) share words, and constant lanes (COUNT's zeros)
  carry none.

Exact for any |value| < 2^62 (int64 bias); lanes with no usable bound get
the full two-word int64 split, still exact but wider.
"""

from __future__ import annotations

import functools

_CHUNK = 1 << 23  # int32 accumulator headroom: 255 * 2^23 < 2^31
_LIMB_BITS = 8  # biased to [-128, 127] so full bytes ride SIGNED int8
_LIMB_MASK = (1 << _LIMB_BITS) - 1
_LIMB_BIAS = 1 << (_LIMB_BITS - 1)
_WORD_LIMBS = 4  # bytes a 32-bit word carries: one packed sublane of an int8 tile
_LANES = 128  # a vreg's minor width: rows go to the dot as (rows / 128, 128)
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
    the dot's words — ``("w", pair)`` one a distinct weight lane, then
    ``("v", pair, j)`` the j-th 32 bits of a distinct value lane — and which
    words each pair reads. Word p's byte t is column 4p + t of the dot.
    Computed from ONE batch's pair objects; the layout is positional, so the
    same plan serves every equally-structured batch (the per-block fused
    kernel)."""
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

    # words: [w0, w1, ...] one per distinct weight lane id, then per lane its
    # value words. Dedup: pairs sharing (value id, weight id) read the same
    # words; zero-span lanes (COUNT) read only their weight's.
    words: list[tuple] = []
    w_word_of = []
    w_ids: dict[int, int] = {}
    for i, (_v, w) in enumerate(pairs):
        if id(w) not in w_ids:
            w_ids[id(w)] = len(words)
            words.append(("w", i))
        w_word_of.append(w_ids[id(w)])
    v_words_of: list[list[int]] = []
    lane_ids: dict[tuple, int] = {}
    for i, (lo, nl, _span) in enumerate(plans):
        if nl == 1 and bounds[i] is not None and int(bounds[i][0]) == int(bounds[i][1]):
            v_words_of.append([])  # constant lane: sum = cnt * lo, no words
            continue
        key = (id(pairs[i][0]), id(pairs[i][1]), lo, nl)
        dup = lane_ids.get(key)
        if dup is not None:
            v_words_of.append(v_words_of[dup])
            continue
        lane_ids[key] = i
        nw = -(-nl // _WORD_LIMBS)
        v_words_of.append(list(range(len(words), len(words) + nw)))
        words += [("v", i, j) for j in range(nw)]
    return (plans, words, w_word_of, v_words_of, _WORD_LIMBS * len(words))


@_scoped
def dot_acc(seg, pairs, B: int, n: int, plan, acc=None):
    """Accumulate one batch's grouped int8 matmuls into ``acc`` (B, C) int64.
    Chunks internally so the int32 accumulator never overflows."""
    import jax
    import jax.numpy as jnp

    plans, words, _w_word_of, _v_words_of, C = plan
    if acc is None:
        acc = jnp.zeros((B, C), dtype=jnp.int64)
    bidx = jnp.arange(B, dtype=jnp.int32)
    shifts = (_LIMB_BITS * jnp.arange(_WORD_LIMBS, dtype=jnp.int32)).reshape(1, _WORD_LIMBS, 1)
    # grouping sets: ``seg`` may be a LIST of (seg_lane, lo, hi) windows —
    # each row then belongs to ONE bucket per window and the "one-hot"
    # becomes (n_sets)-hot, computing every grouping set in the SAME matmul
    # with zero row replication (the Expand fusion;
    # ref: cophandler/mpp_exec.go:422-466 replicates rows instead)
    windows = seg if isinstance(seg, list) else [(seg, 0, B)]
    for start in range(0, n, _CHUNK):
        sl = slice(start, min(start + _CHUNK, n))
        rows = sl.stop - sl.start
        blk = (rows // _LANES, _LANES) if rows % _LANES == 0 else (1, rows)
        hot = [
            (s[sl].reshape(blk)[None] == bidx[lo:hi, None, None]).astype(jnp.int8)
            for s, lo, hi in windows
        ]
        onehot = hot[0] if len(hot) == 1 else jnp.concatenate(hot, axis=0)
        biased: dict = {}
        cols = []
        for spec in words:
            if spec[0] == "w":
                word = pairs[spec[1]][1][sl].astype(jnp.int32)
            else:
                _, i, j = spec
                if i not in biased:
                    v, w = pairs[i]
                    lo, _nl, span = plans[i]
                    if v.dtype == jnp.int32 and span < (1 << 31) and -(1 << 31) <= lo:
                        # narrow input lane + proven span: the bias-subtract
                        # runs NATIVE int32 — no emulated-pair int64 op ever
                        # touches this lane
                        biased[i] = jnp.where(w[sl], v[sl] - jnp.int32(lo), 0)
                    else:
                        vb = jnp.where(w[sl], v[sl].astype(jnp.int64) - lo, 0)
                        # span proven < 2^31: one native int32 word
                        biased[i] = vb.astype(jnp.int32) if span < (1 << 31) else vb
                vb = biased[i]
                word = vb if vb.dtype == jnp.int32 else jax.lax.bitcast_convert_type(
                    (vb >> (32 * j)).astype(jnp.uint32), jnp.int32
                )
            # the word's 4 bytes, each - 128: (rows / 128, 4, 128) int8
            cols.append(
                (((word.reshape(blk[0], 1, blk[1]) >> shifts) & _LIMB_MASK) - _LIMB_BIAS).astype(jnp.int8)
            )
        # (rows / 128, C, 128): each 128-row block's C columns side by side
        limbs = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
        part = jax.lax.dot_general(
            onehot, limbs, (((1, 2), (0, 2)), ((), ())), preferred_element_type=jnp.int32
        )
        acc = acc + part.astype(jnp.int64)
    return acc


@_scoped
def dot_recombine(acc, plan, L: int, B: int):
    """(B, C) byte accumulator → exact (counts, sums), both (B, L) int64.
    Every row routed to a bucket added (byte - 128) to each of its columns:
    a weight word's byte 1 is 0 on every row, so its column reads -128 x the
    rows routed (``occ``), and its byte 0 adds the weight on top."""
    import jax.numpy as jnp

    plans, _words, w_word_of, v_words_of, _C = plan
    col = lambda p, t: acc[:, _WORD_LIMBS * p + t]  # noqa: E731
    occ = -col(w_word_of[0], 1) >> (_LIMB_BITS - 1)  # exact: a multiple of 128
    counts, sums = [], []
    for i in range(L):
        cnt = col(w_word_of[i], 0) + occ * _LIMB_BIAS
        lo, _nl, _span = plans[i]
        s = jnp.zeros(B, dtype=jnp.int64)
        for j, p in enumerate(v_words_of[i]):
            for t in range(_WORD_LIMBS):
                s = s + ((col(p, t) + occ * _LIMB_BIAS) << (32 * j + _LIMB_BITS * t))
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
