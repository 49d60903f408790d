"""MPP fragments as shard_map programs: the distributed query step.

The canonical two-fragment MPP plan (ref: fragment.go + mpp_exec.go):

  Fragment 1 (per shard): Scan → Selection → PartialAgg
  ── Hash exchange on group keys (all_to_all) ──
  Fragment 2 (per shard): merge partials for owned key range
  ── PassThrough exchange (all_gather) ──
  root: finalize

Everything below runs inside ONE jitted shard_map over mesh axis ``dp`` —
fragment boundaries become collectives, not gRPC streams. Group capacities
are static (padded); hash-bucket capacity equals the per-shard group cap, so
the exchange can never overflow.
"""

from __future__ import annotations

import functools
import re
from dataclasses import dataclass, replace
from typing import Callable, Sequence

import numpy as np


@dataclass
class DistAggSpec:
    """A distributed group-by/aggregate over sharded columns.

    ``n_keys`` leading input columns are the group keys (int lanes);
    ``sums``: indices of value columns to SUM; COUNT(*) always included.
    ``group_cap``: static max distinct groups per shard (and per exchange
    bucket). ``key_bounds``: per data key (lo, hi) value bounds or None —
    bounded keys pack into ONE narrow sort lane (int32 when the domain
    fits), replacing the multi-lane stable-argsort chain with a single
    native sort."""

    n_keys: int
    sums: Sequence[int]
    group_cap: int = 256
    key_bounds: tuple = ()
    # per ``sums`` PAIR (data+valid): "sum" | "min" | "max" — how the value
    # lane reduces within a group (and re-reduces across the exchange)
    val_kinds: tuple = ()
    # distinct aggregates (ref: TiFlash two-phase distinct agg): ``n_dkeys``
    # input lanes AFTER the group keys hold the (shared) distinct argument
    # as a (data, valid) pair. Stage 1 groups by (g, x) — deduping x within
    # g — the exchange routes by g only, and a final per-g reduction counts/
    # sums the surviving distinct slots. ``distinct_mask``: per agg-with-arg
    # (output order), True when its (value, count) output pair reads the
    # distinct slot reduction instead of a plain value lane.
    n_dkeys: int = 0
    distinct_mask: tuple = ()
    # index of the chain's join whose UNIQUE build row determines every group
    # key, the join key among them (Q3: GROUP BY l_orderkey, o_orderdate,
    # o_shippriority under ``l_orderkey = o_orderkey``): a group IS a build
    # slot, so the partial aggregate reduces by slot (:func:`_slot_partial`)
    # where the fragment took the direct-address lookup for that join
    slot_join: int | None = None
    # every group's rows lie on ONE shard already (the slot join repartitioned
    # by key), or on so few that the root's merge of partials is the merge
    # (rows in place, a group astride a shard boundary): no exchange of group
    # slots and no second reduction on the mesh. The gather's decision
    placed: bool = False


def _pack_keys(jnp, keys, bounds):
    """Collision-FREE packing of bounded key components into one sort lane,
    int32 when the domain fits — native TPU sorts instead of x64-emulated
    pair sorts (the dominant MPP cost at millions of rows). Returns
    (lane, n_codes) or None when any component is unbounded/out-of-budget;
    codes occupy [0, n_codes), leaving headroom for dead-row sentinels."""
    if not bounds or any(b is None for b in bounds):
        return None
    spans = []
    total = 1
    for lo, hi in bounds:
        s = int(hi) - int(lo) + 1
        if s < 1:
            s = 1
        spans.append(s)
        total *= s
        if total > (1 << 60):
            return None
    acc = None
    for (lo, _hi), k, s in zip(bounds, keys, spans):
        code = jnp.clip(k.astype(jnp.int64) - int(lo), 0, s - 1)
        acc = code if acc is None else acc * s + code
    if total <= (1 << 30):
        return acc.astype(jnp.int32), total
    return acc, total


def _segment_partial(jnp, keys, vals, mask, cap, bounds=(), val_kinds=()):
    """Sort-based grouped partial agg on one shard (same algorithm as
    ops/dag_kernel.py — key-exact, no hash collisions). Returns
    (keys, sums, counts, overflow): ``overflow`` counts distinct groups
    beyond ``cap`` — results are invalid unless it is zero, so callers
    surface it and retry with a bigger cap."""
    n = keys[0].shape[0]
    packed = _pack_keys(jnp, keys, bounds)
    if packed is not None:
        lane, n_codes = packed
        dead = n_codes if n_codes < (1 << 30) else jnp.int64(n_codes)
        perm = jnp.argsort(jnp.where(mask, lane, dead))
        sm = mask[perm]
        ls = lane[perm]
        first = jnp.arange(n) == 0
        diff = jnp.concatenate([jnp.zeros(1, bool), ls[1:] != ls[:-1]])
    else:
        lanes = [~mask] + list(keys)
        perm = jnp.argsort(lanes[-1], stable=True)
        for lane in reversed(lanes[:-1]):
            perm = perm[jnp.argsort(lane[perm], stable=True)]
        sm = mask[perm]
        first = jnp.arange(n) == 0
        diff = jnp.zeros(n, dtype=bool)
        for k in keys:
            ks = k[perm]
            diff = diff | jnp.concatenate([jnp.zeros(1, bool), ks[1:] != ks[:-1]])
    boundary = sm & (first | diff)
    overflow = jnp.maximum(boundary.sum() - cap, 0)
    seg = jnp.clip(jnp.cumsum(boundary) - 1, 0, None)
    # scatter-free segmented reduction (TPU scatter serializes — same policy
    # as ops/dag_kernel.py): cumsum deltas at searchsorted boundaries
    ks = jnp.arange(cap)
    starts = jnp.searchsorted(seg, ks)
    starts_c = jnp.clip(starts, 0, n - 1)
    ends_c = jnp.clip(jnp.searchsorted(seg, ks, side="right") - 1, 0, n - 1)
    slot_live = ks < boundary.sum()

    def _csum_delta(x):
        cs = jnp.cumsum(x)
        lo = jnp.where(starts_c > 0, cs[jnp.maximum(starts_c - 1, 0)], 0)
        return jnp.where(slot_live, cs[ends_c] - lo, 0)

    cnt = _csum_delta(sm.astype(jnp.int64))
    out_keys = []
    for k in keys:
        out_keys.append(jnp.where(slot_live, k[perm][starts_c], 0))
    out_sums = []
    seg_sorted: dict = {}  # one (seg, value)-sort serves both MIN and MAX
    for vi, v in enumerate(vals):
        kind = val_kinds[vi] if vi < len(val_kinds) else "sum"
        vs = v[perm]
        if kind in ("min", "max"):
            # grouped extreme by order statistics (see seg_value_sorted):
            # dead rows sink under a +max sentinel, so min = the group's
            # start slot, max = start + live_count - 1
            from tidb_tpu.ops.window_core import seg_value_sorted

            vs2 = seg_sorted.get(id(v))
            if vs2 is None:
                if jnp.issubdtype(vs.dtype, jnp.floating):
                    sent = jnp.inf
                else:
                    sent = jnp.iinfo(vs.dtype).max
                vs2 = seg_value_sorted(jnp, jnp.where(sm, vs, sent), seg)
                seg_sorted[id(v)] = vs2
            if kind == "min":
                out_sums.append(jnp.where(slot_live, vs2[starts_c], 0))
            else:
                last_live = jnp.clip(starts_c + cnt - 1, 0, n - 1)
                out_sums.append(jnp.where(slot_live, vs2[last_live], 0))
        else:
            out_sums.append(_csum_delta(jnp.where(sm, vs, 0)))
    return out_keys, out_sums, cnt, overflow  # slot i valid iff cnt[i] > 0


def build_dist_agg(mesh, spec: DistAggSpec, selection: Callable | None = None):
    """→ fn(*sharded_cols) executing the two-fragment MPP agg (the no-join
    specialization of :func:`build_dist_join_agg`).

    Input: one array per column, sharded along dp (global length =
    ndev * local_n). Output (replicated): (keys..., sums..., count, total)
    arrays of length ndev * group_cap; slots with count==0 are padding.
    Group-cap overflow is never silent: the runner retries with a larger cap
    until the result is exact (coprocessor grow-on-demand paging spirit).
    """
    from dataclasses import replace

    import numpy as np

    def run(*cols):
        cap = spec.group_cap
        while True:
            fn = build_dist_join_agg(
                mesh,
                None,
                replace(spec, group_cap=cap),
                n_left=len(cols),
                left_selection=selection,
            )
            import jax

            outs = jax.device_get(fn(*cols))  # one batched transfer
            if int(outs[-1]) == 0:  # overflow lane
                return outs[:-2]  # drop (dropped, overflow) — both zero
            cap *= 4

    return run


@dataclass
class DistJoinSpec:
    """A distributed equi-join between two sharded sides (ref: the MPP
    shuffle/broadcast hash join, mpp_exec.go join + exchange senders).

    ``left_keys``/``right_keys``: column indices of the join keys (int
    lanes) — left indices address the accumulated probe-side lane layout,
    right indices the build reader's local lanes.
    ``exchange``: "hash" (both sides shuffled by key owner — all_to_all) or
    "broadcast" (right side replicated — all_gather), or "local" (the
    gather saw that each shard's probe rows span a narrow key range and that
    the build side lies in key order: the probe stays, a shard keeps its own
    build rows and is sent the few of every other shard's that fall in its
    range — ``halo_cap`` rows a pair at the most — and builds its table over
    ``local_codes`` keys from its range's low end, operand ``range_operand``
    = [ndev, 2] packed key codes, the (low, high) of each shard's probe rows;
    ``right_live`` = the build block's lane that says which rows exist).
    ``row_cap``: static per-destination receive capacity for hash exchange
    (overflow is reported, never silently dropped on the result path);
    ``left_row_cap``/``right_row_cap`` size the two sides independently —
    a small build side must not inherit the probe side's capacity.
    ``unique``: build side proven unique on the key (PK/unique index) →
    match-gather probe, no expansion. Otherwise the join expands each probe
    row to its match count, bounded by ``out_cap`` (overflow retried)."""

    left_keys: Sequence[int]
    right_keys: Sequence[int]
    # inner | left | semi | anti (ref: mpp_exec.go join types; outer fills
    # NULL build lanes, semi/anti filter the probe and append nothing)
    kind: str = "inner"
    exchange: str = "hash"  # hash | broadcast | local
    row_cap: int = 4096
    left_row_cap: int | None = None
    right_row_cap: int | None = None
    unique: bool = True
    out_cap: int = 8192
    # validity lanes of the join keys: inner-join keys must be non-NULL to
    # match (NULL data slots hold 0, which would otherwise equal a real 0)
    left_key_valid: Sequence[int] = ()
    right_key_valid: Sequence[int] = ()
    # JOINT (both sides) per-key (lo, hi) value bounds or () — bounded keys
    # pack into one narrow exact lane (int32 when the domain fits): native
    # sorts, and component re-verification becomes belt-and-braces
    key_bounds: tuple = ()
    # a snowflake arm: folded into the build side of the join before it,
    # first (the gather decides: a unique inner join whose probe keys all lie
    # in that build side, ``PhysMPPGather.arm_folds``)
    arm: bool = False
    halo_cap: int = 0
    local_codes: int = 0
    range_operand: int = -1
    right_live: int = -1


def _combine_keys(jnp, keys):
    """Mix multiple int64 key lanes into one ordering/bucketing lane.
    Components are verified exactly after matching, so a (cosmically rare)
    mix collision can only cost a missed adjacency, never a false match."""
    h = keys[0].astype(jnp.int64)
    for k in keys[1:]:
        # 0x9E3779B97F4A7C15 as signed int64 (two's complement)
        h = h * jnp.int64(-7046029254386353131) + k.astype(jnp.int64)
    return h


def _exact_pair_lanes(jnp, lcomps, rcomps):
    """Collision-FREE single-lane encoding of a multi-component join key
    across BOTH sides — the packed-exact fallback when no static value
    bounds exist (floats, unbounded domains): per component, dense ranks
    over the union of the two sides' local values (two argsorts + a
    cumsum), folded pairwise with re-compression so the accumulator never
    exceeds span² < 2⁶² regardless of component count. Tuple equality ⇔
    code equality, so count-based existence joins (semi/anti) and left-outer
    match counts are EXACT — no mixed-hash collision can duplicate or drop a
    row. Returns (lcode, rcode, span): codes lie in [0, span), with span =
    n_left + n_right + 1 a static Python int for dead-row sentinels."""
    nl = lcomps[0].shape[0]
    span = nl + rcomps[0].shape[0] + 1

    def ranks(lv, rv):
        comb = jnp.concatenate([lv, rv])
        order = jnp.argsort(comb)
        sv = comb[order]
        newg = jnp.concatenate(
            [jnp.zeros(1, jnp.int64), (sv[1:] != sv[:-1]).astype(jnp.int64)]
        )
        rk = jnp.cumsum(newg)
        inv = jnp.argsort(order)
        r = rk[inv]
        return r[:nl], r[nl:]

    accl, accr = ranks(lcomps[0], rcomps[0])
    for lc, rc in zip(lcomps[1:], rcomps[1:]):
        rl, rr = ranks(lc, rc)
        accl, accr = ranks(accl * span + rl, accr * span + rr)
    return accl, accr, span


EXCHANGE_SCOPE = "mpp.exchange"
EXCHANGE_KINDS = ("hash", "broadcast", "local", "groups")
_COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter", "collective-permute")
_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


class _Exchange:
    """The fragment program's collectives, every one under the named scopes
    ``mpp.exchange`` / ``<kind>`` (``hash``: both sides of a join by key
    owner, ``broadcast``: a build side to every shard, ``local``: a build
    side's slivers, ``groups``: group slots to their owners, and the
    replicated result): what :func:`compiled_exchange_bytes` tells them by."""

    def __init__(self, jax, ndev: int):
        self.jax, self.ndev = jax, ndev

    def _scope(self, kind: str):
        return self.jax.named_scope(f"{EXCHANGE_SCOPE}/{kind}")

    def all_to_all(self, kind: str, buf):
        """``buf`` [ndev, cap]: row d goes to shard d; → [ndev * cap], what every shard sent here."""
        with self._scope(kind):
            return self.jax.lax.all_to_all(buf, "dp", split_axis=0, concat_axis=0, tiled=False).reshape(-1)

    def all_gather(self, kind: str, x):
        with self._scope(kind):
            return self.jax.lax.all_gather(x, "dp").reshape((-1,) + x.shape[1:])

    def psum(self, kind: str, x):
        with self._scope(kind):
            return self.jax.lax.psum(x, "dp")


def compiled_collectives(text: str) -> list[tuple[str, int, str]]:
    """(operation, result bytes, op_name) of every collective in a compiled
    program's HLO text. An asynchronous pair counts once, at its ``-start``,
    whose result repeats the operand first: the last shape is the buffer."""
    out = []
    for m in re.finditer(r"^\s*\S+ = (\(.*?\)|\S+) ((?:%s)(?:-start)?)\(.*$" % "|".join(_COLLECTIVES), text, re.M):
        shapes = re.findall(r"(\w+)\[([\d,]*)\]", m.group(1))
        if m.group(2).endswith("-start") and len(shapes) > 1:
            shapes = shapes[len(shapes) // 2 :]
        nbytes = 0
        for dtype, dims in shapes:
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            nbytes += n * _HLO_BYTES[dtype]
        name = re.search(r'op_name="([^"]*)"', m.group(0))
        out.append((m.group(2), nbytes, name.group(1) if name else ""))
    return out


def compiled_exchange_bytes(text: str, ndev: int) -> dict:
    """kind -> bytes a compiled fragment program moves between chips a run:
    over its collectives, the result buffer x (ndev - 1): what leaves a chip
    for another (the buffer's share that is not its own, (ndev - 1) / ndev)
    summed over the chips. Padding counts; a lane the compiler dropped does
    not. The kind is read off the scope the collective was traced under
    (:class:`_Exchange`; one that lies under none counts as ``groups``)."""
    out: dict = {}
    for _op, nbytes, name in compiled_collectives(text):
        _, _, tail = name.partition(EXCHANGE_SCOPE + "/")
        kind = tail.split("/", 1)[0]
        kind = kind if kind in EXCHANGE_KINDS else "groups"
        out[kind] = out.get(kind, 0) + nbytes * (ndev - 1)
    return out


def _send_runs(xc, jnp, kind, lanes, first, last, cap, to=None, rows=None):
    """Send destination d the rows [first[d], last[d]) of every lane, ``cap``
    of them at the most: a destination's rows are one contiguous run, so its
    send buffer is a slice, never a gather. ``to`` [ndev] / ``rows`` [n]
    narrow it to some destinations / some rows of a run. Returns (what every
    shard sent here, lane by lane; which of it holds a row; the rows sent)."""
    jax, n = xc.jax, lanes[0].shape[0]
    # a slice may not run off the end: it starts early instead, and what it
    # then holds of the run before is masked
    at = jnp.minimum(first, n - cap)
    pos = at[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    ok = (pos >= first[:, None]) & (pos < last[:, None])

    def blocks(x):  # [ndev, cap]: cap rows of x from each start
        return jnp.stack([jax.lax.dynamic_slice(x, (at[d],), (cap,)) for d in range(xc.ndev)])

    if to is not None:
        ok = ok & to[:, None]
    if rows is not None:
        ok = ok & blocks(rows)
    got = [xc.all_to_all(kind, jnp.where(ok, blocks(x), jnp.zeros((), x.dtype))) for x in lanes]
    return got, xc.all_to_all(kind, ok), ok.sum()


def _route_rows(xc, jnp, arrays, valid, owner, cap, kind="hash"):
    """Hash-exchange rows to owner shards (all_to_all with static per-dest
    capacity). Returns (received arrays, received valid, locally dropped).

    The lanes ride ONE sort by destination; a destination's rows are then a
    contiguous run (:func:`_send_runs`): no scatter (TPU lowers large
    scatters to a serialized loop) and no gather of a send buffer's size per
    lane (element by element, ~116M a second on a v5e). A lane nothing reads
    afterwards leaves the sort too."""
    jax, ndev = xc.jax, xc.ndev
    if ndev == 1:
        # single-shard mesh: every row is already home — the exchange is the
        # identity and padding to ``cap`` would only add work
        return list(arrays), valid, jnp.int64(0)
    cap = min(cap, valid.shape[0])  # one shard cannot send a destination more rows than it holds
    dest = jnp.where(valid, owner, ndev).astype(jnp.int32)
    flags = [a.dtype == jnp.bool_ for a in arrays]
    so, *lanes = jax.lax.sort((dest, *(a.astype(jnp.int8) if f else a for a, f in zip(arrays, flags))), num_keys=1, is_stable=True)
    # per-destination block starts: ndev+1 searchsorted queries, not n
    starts = jnp.searchsorted(so, jnp.arange(ndev + 1, dtype=jnp.int32)).astype(jnp.int32)
    dropped = jnp.maximum(starts[1:] - starts[:-1] - cap, 0).sum().astype(jnp.int64)
    got, ok, _ = _send_runs(xc, jnp, kind, lanes, starts[:-1], starts[1:], cap)
    return [rx.astype(bool) if f else rx for rx, f in zip(got, flags)], ok, dropped


def _send_slivers(xc, jnp, rcols, rvalid, rkey, live, rng, cap):
    """The ``local`` exchange: every shard keeps its build rows and appends
    what the others send it: of each other shard's build rows, those whose
    key lies in the range this shard's probe rows span (``rng`` [ndev, 2],
    low and high, a shard without rows high < low). ``rkey`` over the rows
    that exist (``live``) does not decrease (the gather saw it), so what a
    destination needs is one contiguous run; more than ``cap`` rows of it are
    counted as dropped and the gather grows the cap. Returns (lanes, valid,
    dropped, rows sent)."""
    jax, ndev = xc.jax, xc.ndev
    cap = min(cap, rvalid.shape[0])
    sk = jnp.where(live, rkey, jnp.iinfo(rkey.dtype).max)
    lo, hi = rng[:, 0].astype(rkey.dtype), rng[:, 1].astype(rkey.dtype)
    first = jnp.searchsorted(sk, lo, side="left").astype(jnp.int32)
    last = jnp.searchsorted(sk, hi, side="right").astype(jnp.int32)
    others = (jnp.arange(ndev) != jax.lax.axis_index("dp")) & (hi >= lo)
    dropped = jnp.where(others, jnp.maximum(last - first - cap, 0), 0).sum().astype(jnp.int64)
    got, ok, sent = _send_runs(xc, jnp, "local", rcols, first, last, cap, to=others, rows=rvalid)
    return [jnp.concatenate([c, rx]) for c, rx in zip(rcols, got)], jnp.concatenate([rvalid, ok]), dropped, sent


def _sorted_lookup(jnp, rk_s, lkey):
    """Index of the last element of sorted ``rk_s`` that is <= each lkey,
    via sort-merge instead of searchsorted: TPU lowers many-query binary
    search to ~18 serialized dynamic-gather rounds (~1.2s for 2M probes,
    measured); two argsorts + a cumsum + gathers do the same in ~50ms."""
    m = rk_s.shape[0]
    comb = jnp.concatenate([rk_s, lkey])
    perm = jnp.argsort(comb, stable=True)  # equal keys: right rows first
    inv = jnp.argsort(perm)  # combined index → sorted position
    cum_right = jnp.cumsum(jnp.where(perm < m, 1, 0))
    pos = inv[m:]
    return jnp.clip(cum_right[pos] - 1, 0, m - 1)


# the widest key domain a direct-address table is built over: int32 slots,
# 512 MiB, 3% of one v5e's HBM. A join whose packed key domain is wider, or
# whose keys carry no bounds, keeps the sort-merge lookup.
DIRECT_DOMAIN_MAX = 1 << 27


def _direct_table(jnp, rkey, rvalid, n_codes):
    """The direct-address table over a key's domain: each code's build row,
    -1 where the build side holds none. One scatter of the build side's row
    numbers, no sort. Keys are packed codes in [0, n_codes) (:func:`_pack_keys`
    over the JOINT bounds of both sides, so equal codes are equal keys); the
    build side holds a code at most once. On one v5e (builder's chip run,
    PR 29): 25 ms to scatter 4M rows into 12M slots."""
    return jnp.full(n_codes, -1, jnp.int32).at[jnp.where(rvalid, rkey, n_codes)].set(
        jnp.arange(rkey.shape[0], dtype=jnp.int32), mode="drop"
    )


def _direct_lookup(jnp, lkey, lvalid, rkey, rvalid, n_codes):
    """The build row of each probe row, -1 where it has none: one gather from
    :func:`_direct_table` by the probe keys, element by element (145 ms for
    16M int32 on one v5e, PR 29, sorted or not; the sort-merge lookup sorts
    build + probe concatenated, twice). What a probe row carries where build
    lanes are read at the probe's row count; a join read only for "did the
    row match" and "which group" asks :func:`_probe_match` instead."""
    return _table_rows(jnp, _direct_table(jnp, rkey, rvalid, n_codes), lkey, lvalid)


def _table_rows(jnp, table, lkey, lvalid):
    """``table`` at each valid row's key code, one element a row; -1 for the others."""
    return jnp.where(lvalid, table[jnp.where(lvalid, lkey, 0)], -1)


# the blocked probe: probe rows a block, and the codes a row of the table's
# presence bitmap holds (128 words, the chip's lanes, of 32 codes each). A
# block reads the two rows from its least live code's on: 4,097 to 8,192 codes,
# where 128 rows of a fact table in its dimension's key order span 128 keys
# (TPC-H's order keys, 8 of every 32 values: at most ~540 codes)
PROBE_BLOCK = 128
_ROW_CODES = 128 * 32


def _probe_match(jax, jnp, table, lkey, live):
    """Which probe rows have a build row in ``table`` (:func:`_direct_table`),
    bit for bit ``_direct_lookup(...) >= 0``, and how it was found. Probe rows
    in key order (`lineitem` by `l_orderkey`) are answered by BLOCKS: the live
    codes of ``PROBE_BLOCK`` consecutive rows lie in two adjacent rows of the
    table's presence bits, packed 32 codes a word and 128 words a row, so a
    block fetches two whole rows (a gather of rows, 2.3 ms for 131,072 blocks
    on one v5e where the 16.7M single elements take 145, PR 34) and every
    probe row picks its bit out of them. Where any block's live codes span
    more (an unsorted probe: `orders` by `o_custkey`), the whole lane takes
    the element gather. Which, the program sees in the data, as
    :func:`_slot_partial` does. Dead rows (padding and NULL keys hold code 0)
    count in no span. Returns (match, slot, blocked): ``slot`` is each row's
    build row, -1 without, gathered only where something reads it."""
    n = lkey.shape[0]
    B = min(PROBE_BLOCK, n)
    nb = -(-n // B)
    k = jnp.pad(lkey, (0, nb * B - n)).reshape(nb, B)
    l = jnp.pad(live, (0, nb * B - n)).reshape(nb, B)
    lo = jnp.where(l, k, jnp.iinfo(k.dtype).max).min(axis=1)
    hi = jnp.where(l, k, -1).max(axis=1)  # -1: a block with no live row
    r = jnp.where(hi >= 0, lo // _ROW_CODES, 0)  # the bitmap row of each block's least live code
    fits = jnp.all(hi // _ROW_CODES - r <= 1)

    def gather():
        slot = _table_rows(jnp, table, lkey, live)
        return slot >= 0, slot

    def blocked():
        nr = -(-table.shape[0] // _ROW_CODES) + 1  # one row more: every block has a second
        bits = jnp.pad(table >= 0, (0, nr * _ROW_CODES - table.shape[0])).reshape(nr * 128, 32)
        rows = (bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)).sum(axis=1, dtype=jnp.uint32).reshape(nr, 128)
        win = jnp.concatenate([rows[r], rows[r + 1]], axis=1)  # [nb, 256]
        off = k - (r * _ROW_CODES)[:, None]  # a live row's: in [0, 2 rows of codes)
        word = jnp.where((off >> 5)[:, :, None] == jnp.arange(256, dtype=off.dtype), win[:, None, :], jnp.uint32(0)).sum(axis=2, dtype=jnp.uint32)
        match = (l & (((word >> (off & 31).astype(jnp.uint32)) & 1) == 1)).reshape(-1)[:n]
        return match, _table_rows(jnp, table, lkey, match)

    match, slot = jax.lax.cond(fits, blocked, gather)
    return match, slot, fits


def probe_paths(probed) -> str:
    """"blocked,gather": how each join's direct-address lookup answered its
    probe rows, from the program's [probe rows, of them by blocks] a join
    (shards summed: "mixed" where they differ; "-" where no such lookup ran)."""
    return ",".join("-" if not n else "blocked" if b == n else "mixed" if b else "gather" for n, b in probed)


def _slot_partial(jax, jnp, slot, mask, vals, cap):
    """Grouped partial sums where a group IS a build slot. ``slot`` names
    each probe row's, -1 for a row of none: the join key's code as
    :func:`_fold_join` hands it (``code``; one code, one build row), or the
    build row itself. Probe rows whose live slots never step back (a fact
    table stored in its dimension's key order: `lineitem` by `l_orderkey`)
    are reduced in place, as runs; anything else is sorted by slot first, the
    value lanes riding the sort. Which, the program sees in the data (one
    running maximum). Returns (slot, first probe row, sums, counts, overflow)
    of the first ``cap`` groups; a group holds rows iff its count > 0, and
    ``overflow`` counts groups past ``cap`` as :func:`_segment_partial` does."""
    n = slot.shape[0]
    live = mask & (slot >= 0)
    s = jnp.where(live, slot, -1)
    rows = jnp.arange(n, dtype=jnp.int32)
    vals = [jnp.where(live, v, 0) for v in vals]

    def before(x):  # the last live slot before each row
        return jnp.concatenate([jnp.full(1, -1, x.dtype), jax.lax.cummax(x)[:-1]])

    def by_slot():
        key = jnp.where(live, s, jnp.iinfo(jnp.int32).max)  # rows of no group sort last
        out = jax.lax.sort((key, rows, *vals), num_keys=1)
        key = jnp.where(out[0] == jnp.iinfo(jnp.int32).max, -1, out[0])
        return (key, before(key), out[1], *out[2:])

    prev = before(s)
    s, prev, rows, *vals = jax.lax.cond(jnp.all(~live | (s >= prev)), lambda: (s, prev, rows, *vals), by_slot)
    live = s >= 0
    first = live & (s != prev)
    seg = jnp.cumsum(first.astype(jnp.int32))  # groups begun up to and at each row
    overflow = jnp.maximum(seg[-1].astype(jnp.int64) - cap, 0)
    at = jnp.searchsorted(seg, jnp.arange(1, cap + 2, dtype=jnp.int32))  # group k's first row; n past the last
    starts = jnp.clip(at[:-1], 0, n - 1)
    ends = jnp.clip(at[1:] - 1, 0, n - 1)
    has = jnp.arange(cap, dtype=jnp.int32) < seg[-1]

    def run_sums(x):
        cs = jnp.cumsum(x)
        lo = jnp.where(starts > 0, cs[jnp.maximum(starts - 1, 0)], 0)
        return jnp.where(has, cs[ends] - lo, 0)

    sums = [run_sums(v) for v in vals]
    cnt = run_sums(live.astype(jnp.int32)).astype(jnp.int64)
    return jnp.where(has, s[starts], 0), jnp.where(has, rows[starts], 0), sums, cnt, overflow


def _local_unique_join(jax, jnp, lkey, lkeys, lvalid, rkey, rkeys, rcols, rvalid,
                       dead_build=None, dead_probe=None):
    """Per-shard probe of a unique-key build side: for each left row find its
    right match (≤1 by uniqueness). Returns (gathered right cols, match).
    ``dead_build``/``dead_probe``: sentinels above every live key code
    (packed-lane dtype-aware); default to the mixed-key int64 sentinels."""
    db = jnp.int64(2**62) if dead_build is None else dead_build
    rperm = jnp.argsort(jnp.where(rvalid, rkey, db))
    rk_s = jnp.where(rvalid, rkey, db)[rperm]
    pkey = lkey if dead_probe is None else jnp.where(lvalid, lkey, dead_probe)
    idx = _sorted_lookup(jnp, rk_s, pkey)
    match = (rk_s[idx] == pkey) & lvalid
    match &= rvalid[rperm][idx]
    # exact component verification (mix collisions can't fabricate a match)
    for lcomp, rcomp in zip(lkeys, rkeys):
        match &= rcomp[rperm][idx] == lcomp
    gathered = [rc[rperm][idx] for rc in rcols]
    return gathered, match


def _sorted_bounds(jnp, rk_s, lkey):
    """For each probe key: (lo, hi) = [count of sorted build keys < key,
    count ≤ key), via two sort-merges (see _sorted_lookup for why not
    searchsorted on TPU). Match count per probe row = hi - lo."""
    m = rk_s.shape[0]
    np_ = lkey.shape[0]
    # hi: ties put build rows first → cum counts build rows <= key
    perm1 = jnp.argsort(jnp.concatenate([rk_s, lkey]), stable=True)
    inv1 = jnp.argsort(perm1)
    hi = jnp.cumsum(jnp.where(perm1 < m, 1, 0))[inv1[m:]]
    # lo: ties put probe rows first → cum counts build rows < key
    perm2 = jnp.argsort(jnp.concatenate([lkey, rk_s]), stable=True)
    inv2 = jnp.argsort(perm2)
    lo = jnp.cumsum(jnp.where(perm2 >= np_, 1, 0))[inv2[:np_]]
    return lo, hi


def _local_expand_join(jax, jnp, lkey, lkeys, lvalid, rkey, rkeys, rcols, rvalid, lcols, out_cap,
                       dead_build=None, dead_probe=None, left_outer=False, lmatch=None):
    """Per-shard equi-join with a NON-unique build side: each probe row
    expands to its match count. Output is ``out_cap`` static slots; slot j
    maps back to (probe row, match ordinal) through a cumsum of per-probe
    match counts — pure gathers, no scatter (TPU policy). ``left_outer``:
    matchless probe rows still emit ONE slot with the build lanes zeroed
    (NULL-extended); ``lmatch`` narrows which live probes may MATCH (NULL-key
    rows emit but never match). Returns (probe-lane outputs, build-lane
    outputs, live, overflow)."""
    big = jnp.int64(2**62) if dead_build is None else dead_build
    big_p = big - 1 if dead_probe is None else dead_probe
    if lmatch is None:
        lmatch = lvalid
    rperm = jnp.argsort(jnp.where(rvalid, rkey, big))
    rk_s = jnp.where(rvalid, rkey, big)[rperm]
    pkey = jnp.where(lmatch, lkey, big_p)  # dead/NULL-key probes match nothing
    lo, hi = _sorted_bounds(jnp, rk_s, pkey)
    mcnt = jnp.where(lmatch, hi - lo, 0)  # true match count per probe
    cnt = jnp.where(lvalid & (mcnt == 0), 1, mcnt) if left_outer else mcnt
    cum = jnp.cumsum(cnt)
    total = cum[-1] if cnt.shape[0] else jnp.int64(0)
    overflow = jnp.maximum(total - out_cap, 0)
    j = jnp.arange(out_cap)
    p = jnp.searchsorted(cum, j, side="right")  # out_cap queries over n probes
    p_c = jnp.clip(p, 0, cnt.shape[0] - 1)
    base = jnp.where(p_c > 0, cum[jnp.maximum(p_c - 1, 0)], 0)
    ridx = jnp.clip(lo[p_c] + (j - base), 0, rk_s.shape[0] - 1)
    matched = (j < total) & lmatch[p_c] & (mcnt[p_c] > 0) & rvalid[rperm][ridx]
    # exact component verification: a mixed-key collision inside [lo, hi)
    # kills the slot rather than fabricating a joined row
    for lcomp, rcomp in zip(lkeys, rkeys):
        matched &= rcomp[rperm][ridx] == lcomp[p_c]
    out_left = [lc[p_c] for lc in lcols]
    if left_outer:
        live = (j < total) & lvalid[p_c]
        out_right = [jnp.where(matched, rc[rperm][ridx], 0) for rc in rcols]
    else:
        live = matched
        out_right = [rc[rperm][ridx] for rc in rcols]
    return out_left, out_right, live, overflow


def _local_filtered_exists(jax, jnp, lkey, lkeys, lvalid, rkey, rkeys, rcols, rvalid, lcols,
                           out_cap, pair_filter, dead_build=None, dead_probe=None):
    """Existence with non-equality join conditions (semi/anti joins carrying
    ``other_conds``, the Q21 ``l2.l_suppkey <> l1.l_suppkey`` idiom): expand
    each probe row to its candidate matches, verify key components exactly,
    evaluate ``pair_filter`` over the joined (probe lanes, build lanes)
    pairs, and reduce back to a per-probe PASSING-match count via a cumsum
    over the probe-ordered slots. Exact: hash-collision candidates die at
    component verification before the filter sees them, and a probe row with
    no candidates contributes no slots (count 0 — kept by anti, dropped by
    semi). Returns (per-probe pass counts, overflow vs ``out_cap``)."""
    big = jnp.int64(2**62) if dead_build is None else dead_build
    big_p = big - 1 if dead_probe is None else dead_probe
    rperm = jnp.argsort(jnp.where(rvalid, rkey, big))
    rk_s = jnp.where(rvalid, rkey, big)[rperm]
    pkey = jnp.where(lvalid, lkey, big_p)
    lo, hi = _sorted_bounds(jnp, rk_s, pkey)
    mcnt = jnp.where(lvalid, hi - lo, 0)
    cum = jnp.cumsum(mcnt)
    total = cum[-1] if mcnt.shape[0] else jnp.int64(0)
    overflow = jnp.maximum(total - out_cap, 0)
    j = jnp.arange(out_cap)
    p = jnp.searchsorted(cum, j, side="right")
    p_c = jnp.clip(p, 0, mcnt.shape[0] - 1)
    base = jnp.where(p_c > 0, cum[jnp.maximum(p_c - 1, 0)], 0)
    ridx = jnp.clip(lo[p_c] + (j - base), 0, rk_s.shape[0] - 1)
    cand = (j < total) & lvalid[p_c] & (mcnt[p_c] > 0) & rvalid[rperm][ridx]
    for lcomp, rcomp in zip(lkeys, rkeys):
        cand &= rcomp[rperm][ridx] == lcomp[p_c]
    out_l = [lc[p_c] for lc in lcols]
    out_r = [rc[rperm][ridx] for rc in rcols]
    passed = cand & pair_filter(out_l, out_r)
    cs = jnp.cumsum(passed.astype(jnp.int64))
    base_i = cum - mcnt
    end_c = jnp.clip(cum - 1, 0, out_cap - 1)
    below = jnp.where(base_i > 0, cs[jnp.clip(base_i - 1, 0, out_cap - 1)], 0)
    cnt_pass = jnp.where(mcnt > 0, cs[end_c] - below, 0)
    return cnt_pass, overflow


def _local_match_counts(jax, jnp, lkey, lkeys, lvalid, rkey, rkeys, rvalid, dead_build=None, dead_probe=None):
    """Per-probe match count against the build side (semi/anti joins need no
    expansion — just existence). Exact for single-component or packed keys;
    for mixed multi-key hashes a count>0 may be a collision, so callers only
    get this path when keys are packed or single."""
    big = jnp.int64(2**62) if dead_build is None else dead_build
    big_p = big - 1 if dead_probe is None else dead_probe
    rperm = jnp.argsort(jnp.where(rvalid, rkey, big))
    rk_s = jnp.where(rvalid, rkey, big)[rperm]
    pkey = jnp.where(lvalid, lkey, big_p)
    lo, hi = _sorted_bounds(jnp, rk_s, pkey)
    return jnp.where(lvalid, hi - lo, 0)


@dataclass
class DistStageSpec:
    """One device-resident pipeline STAGE producing a build side for the
    next fragment (ref: fragment trees whose exchange receivers feed further
    exchange senders, fragment.go stacked fragments). The staged subplan
    (scan → [join chain] → grouped agg → finalize/having/proj) runs inside
    the SAME shard_map program as its consumer; its group slots stay in HBM
    and the downstream join re-partitions them with ``all_to_all`` on the
    NEW key — the inter-stage repartition that used to be a D2H gather →
    host re-slice → H2D re-upload.

    Pure data (callables ride the StageRuntime wrapper so this spec can be
    part of a compiled-program cache key): ``n_lanes`` per stage-reader
    input lane counts; ``joins`` the left-deep chain INSIDE the stage;
    ``n_keys``/``sums``/``group_cap``/``key_bounds``/``val_kinds`` the
    stage's agg spec (same contract as DistAggSpec); ``out_width`` the
    number of output (data, valid) lane pairs the finalize emits."""

    n_lanes: Sequence[int]
    joins: Sequence[DistJoinSpec]
    n_keys: int
    sums: Sequence[int]
    group_cap: int = 256
    key_bounds: tuple = ()
    val_kinds: tuple = ()
    out_width: int = 0


class StageRuntime:
    """DistStageSpec + the traced callables that close over bound
    expressions: per-stage-reader selections, the agg-input mapper, and the
    finalize (agg outputs → build lanes + live mask, incl. HAVING/proj).
    Kept OUT of the dataclass so ``repr(spec)`` stays a stable cache key."""

    __slots__ = ("spec", "selections", "agg_inputs", "finalize", "pair_filters", "chain_filters")

    def __init__(self, spec, selections, agg_inputs, finalize, pair_filters=None, chain_filters=()):
        self.spec = spec
        self.selections = selections
        self.agg_inputs = agg_inputs
        self.finalize = finalize
        self.pair_filters = pair_filters
        self.chain_filters = chain_filters  # [(chain position, mask fn)]


def keeps_rows(kind: str, unique: bool, exchange: str, ndev: int) -> bool:
    """Does a fold of this kind leave every probe row where it was, once?
    (A hash exchange moves them, an outer or a non-unique join adds rows.)
    The by-slot aggregate and the gather's placing of joins both ask."""
    return (ndev == 1 or exchange != "hash") and kind in ("inner", "semi", "anti") and unique


def _fold_join(xc, jnp, join, acc, mask, rcols, rvalid, pf, slot_out=None, operands=()):
    """Fold ONE build side into the accumulated probe layout — the per-join
    body of the fragment pipeline, shared by the outer chain and the join
    chains INSIDE device stages. Returns (acc, mask, dropped, overflow,
    moved) deltas accumulated into the caller's counters; ``moved`` =
    [bytes, rows] of the valid rows this fold handed to an exchange (the
    lanes' own widths; what the buffers hold, :func:`compiled_exchange_bytes`).
    ``slot_out``: a dict that receives, where the direct-address lookup ran,
    ``table`` (each key code's build row), ``code`` (each matched probe row's
    key code, -1 else: a probe row carries its match and its group, never its
    build row), ``build`` (the build lanes ``table`` indexes) and ``probe``
    ([probe rows, of them answered by blocks], :func:`_probe_match`).
    ``operands``: the program's, for a ``local`` join's ranges."""
    jax, ndev = xc.jax, xc.ndev
    dropped = jnp.int64(0)
    overflow = jnp.int64(0)
    moved = jnp.zeros(2, jnp.int64)
    kb = tuple(join.key_bounds) if join.key_bounds else None

    def join_lane(comps, _kb=kb):
        p = _pack_keys(jnp, comps, _kb) if _kb else None
        if p is None:
            return _combine_keys(jnp, comps), None
        return p

    def width(lanes):
        return sum(a.dtype.itemsize for a in lanes)

    kind = join.kind
    lkeys = [acc[i] for i in join.left_keys]
    rkeys = [rcols[i] for i in join.right_keys]
    # probe rows with NULL keys: inner/semi joins drop them up front;
    # left joins must keep them (NULL-extended), anti joins must keep
    # them (a NULL key matches nothing)
    lkv = jnp.ones(mask.shape[0], dtype=bool)
    for vl in join.left_key_valid:
        lkv = lkv & acc[vl].astype(bool)
    if kind in ("inner", "semi"):
        mask = mask & lkv
    lkey, ncodes = join_lane(lkeys)
    rkey, _ = join_lane(rkeys)
    if join.exchange == "hash" and ndev > 1:
        if ncodes is not None:
            # packed codes are dense in [0, ncodes): code % ndev owns a key and
            # code // ndev is dense among a shard's own, so the table a shard
            # builds spans the keys it owns, a 1/ndev of the domain
            lowner, rowner = lkey % ndev, rkey % ndev
        else:
            lowner, rowner = jnp.abs(lkey) % ndev, jnp.abs(rkey) % ndev
        lowner = jnp.where(lkv, lowner, 0)  # NULL-key survivors route to shard 0 (they match nothing)
        lcap = join.left_row_cap or join.row_cap
        rcap = join.right_row_cap or join.row_cap
        nl, nr = mask.sum().astype(jnp.int64), rvalid.sum().astype(jnp.int64)
        moved = moved + jnp.stack([nl * width(acc) + nr * width(rcols), nl + nr])
        acc, mask, d1 = _route_rows(xc, jnp, acc, mask, lowner, lcap)
        rcols, rvalid, d2 = _route_rows(xc, jnp, rcols, rvalid, rowner, rcap)
        dropped = dropped + d1 + d2
        lkeys = [acc[i] for i in join.left_keys]
        rkeys = [rcols[i] for i in join.right_keys]
        lkv = jnp.ones(mask.shape[0], dtype=bool)
        for vl in join.left_key_valid:
            lkv = lkv & acc[vl].astype(bool)
        lkey, ncodes = join_lane(lkeys)
        rkey, _ = join_lane(rkeys)
        if ncodes is not None:
            lkey, rkey, ncodes = lkey // ndev, rkey // ndev, -(-ncodes // ndev)
    elif join.exchange == "local" and ndev > 1:
        rng = operands[join.range_operand]
        rcols, rvalid, d2, sent = _send_slivers(
            xc, jnp, rcols, rvalid, rkey, rcols[join.right_live].astype(bool), rng, join.halo_cap
        )
        dropped = dropped + d2
        moved = moved + jnp.stack([sent * width(rcols), sent]).astype(jnp.int64)
        rkeys = [rcols[i] for i in join.right_keys]
        rkey, _ = join_lane(rkeys)
        # the table spans this shard's range only: codes from its low end
        low = rng[jax.lax.axis_index("dp"), 0].astype(lkey.dtype)
        lkey, rkey, ncodes = lkey - low, rkey - low, join.local_codes
        lkv = lkv & (lkey >= 0) & (lkey < ncodes)
        rvalid = rvalid & (rkey >= 0) & (rkey < ncodes)
    elif ndev > 1:  # broadcast: replicate the build side on every shard
        nr = rvalid.sum().astype(jnp.int64)
        moved = moved + jnp.stack([nr * width(rcols), nr])
        rcols = [xc.all_gather("broadcast", c) for c in rcols]
        rvalid = xc.all_gather("broadcast", rvalid)
        rkeys = [rcols[i] for i in join.right_keys]
        rkey, _ = join_lane(rkeys)
    rlive = rvalid  # post-selection build rows (right joins preserve
    # these even with NULL keys — key validity only gates MATCHING)
    for vl in join.right_key_valid:
        rvalid = rvalid & rcols[vl].astype(bool)
    # dead-row sentinels above every live key code (packed lanes stay
    # in their narrow dtype; mixed-hash lanes use the int64 bigs)
    dead_b = None if ncodes is None else ncodes + 1
    dead_p = None if ncodes is None else ncodes
    if (
        ncodes is None
        and len(lkeys) > 1
        and not join.unique
        and (kind == "left" or (kind in ("semi", "anti") and pf is None))
    ):
        # count-based existence / left-outer match counts must be
        # EXACT and no static bounds packed the key — rank-compress
        # the composite key over both sides instead (collision-free)
        lkey, rkey, span = _exact_pair_lanes(jnp, lkeys, rkeys)
        dead_b, dead_p = span + 1, span
    probe_live = mask & lkv  # rows eligible to match
    if kind == "right":
        # build-side outer (ref: mpp.go:397 right-out join build):
        # matched pairs emit like inner; build rows NO probe row
        # matched emit once with the probe lanes NULL-extended. With
        # hash exchange each build row lives on exactly one shard, so
        # the unmatched flag is local; with broadcast the flag must
        # AND across shards (psum of per-shard match counts) and only
        # shard 0 emits the survivors.
        if join.unique:
            gathered, match = _local_unique_join(
                jax, jnp, lkey, lkeys, probe_live, rkey, rkeys, rcols, rvalid, dead_b, dead_p
            )
            macc = acc + gathered
            mmask = match
        else:
            out_l, out_r, mmask, of = _local_expand_join(
                jax, jnp, lkey, lkeys, probe_live, rkey, rkeys,
                rcols, rvalid, acc, join.out_cap, dead_b, dead_p,
                left_outer=False, lmatch=probe_live
            )
            overflow = overflow + of
            macc = out_l + out_r
        # per-build-row probe-match counts (roles swapped; exact —
        # the planner admits single-key right joins only)
        cnt_b = _local_match_counts(
            jax, jnp, rkey, rkeys, rvalid, lkey, lkeys, probe_live, dead_b, dead_p
        )
        if join.exchange == "broadcast":
            cnt_b = xc.psum("broadcast", cnt_b)
            emit = jax.lax.axis_index("dp") == 0
            unmatched = rlive & (cnt_b == 0) & emit
        else:
            unmatched = rlive & (cnt_b == 0)
        n_probe_lanes = len(acc)
        rn = rlive.shape[0]
        acc = [
            jnp.concatenate([a, jnp.zeros(rn, a.dtype)])
            for a in macc[:n_probe_lanes]
        ] + [
            jnp.concatenate([a, rc])
            for a, rc in zip(macc[n_probe_lanes:], rcols)
        ]
        mask = jnp.concatenate([mmask, unmatched])
    elif kind in ("semi", "anti") and pf is not None:
        # existence gated on non-equality pair conditions: expand,
        # verify, filter, reduce (unique build sides ride the same
        # path — the expansion then has ≤1 candidate per probe row)
        cnt_pass, of = _local_filtered_exists(
            jax, jnp, lkey, lkeys, probe_live, rkey, rkeys, rcols, rvalid,
            acc, join.out_cap, pf, dead_b, dead_p,
        )
        overflow = overflow + of
        mask = mask & (cnt_pass > 0) if kind == "semi" else mask & (cnt_pass == 0)
    elif kind in ("semi", "anti") and not join.unique:
        cnt = _local_match_counts(
            jax, jnp, lkey, lkeys, probe_live, rkey, rkeys, rvalid, dead_b, dead_p
        )
        mask = mask & (cnt > 0) if kind == "semi" else mask & (cnt == 0)
    elif join.unique:
        if ncodes is not None and ncodes <= DIRECT_DOMAIN_MAX:
            # bounded keys, a domain that fits: no sort (the bounds decide)
            with jax.named_scope("mpp.build"):
                table = _direct_table(jnp, rkey, rvalid, ncodes)
                match, slot, blocked = _probe_match(jax, jnp, table, lkey, probe_live)
            gathered = [rc[jnp.maximum(slot, 0)] for rc in rcols]  # lanes nothing reads are never gathered, nor is ``slot`` then
            if slot_out is not None:
                # the key's code says which rows are one group's run, on one
                # shard as on four (slivers sit behind a shard's own build rows,
                # so build rows step back where the keys do not)
                n = lkey.shape[0]
                slot_out.update(table=table, code=jnp.where(match, lkey, -1).astype(jnp.int32), build=rcols,
                                probe=jnp.stack([n, n * blocked]).astype(jnp.int64))
        else:
            gathered, match = _local_unique_join(
                jax, jnp, lkey, lkeys, probe_live, rkey, rkeys, rcols, rvalid, dead_b, dead_p
            )
        if kind == "inner":
            mask = match
            acc = acc + gathered
        elif kind == "left":
            # NULL-extend the build lanes for matchless probe rows
            acc = acc + [jnp.where(match, g, 0) for g in gathered]
        elif kind == "semi":
            mask = match
        else:  # anti
            mask = mask & ~match
    else:
        out_l, out_r, newmask, of = _local_expand_join(
            jax, jnp, lkey, lkeys, probe_live if kind == "inner" else mask, rkey, rkeys,
            rcols, rvalid, acc, join.out_cap, dead_b, dead_p,
            left_outer=(kind == "left"), lmatch=probe_live
        )
        overflow = overflow + of
        mask = newmask
        acc = out_l + out_r
    return acc, mask, dropped, overflow, moved


def _exchange_group_slots(xc, jnp, cap, pkeys, psums, pcnt, route_keys=None):
    """Hash-exchange per-shard group SLOTS to their key owners — the
    fragment-boundary ``all_to_all`` between a partial agg and its merge
    (shared by the final agg tail and inter-stage repartitions). Routes by
    ``route_keys`` (default: every key lane); returns (rxkeys, rxsums,
    rxcnt, slot_overflow)."""
    ndev = xc.ndev
    h = _combine_keys(jnp, route_keys if route_keys is not None else pkeys)
    owner = jnp.where(pcnt > 0, jnp.abs(h) % ndev, ndev - 1)
    order = jnp.argsort(owner, stable=True)
    so = owner[order]
    rank = jnp.arange(cap) - jnp.searchsorted(so, so, side="left")
    # one dest owning more than ``cap`` group slots overflows the bucket
    of_slots = ((pcnt[order] > 0) & (rank >= cap)).sum()

    def bucketize(x):
        buf = jnp.zeros((ndev * cap,), dtype=x.dtype)
        return buf.at[so * cap + rank].set(x[order])

    def exchange(buf):
        return xc.all_to_all("groups", buf.reshape(ndev, cap))

    rxkeys = [exchange(bucketize(k)) for k in pkeys]
    rxsums = [exchange(bucketize(s)) for s in psums]
    rxcnt = exchange(bucketize(pcnt))
    return rxkeys, rxsums, rxcnt, of_slots


def _run_stage(xc, jnp, stage: StageRuntime, block):
    """Execute one DEVICE stage over its readers' input lane block: fold the
    stage's join chain, run the two-phase grouped agg (partial →
    group-owner all_to_all → merge), finalize to build lanes. The returned
    lanes are per-shard ``group_cap`` slots, DEVICE-RESIDENT — the consumer
    join's exchange re-partitions them on the new key without any host
    round-trip. Returns (out_lanes, out_valid, dropped, overflow, moved)."""
    spec = stage.spec

    def _chain(pos, acc, mask):
        for fpos, fn in stage.chain_filters:
            if fpos == pos:
                mask = mask & fn(acc)
        return mask

    soffs = [sum(spec.n_lanes[:i]) for i in range(len(spec.n_lanes) + 1)]
    acc = list(block[soffs[0] : soffs[1]])
    mask = jnp.ones(acc[0].shape[0], dtype=bool)
    if stage.selections[0] is not None:
        mask = stage.selections[0](*acc)
    mask = _chain(0, acc, mask)
    dropped = jnp.int64(0)
    overflow = jnp.int64(0)
    moved = jnp.zeros(2, jnp.int64)
    for ji, join in enumerate(spec.joins):
        rcols = list(block[soffs[ji + 1] : soffs[ji + 2]])
        rvalid = jnp.ones(rcols[0].shape[0], dtype=bool)
        if stage.selections[ji + 1] is not None:
            rvalid = stage.selections[ji + 1](*rcols)
        pf = stage.pair_filters[ji] if stage.pair_filters is not None else None
        acc, mask, d, of, mv = _fold_join(xc, jnp, join, acc, mask, rcols, rvalid, pf)
        dropped, overflow, moved = dropped + d, overflow + of, moved + mv
        mask = _chain(ji + 1, acc, mask)
    acols = stage.agg_inputs(acc)
    keys = list(acols[: spec.n_keys])
    vals = [acols[i] for i in spec.sums]
    pkeys, psums, pcnt, of1 = _segment_partial(
        jnp, keys, vals, mask, spec.group_cap, spec.key_bounds, spec.val_kinds
    )
    # the inter-stage repartition: live group slots cross the mesh ONCE,
    # each lane at its own width (keys + sums + count) — all on ICI
    nslots = (pcnt > 0).sum().astype(jnp.int64)
    moved = moved + jnp.stack([nslots * sum(a.dtype.itemsize for a in (*pkeys, *psums, pcnt)), nslots])
    rxkeys, rxsums, rxcnt, of_slots = _exchange_group_slots(xc, jnp, spec.group_cap, pkeys, psums, pcnt)
    mkeys, msums_cnt, _, of3 = _segment_partial(
        jnp,
        rxkeys,
        rxsums + [rxcnt],
        rxcnt > 0,
        spec.group_cap,
        spec.key_bounds,
        tuple(spec.val_kinds) + ("sum",),
    )
    out_lanes, out_valid = stage.finalize(mkeys, list(msums_cnt[:-1]), msums_cnt[-1])
    # trailing live lane keeps the block layout identical to a plain
    # reader's (2*ncols data/valid pairs + live), so the accumulated lane
    # offsets downstream stay uniform
    return out_lanes + [out_valid], out_valid, dropped, overflow + of1 + of_slots + of3, moved


@dataclass
class DistTopNSpec:
    """Per-shard TopN/Limit/row-gather tail over the joined lane layout.

    ``order``: [(lane index, valid lane index, desc)] — empty = plain
    limit/row gather. ``limit``: static per-shard output rows (None for
    row-gather, sized by ``out_cap``). ``out_lanes``: (data lane, valid lane)
    pairs to emit. The root re-sorts/trims the gathered candidate union, so
    per-shard heads are a superset protocol like coprocessor TopN tasks."""

    order: Sequence[tuple]
    limit: int | None
    out_lanes: Sequence[tuple]
    out_cap: int = 4096


def build_dist_pipeline(
    mesh,
    joins: Sequence[DistJoinSpec],
    agg: DistAggSpec | None,
    *,
    n_lanes: Sequence[int],
    selections: Sequence[Callable | None],
    agg_inputs: Callable | None = None,
    topn: "DistTopNSpec | None" = None,
    warn_sink=None,
    shard_probe: Callable | None = None,
    pair_filters: Sequence[Callable | None] | None = None,
    chain_filters: Sequence[tuple] = (),
    stages: "Sequence[StageRuntime | None] | None" = None,
    n_operands: int = 0,
    bind_operands: Callable | None = None,
    name: str = "mpp",
    count_rows: bool = False,
):
    """The generalized MPP pipeline in ONE jitted shard_map (ref: §3.3 —
    fragments: scan→sel→[exchange→join]*→(partial agg→hash exchange→merge |
    topN/limit)→gather; fragment boundaries are collectives on ``dp``).

    Inputs: reader 0's ``n_lanes[0]`` sharded lanes, then reader 1's, ... A
    left-deep join chain folds each build reader into the accumulated probe
    lane layout (probe lanes + gathered build lanes per join). The tail is
    either the two-phase agg (``agg`` + ``agg_inputs``) or a per-shard
    TopN/limit head (``topn``).

    Agg returns replicated (keys..., sums..., count, total, dropped,
    overflow); TopN returns (out lanes..., live, count, total, dropped,
    overflow).

    ``shard_probe(shard_id, rows, exchange_bytes)``: a host callback invoked
    ONCE per mesh shard (``jax.debug.callback``) with that shard's
    post-fragment live row count and its exchanged byte estimate — its args
    depend on the shard-LOCAL tail reduction (before the final replicating
    collectives, which would synchronize every shard to the same finish
    time), so the invocation time attributes per-shard compute: the
    straggler probe behind the ``mpp_task: {..., slowest: shard k}`` line.

    ``stages``: per-reader StageRuntime or None — reader k with a stage runs
    its input block through :func:`_run_stage` and the STAGE OUTPUT slots
    (device-resident) become the join's build side; with stages present the
    program emits one extra replicated output, the per-stage exchanged-byte
    vector (ordered by reader index), before the warn count.

    ``n_operands`` scalars follow the lanes: the literals of the readers'
    pushed conditions, handed to ``bind_operands`` at trace time so that the
    selections read them as traced values — one program serves every literal.
    A ``local`` join's ranges ([ndev, 2] each) follow them, replicated too
    (``DistJoinSpec.range_operand`` indexes the whole operand list).
    ``name`` names the XLA module (``jit_<name>``); the stages carry the
    scopes ``mpp.build``, ``mpp.probe``, ``mpp.agg``, ``mpp.topn``, and every
    collective lies under ``mpp.exchange`` (:class:`_Exchange`).

    ``count_rows``: the program emits one more replicated output before the
    warn count, all shards summed: the valid rows its exchanges carried, then
    join by join [probe rows, of them answered by blocks] (:func:`_probe_match`;
    0, 0 for a join that took no direct-address lookup)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    ndev = mesh.devices.size
    xc = _Exchange(jax, ndev)
    cap = agg.group_cap if agg is not None else 0
    n_readers = len(n_lanes)
    offs = [sum(n_lanes[:i]) for i in range(n_readers + 1)]

    def _apply_chain(pos, acc, mask):
        # post-join filters over the accumulated lane layout (a WHERE
        # residue that compares across join sides — e.g. the decorrelated
        # Q17 ``l_quantity < 0.2*avg`` against the joined subquery lane);
        # position k applies after the k-th join has folded in
        for fpos, fn in chain_filters:
            if fpos == pos:
                mask = mask & fn(acc)
        return mask

    def step(*cols):
        operands = cols[offs[-1] :]
        if bind_operands is not None:
            bind_operands(operands)
        acc = list(cols[offs[0] : offs[1]])
        mask = jnp.ones(acc[0].shape[0], dtype=bool)
        if selections[0] is not None:
            mask = selections[0](*acc)
        mask = _apply_chain(0, acc, mask)
        dropped = jnp.int64(0)
        overflow = jnp.int64(0)
        # per-shard [bytes, rows] of the valid rows handed to an exchange (a
        # lane at its own width); DCE'd where neither a shard_probe nor
        # ``count_rows`` consumes it
        moved = jnp.zeros(2, jnp.int64)
        # per-stage exchanged bytes (reader order), replicated output when
        # any stage exists — the dryrun/EXPLAIN per-stage breakdown
        stage_xb: list = []
        builds: list = []  # per join: (build lanes, live mask)
        for ji in range(len(joins)):
            block = list(cols[offs[ji + 1] : offs[ji + 2]])
            stage = stages[ji + 1] if stages is not None else None
            if stage is not None:
                rcols, rvalid, d_s, of_s, mv_s = _run_stage(xc, jnp, stage, block)
                dropped = dropped + d_s
                overflow = overflow + of_s
                moved = moved + mv_s
                stage_xb.append(mv_s[0])
            else:
                rcols = block
                rvalid = jnp.ones(rcols[0].shape[0], dtype=bool)
                if selections[ji + 1] is not None:
                    rvalid = selections[ji + 1](*rcols)
            builds.append((rcols, rvalid))
        # a snowflake arm (``DistJoinSpec.arm``, the gather's decision) folds
        # from its tip: a unique inner join whose probe keys all lie in the
        # build side of the join before it (lineitem -> orders -> customer:
        # ``o_custkey`` is a lane of ``orders``) is folded into THAT build
        # side first, at its row count — the 12M-row probe then looks up ONE
        # table that already says which orders pass. The lanes it gathers land
        # where the chain would have put them, so the accumulated layout, and
        # every filter placed in it, is as written.
        probes = [jnp.zeros(2, jnp.int64)] * len(joins)  # per join: [probe rows, of them answered by blocks] where a direct-address lookup ran
        lane_off = [len(acc)]  # where join ji's build lanes start in the accumulated layout
        for ji, join in enumerate(joins):
            lane_off.append(lane_off[-1] + (len(builds[ji][0]) if join.kind in ("inner", "left", "right") else 0))
        for ji in reversed(range(1, len(joins))):
            join, before = joins[ji], joins[ji - 1]
            if not join.arm:
                continue
            lo = lane_off[ji - 1]
            arm = replace(
                join,
                left_keys=[k - lo for k in join.left_keys],
                left_key_valid=tuple(k - lo for k in join.left_key_valid),
                left_row_cap=before.right_row_cap,
            )
            took = {}
            with jax.named_scope("mpp.build"):
                bl, bv, d, of, mv = _fold_join(xc, jnp, arm, *builds[ji - 1], *builds[ji], None, took, operands)
            builds[ji - 1] = (bl, bv)
            probes[ji] = took.get("probe", probes[ji])
            dropped, overflow, moved = dropped + d, overflow + of, moved + mv
        slot = None  # by-slot aggregate: (the join's table, each matched row's key code, that build's lanes, where they start)
        for ji, join in enumerate(joins):
            if join.arm:
                mask = _apply_chain(ji + 1, acc, mask)
                continue
            pf = pair_filters[ji] if pair_filters is not None else None
            took = {}
            if slot is not None and not keeps_rows(join.kind, join.unique, join.exchange, ndev):
                slot = None  # this fold moves or multiplies the probe rows
            n_before = len(acc)
            with jax.named_scope("mpp.probe"):
                acc, mask, d, of, mv = _fold_join(xc, jnp, join, acc, mask, *builds[ji], pf, took, operands)
            dropped, overflow, moved = dropped + d, overflow + of, moved + mv
            probes[ji] = took.get("probe", probes[ji])
            if "table" in took and agg is not None and agg.slot_join == ji:
                slot = (took["table"], took["code"], took["build"], n_before)
            mask = _apply_chain(ji + 1, acc, mask)
        if agg is not None:
            with jax.named_scope("mpp.agg"):
                outs, local_rows, sent = _agg_tail(acc, mask, dropped, overflow, slot)
        else:
            with jax.named_scope("mpp.topn"):
                outs, local_rows, sent = _topn_tail(acc, mask, dropped, overflow)
        if shard_probe is not None:
            # effect-only host callback; local_rows depends on the shard's
            # tail reduction, so the probe fires after this shard's compute
            # but BEFORE the synchronizing gathers equalize finish times
            jax.debug.callback(shard_probe, jax.lax.axis_index("dp"), local_rows, moved[0] + sent[0])
        if stage_xb:
            # per-stage exchange bytes, summed across shards — rides home as
            # one replicated vector (staged-reader order)
            outs = (*outs, xc.psum("groups", jnp.stack(stage_xb)))
        if count_rows:
            outs = (*outs, xc.psum("groups", jnp.concatenate([(moved[1] + sent[1])[None], *probes])))
        if warn_sink is not None:
            # device warnings born inside the fragment (division by 0 in a
            # selection/agg argument) ride ONE replicated count output —
            # psum across shards, converted back to session warnings by the
            # gather (the per-SelectResponse warning carriage)
            wtotal = jnp.int64(0)
            for _code, _msg, c in warn_sink.items:
                wtotal = wtotal + jnp.asarray(c, jnp.int64)
            outs = (*outs, xc.psum("groups", wtotal))
        return outs

    def _topn_tail(joined, mask, dropped, overflow):
        n = mask.shape[0]
        lanes = [~mask]
        for di, vi, desc in topn.order:
            d = joined[di]
            v = joined[vi].astype(bool) if vi is not None else jnp.ones(n, bool)
            if desc:
                lanes.append(~v)  # NULLs last
                dd = jnp.where(v, d, 0)
                lanes.append(-dd if jnp.issubdtype(dd.dtype, jnp.floating) else ~dd)
            else:
                lanes.append(v)  # NULLs first
                lanes.append(jnp.where(v, d, 0))
        perm = jnp.argsort(lanes[-1], stable=True) if len(lanes) > 1 else jnp.argsort(lanes[0], stable=True)
        for lane in reversed(lanes[:-1] if len(lanes) > 1 else []):
            perm = perm[jnp.argsort(lane[perm], stable=True)]
        out_n = min(topn.limit if topn.limit is not None else topn.out_cap, n)
        head = perm[:out_n]
        cnt = mask.sum()
        if topn.limit is None:
            # plain row gather: exceeding the static cap is an overflow (the
            # runner retries bigger); TopN heads are supersets by protocol
            overflow = overflow + jnp.maximum(cnt - out_n, 0)
        outs = []
        for di, vi in topn.out_lanes:
            outs.append(xc.all_gather("groups", joined[di][head]))
            v = joined[vi][head] if vi is not None else jnp.ones(out_n, jnp.int64)
            outs.append(xc.all_gather("groups", v))
        hlive = mask[perm][:out_n]
        glive = xc.all_gather("groups", hlive)
        total = xc.psum("groups", cnt)
        gdropped = xc.psum("groups", dropped)
        goverflow = xc.psum("groups", overflow)
        nlive = hlive.sum().astype(jnp.int64)  # this shard's head rows in the gathered result
        return (*outs, glive, total, gdropped, goverflow), cnt, jnp.stack([nlive * sum(o.dtype.itemsize for o in outs), nlive])

    def _agg_tail(joined, mask, dropped, overflow, slot=None):
        acols = agg_inputs(joined) if agg_inputs is not None else joined
        G, D = agg.n_keys, agg.n_dkeys
        vals = [acols[i] for i in agg.sums]
        if slot is not None and not D and all(k == "sum" for k in agg.val_kinds):
            # a group IS a build slot of the direct-address join: sums by the
            # key's code, and the group's key lanes read where the group is
            # known — build lanes at its build row (the table at its code: a
            # gather of ``cap`` elements), probe lanes at its first row — so no
            # build lane, and no build row, is ever gathered out to the probe's
            # row count
            table, code, build, at = slot
            gcode, grow, psums, pcnt, of1 = _slot_partial(jax, jnp, code, mask, vals, cap)
            gslot = jnp.maximum(table[gcode], 0)
            head = [a[grow] for a in joined[:at]] + [b[gslot] for b in build]
            head += [a[grow] for a in joined[at + len(build) :]]
            pkeys = [jnp.where(pcnt > 0, k, 0) for k in agg_inputs(head)[:G]]
        else:
            # distinct lanes join the stage-1 segment keys: grouping by (g, x)
            # IS the dedup (ref: TiFlash two-phase distinct aggregation)
            keys = list(acols[: G + D])
            pkeys, psums, pcnt, of1 = _segment_partial(jnp, keys, vals, mask, cap, agg.key_bounds, agg.val_kinds)
        sent = jnp.int64(0)  # live group slots handed to the exchange, then to the gathered result
        if ndev == 1 or (agg.placed and not D):
            # one shard: its partial groups are the groups — nothing to
            # exchange, nothing to merge. The same where the gather placed
            # every group on one shard (``DistAggSpec.placed``)
            mkeys, msums_cnt, of_slots, of3 = pkeys, psums + [pcnt], 0, 0
        else:
            # route by GROUP keys only: every (g, *) slot lands on g's owner
            # shard, where x dedups globally
            sent = (pcnt > 0).sum().astype(jnp.int64)
            rxkeys, rxsums, rxcnt, of_slots = _exchange_group_slots(xc, jnp, cap, pkeys, psums, pcnt, route_keys=pkeys[:G])
            mkeys, msums_cnt, _, of3 = _segment_partial(jnp, rxkeys, rxsums + [rxcnt], rxcnt > 0, cap, agg.key_bounds, tuple(agg.val_kinds) + ("sum",))
        if D:
            # stage 3: per-g reduction over the deduped (g, x) slots — the
            # distinct output pair is (Σ distinct x, count of distinct x);
            # plain value lanes re-reduce by their own kinds
            bcnt = msums_cnt[-1]
            slot_live = bcnt > 0
            xvalid = mkeys[G + 1].astype(bool) & slot_live
            dval = jnp.where(xvalid, mkeys[G], 0)
            cvals = list(msums_cnt[:-1]) + [dval, xvalid.astype(jnp.int64), bcnt]
            ckinds = tuple(agg.val_kinds) + ("sum", "sum", "sum")
            fkeys, fsums, _, of4 = _segment_partial(
                jnp, list(mkeys[:G]), cvals, slot_live, cap, tuple(agg.key_bounds[:G]), ckinds
            )
            of3 = of3 + of4
            nv = len(agg.sums)
            out_sums = []
            vi = 0
            for is_d in agg.distinct_mask:
                if is_d:
                    out_sums += [fsums[nv], fsums[nv + 1]]
                else:
                    out_sums += [fsums[vi], fsums[vi + 1]]
                    vi += 2
            out_keys, gcnt_local = fkeys, fsums[-1]
        else:
            out_keys, out_sums, gcnt_local = mkeys, list(msums_cnt[:-1]), msums_cnt[-1]
        gkeys = [xc.all_gather("groups", k) for k in out_keys]
        gsums = [xc.all_gather("groups", s) for s in out_sums]
        gcnt = xc.all_gather("groups", gcnt_local)
        total = xc.psum("groups", mask.sum())
        gdropped = xc.psum("groups", dropped)
        goverflow = xc.psum("groups", overflow + of1 + of_slots + of3)
        # shard-local live groups after the merge stage — the shard probe's
        # "rows produced" (depends on this shard's heavy reductions)
        local_rows = (gcnt_local > 0).sum()
        sent = sent + local_rows.astype(jnp.int64)
        width = sum(o.dtype.itemsize for o in (*out_keys, *out_sums, gcnt_local))
        return (*gkeys, *gsums, gcnt, total, gdropped, goverflow), local_rows, jnp.stack([sent * width, sent])

    if agg is not None:
        if agg.n_dkeys:
            n_rep = agg.n_keys + 2 * len(agg.distinct_mask) + 1
        else:
            n_rep = agg.n_keys + len(agg.sums) + 1
    else:
        n_rep = 2 * len(topn.out_lanes) + 1
    extra = ()
    if stages is not None and any(s is not None for s in stages):
        extra += (P(None),)  # per-stage exchange-bytes vector
    if count_rows:
        extra += (P(),)  # valid rows the exchanges carried
    if warn_sink is not None:
        extra += (P(),)
    step.__name__ = step.__qualname__ = name  # the XLA module is jit_<name>
    fn = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=tuple(P("dp") for _ in range(sum(n_lanes))) + (P(),) * n_operands,
        out_specs=(P(None),) * n_rep + (P(), P(), P()) + extra,
        check_vma=False,
    )
    return jax.jit(fn)


def build_dist_join_agg(
    mesh,
    join: DistJoinSpec | None,
    agg: DistAggSpec,
    *,
    n_left: int,
    n_right: int = 0,
    left_selection: Callable | None = None,
    right_selection: Callable | None = None,
    agg_inputs: Callable | None = None,
):
    """Single-join (or no-join) agg pipeline — the common star-join shape,
    kept as a thin wrapper over :func:`build_dist_pipeline`."""
    if join is None:
        return build_dist_pipeline(
            mesh,
            [],
            agg,
            n_lanes=[n_left],
            selections=[left_selection],
            agg_inputs=agg_inputs,
        )
    return build_dist_pipeline(
        mesh,
        [join],
        agg,
        n_lanes=[n_left, n_right],
        selections=[left_selection, right_selection],
        agg_inputs=agg_inputs,
    )


def finalize_dist_agg(outs, n_keys: int, n_sums: int):
    """Host-side trim: drop padding slots, return numpy arrays."""
    cnt = np.asarray(outs[n_keys + n_sums])
    live = cnt > 0
    keys = [np.asarray(outs[i])[live] for i in range(n_keys)]
    sums = [np.asarray(outs[n_keys + i])[live] for i in range(n_sums)]
    return keys, sums, cnt[live], int(np.asarray(outs[-1]))
