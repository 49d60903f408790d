"""TPU coprocessor engine: region columns → device cache → fused kernel.

Reference parity: the TiFlash role (columnar accelerator engine behind the
same coprocessor contract as TiKV). Per region task:

1. get/reuse host columnar cache (colcache.ColumnCache);
2. get/reuse *device-resident* arrays keyed by the same
   (region, data_version) identity — steady-state queries touch HBM only.
   Large regions shard into fixed-size device blocks (``_BLOCK`` rows), so
   one kernel compile serves every table size and HBM stays bounded by an
   LRU budget (``colcache.hbm_budget``) instead of growing with the data;
3. bind the DAG (string constants → dictionary codes; binder.py);
4. fetch/compile the fused kernel (ops/dag_kernel.py) and run it — per
   block for sharded regions, with all blocks dispatched asynchronously and
   results stacked on-device into ONE host transfer;
5. trim padded outputs by the kernel-reported count and re-attach string
   dictionaries → chunk.

Block results concatenate without a merge step because of the pushdown
contract: aggregations are dispatched in PARTIAL mode (the executor's final
agg merges duplicate groups across tasks — and now across blocks), TopN
tasks return candidate supersets re-sorted by the root sort, and LIMIT
tasks over-return at most ``limit`` rows per block, trimmed by the root.
This mirrors the coprocessor paging protocol (ref: pkg/kv/kv.go:589-596,
copr/coprocessor.go:368-374): LIMIT DAGs stream blocks lazily
(grow-on-demand) and stop as soon as the limit is satisfiable.

Overflow protocol: if the kernel reports more groups than its static cap, we
recompile with the next power-of-two cap and re-run (bounded doubling).
"""

from __future__ import annotations

import threading
import time as _time
import weakref
from collections import OrderedDict
from typing import NamedTuple

import numpy as np

from tidb_tpu.copr import dagpb
from tidb_tpu.copr.binder import Binder, UnsupportedForDevice
from tidb_tpu.copr.colcache import DEVICE_BLOCK_ROWS, ResolvedTask, cache_for, caches, hbm_budget
from tidb_tpu.copr.host_engine import execute_dag as host_execute_dag
from tidb_tpu.kv import KeyRange, tablecodec
from tidb_tpu.kv.memstore import MemStore, Region
from tidb_tpu.kv.rowcodec import RowSchema
from tidb_tpu.ops.dag_kernel import MAX_RANGES, get_kernel
from tidb_tpu.types import FieldType, TypeKind
from tidb_tpu.types.field_type import bigint_type
from tidb_tpu.utils import eventlog as _ev
from tidb_tpu.utils import execdetails as _ed
from tidb_tpu.utils import metrics as _metrics
from tidb_tpu.utils import tracing as _tracing
from tidb_tpu.utils.chunk import Chunk, Column, bucket_size

from tidb_tpu.ops.dag_kernel import _ensure_x64

_ensure_x64()  # BEFORE any device_put: int64/float64 lanes must not truncate

_DEFAULT_AGG_CAP = 4096
# device block rows; one compile shape for all big tables (keep in sync with
# colcache.DEVICE_BLOCK_ROWS — both read TIDB_TPU_DEVICE_BLOCK_ROWS)
_BLOCK = DEVICE_BLOCK_ROWS
_FUSE_MAX_NB = 8  # fused multi-block programs: HBM holds inputs + the concat
# mapped programs (one call for the regions of a batch that share a kernel key):
# the region counts compiled for, and the padded rows one call may stack
_MAP_STEP = 8
_MAP_ROWS = 1 << 24


def _delta_cap() -> int:
    """The fixed delta-operand row capacity (compile-shape constant)."""
    from tidb_tpu import config as _config

    return int(getattr(_config.current(), "device_delta_cap", 8192))


class _BinderView:
    """Stats facade over several column sources for the binder — a base and
    its delta, or the entries of every region of a batch task: min/max (sort
    bounds, MXU magnitude proofs, narrow-eval proofs) must cover every
    source's values, or a row outside the first one's envelope would break an
    exactness gate. A proof that holds on the union holds on each source."""

    def __init__(self, *sources):
        self.sources = sources
        self.n = sum(s.n for s in sources)
        self._minmax: dict = {}

    @property
    def handles(self):
        # only the endpoints are consumed (binder._col_stats min/max)
        hs = [s.handles for s in self.sources if len(s.handles)]
        if not hs:
            return np.empty(0, np.int64)
        return np.array(
            [min(int(h[0]) for h in hs), max(int(h[-1]) for h in hs)], dtype=np.int64
        )

    def minmax(self, slot: int) -> tuple[int, int]:
        mm = self._minmax.get(slot)
        if mm is None:
            # a delta with no valid PUT value in the slot gives None
            mms = [m for m in (s.minmax(slot) for s in self.sources) if m is not None]
            mm = self._minmax[slot] = (min(m[0] for m in mms), max(m[1] for m in mms))
        return mm


def _n_blocks(n: int) -> int:
    return -(-n // _BLOCK)


class _DeviceLRU:
    """HBM-bounded LRU of device-resident column (data, valid) pairs.

    Ref: the coprocessor cache (copr/coprocessor_cache.go:32) crossed with
    TiFlash's delta-tree page cache — capacity-bounded, recency-evicted.
    Eviction only drops our reference; in-flight kernels keep their inputs
    alive until dispatch completes, so eviction is always safe.
    """

    def __init__(self, budget_bytes: int):
        self.budget = budget_bytes
        self._mu = _tracing.TracedLock("device_lru", threading.Lock())
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()  # key → (pair, nbytes)
        self.total = 0
        # resolved batch tasks used since anything was last looked up here: a use
        # of theirs is a use of every array they hold, noted in one step
        # (``touch``) and applied to the order before anything is moved or evicted
        self._touched: dict = {}  # id → weak reference

    def get(self, key):
        with self._mu:
            if self._touched:
                self._settle()
            hit = self._entries.get(key)
            if hit is None:
                return None
            self._entries.move_to_end(key)
            return hit[0]

    def put(self, key, pair, nbytes: int):
        evicted = []
        with self._mu:
            if self._touched:
                self._settle()
            old = self._entries.pop(key, None)
            if old is not None:
                self.total -= old[1]
            self._entries[key] = (pair, nbytes)
            self.total += nbytes
            while self.total > self.budget and len(self._entries) > 1:
                k, (_, nb) = next(iter(self._entries.items()))
                if k == key:  # never evict the entry just inserted
                    break
                del self._entries[k]
                self.total -= nb
                evicted.append(k)
        _unresolve_evicted(evicted)

    def evict_superseded(self, ident, ver_epoch):
        """Drop stale epochs/versions of the same column — each write bumps
        data_version and stale device arrays would leak HBM forever. Sibling
        blocks of the *current* (version, epoch) stay resident."""
        with self._mu:
            evicted = [
                k
                for k in self._entries
                if k[: len(ident)] == ident and k[len(ident) : len(ident) + 2] != ver_epoch
            ]
            for k in evicted:
                self.total -= self._entries[k][1]
                del self._entries[k]
        _unresolve_evicted(evicted)

    def touch(self, holder) -> None:
        """``holder`` (its ``keys``: entries of this LRU it holds the arrays of)
        was used: they are as recent as it is. One step a task, whatever it
        holds; the order is brought up to date by the next lookup or put."""
        with self._mu:
            self._touched.pop(id(holder), None)
            self._touched[id(holder)] = weakref.ref(holder)

    def holds(self, keys) -> bool:
        with self._mu:
            return all(k in self._entries for k in keys)

    def _settle(self) -> None:
        for ref in self._touched.values():
            holder = ref()
            if holder is not None:
                for k in holder.keys:
                    if k in self._entries:
                        self._entries.move_to_end(k)
        self._touched.clear()


def _unresolve_evicted(keys: list) -> None:
    """A resolved batch task holds its regions' device arrays beside the LRU,
    which owns them: whatever the LRU lets go of takes the tasks that hold an
    array of the same region with it, or they would pin its HBM behind the
    budget's back. Outside the LRU's lock."""
    if not keys:
        return
    gone: dict = {}
    for k in keys:
        gone.setdefault(k[0], set()).add((k[1], k[2]))
    for store, cache in caches():
        regions = gone.get(getattr(store, "nonce", None))
        if regions:
            cache.unresolve_regions(regions)


_DEVICE_LRU = _DeviceLRU(hbm_budget())

# warm-path H2D hoisting: every dispatch used to re-transfer the (tiny)
# padded range array and the valid-row scalar — two synchronous device puts
# per task that dominate the fixed cost of cheap queries like COUNT(*). Both
# are tiny and low-cardinality, so they cache device-resident keyed by value
# (ranges by their byte image).
_MISC_MU = _tracing.TracedLock("device_misc", threading.Lock())
_RANGES_DEV: "OrderedDict[bytes, object]" = OrderedDict()
_NVALID_DEV: "OrderedDict[object, object]" = OrderedDict()
_MISC_CAP = 512


def _misc_cached(cache: OrderedDict, key, make):
    with _MISC_MU:
        hit = cache.get(key)
        if hit is not None:
            cache.move_to_end(key)
            return hit
    val = make()
    with _MISC_MU:
        cache[key] = val
        while len(cache) > _MISC_CAP:
            cache.popitem(last=False)
    return val


def _device_ranges(rarr: np.ndarray):
    """Device-resident copy of the padded range array, keyed by the bound
    ranges' byte image — repeat queries skip the per-dispatch transfer."""
    import jax.numpy as jnp

    return _misc_cached(_RANGES_DEV, rarr.tobytes(), lambda: jnp.asarray(rarr))


def _device_nvalid(n: int):
    """Device-resident valid-row-count scalar (one per distinct count)."""
    import jax.numpy as jnp

    return _misc_cached(_NVALID_DEV, int(n), lambda: jnp.asarray(int(n)))


def _device_put_col(key, make_pair, n_pad: int, cacheable: bool = True):
    """One padded (data, valid) pair on device, LRU-cached under ``key``.
    ``make_pair`` is a THUNK returning (data, valid) — host-side prep (the
    int32 narrowing astype walks the whole column) must only run on an LRU
    miss, never on the warm path. Narrow dtypes are kept narrow in HBM
    (int32 dict codes / narrowed value lanes read half the bytes; the kernel
    upcasts on use, which XLA fuses into the consumer)."""
    import jax
    import jax.numpy as jnp

    det = _ed.current_cop()
    if cacheable:
        hit = _DEVICE_LRU.get(key)
        if hit is not None:
            if det is not None:
                det.dev_cache_hits += 1
            _metrics.DEVICE_CACHE.inc(result="hit")
            return hit
    data, valid = make_pair()
    pd = np.zeros(n_pad, dtype=data.dtype)
    pd[: len(data)] = data
    pv = np.zeros(n_pad, dtype=bool)
    pv[: len(valid)] = valid
    out = (jax.device_put(jnp.asarray(pd)), jax.device_put(jnp.asarray(pv)))
    if det is not None:
        det.dev_cache_misses += 1
        det.h2d_bytes += pd.nbytes + pv.nbytes
    _metrics.DEVICE_CACHE.inc(result="miss")
    _metrics.DEVICE_TRANSFER.inc(pd.nbytes + pv.nbytes, dir="h2d")
    if cacheable:
        # key layout: (store_nonce, region_id, table_id, slot, unit, version,
        # epoch, shape-suffix) — unit is a block index, "s" (single-array), or
        # "d" (delta operand). Superseded-version eviction is per UNIT, so a
        # merge that carries clean blocks replaces only the dirty siblings.
        _DEVICE_LRU.put(key, out, pd.nbytes + pv.nbytes)
        _DEVICE_LRU.evict_superseded(key[:5], key[5:7])
    return out


def _narrowed(entry, column_id: int, data: np.ndarray) -> np.ndarray:
    """int64 value lanes whose min/max fit int32 ship to HBM as int32 —
    bounded DECIMALs, DATE days, and small ints cover the analytic hot path
    (ref: the per-width column discipline of util/chunk/column.go:74). The
    narrowing is deterministic per data version, so it can't split the
    device LRU identity."""
    if data.dtype != np.int64:
        return data
    try:
        lo, hi = entry.minmax(column_id)
    except (KeyError, ValueError):
        return data
    if -(2**31) < lo and hi < 2**31 - 1:
        return data.astype(np.int32)
    return data


def _covers_all(rarr: np.ndarray, entry, delta=None) -> bool:
    """True when the (padded) range set provably covers every entry row —
    the kernel then skips the per-row handle range mask. With a delta the
    proof must cover the delta's handle span too."""
    if entry.n == 0:
        return False
    spans = rarr[rarr[:, 0] < rarr[:, 1]]
    if len(spans) != 1:
        return False
    lo = int(entry.handles[0])
    hi = int(entry.handles[-1])
    if delta is not None and delta.n:
        lo = min(lo, int(delta.handles[0]))
        hi = max(hi, int(delta.handles[-1]))
    return int(spans[0, 0]) <= lo and hi < int(spans[0, 1])


def _block_bounds(n: int) -> list[tuple[int, int]]:
    return [(i, min(i + _BLOCK, n)) for i in range(0, n, _BLOCK)]


def _should_fuse_agg(dag: dagpb.DAGRequest, entry) -> bool:
    """Big-table agg-last DAGs run as ONE fused multi-block dispatch."""
    agg_last = bool(dag.executors[1:]) and dag.executors[-1].tp in (
        dagpb.AGGREGATION,
        dagpb.STREAM_AGG,
    )
    return entry.n > _BLOCK and agg_last and _n_blocks(entry.n) <= _FUSE_MAX_NB


def _fused_block_inputs(store, scan, cache, entry, region):
    """(handles_blocks, cols_blocks, nvalids, nb) for the fused multi-block
    kernel."""
    import jax.numpy as jnp

    bounds = _block_bounds(entry.n)
    cacheable = entry.complete
    handles_blocks = []
    cols_blocks: list[list] = [[] for _ in scan.columns]
    for bi, (lo, hi) in enumerate(bounds):
        h, cols_dev = _block_device_inputs(store, scan, cache, entry, region, bi, lo, hi, cacheable)
        handles_blocks.append(h)
        for ci, pair in enumerate(cols_dev):
            cols_blocks[ci].append(pair)
    nvalids = _misc_cached(
        _NVALID_DEV,
        ("nvalids", tuple(bounds)),
        lambda: jnp.asarray(np.array([hi - lo for lo, hi in bounds], dtype=np.int64)),
    )
    return handles_blocks, cols_blocks, nvalids, len(bounds)


def _block_device_inputs(store, scan, cache, entry, region, bi: int, lo: int, hi: int, cacheable: bool):
    """Device arrays for ONE block, put on demand (LRU-cached). The single
    construction site for the per-block device-LRU key layout — shared by the
    independent-block path and the fused multi-block window path, so the two
    always hit the same cache entries. Blocks carry per-block version tags
    across merges (entry.vtag_span), so a merge re-uploads ONLY dirty blocks."""
    epoch = cache.epoch
    ver = entry.vtag_span(lo, hi)
    base = (store.nonce, region.region_id, scan.table_id)
    hkey = base + (-1, bi, ver, epoch, _BLOCK)
    hpair = _device_put_col(
        hkey, lambda: (entry.handles[lo:hi], np.ones(hi - lo, bool)), _BLOCK, cacheable
    )
    cols_dev = []
    for c in scan.columns:
        if c.is_handle:
            cols_dev.append(hpair)
        else:
            ckey = base + (c.column_id, bi, ver, epoch, _BLOCK)

            def mk(cid=c.column_id):
                data, valid = entry.cols[cid]
                return _narrowed(entry, cid, data[lo:hi]), valid[lo:hi]

            cols_dev.append(_device_put_col(ckey, mk, _BLOCK, cacheable))
    return hpair[0], tuple(cols_dev)


def _delta_device_inputs(store, scan, cache, delta, region):
    """Device operands for the bounded delta: sorted touched handles (pads
    hold int64-max so searchsorted stays legal), per-scan-column lanes, and
    tombstone flags — all padded to the FIXED delta capacity, so every delta
    size reuses one kernel compile. LRU-cached keyed by the delta's version:
    repeat queries between DMLs pay zero H2D."""
    D = _delta_cap()
    if delta.n > D:
        raise UnsupportedForDevice(f"delta {delta.n} rows exceeds operand capacity {D}")
    epoch = cache.epoch
    cacheable = delta.complete
    base = (store.nonce, region.region_id, scan.table_id)

    def pad_handles():
        dh = np.full(D, np.iinfo(np.int64).max, dtype=np.int64)
        dh[: delta.n] = delta.handles
        return dh, np.ones(D, bool)

    hkey = base + (-1, "d", delta.data_version, epoch, D)
    dh_pair = _device_put_col(hkey, pad_handles, D, cacheable)
    tkey = base + (-2, "d", delta.data_version, epoch, D)

    def pad_tomb():
        t = np.zeros(D, dtype=bool)
        t[: delta.n] = delta.tomb
        return t, np.ones(D, bool)

    tomb_pair = _device_put_col(tkey, pad_tomb, D, cacheable)
    cols_dev = []
    for c in scan.columns:
        if c.is_handle:
            cols_dev.append(dh_pair)
        else:
            ckey = base + (c.column_id, "d", delta.data_version, epoch, D)

            def mk(cid=c.column_id):
                data, valid = delta.cols[cid]
                return data, valid

            cols_dev.append(_device_put_col(ckey, mk, D, cacheable))
    return dh_pair[0], tuple(cols_dev), tomb_pair[0]


def _delta_counts(mask_n: int, u_lo: int, u_hi: int):
    """Device-resident [mask_n, union_lo, union_hi], cached by value: the
    whole delta masks base rows; only [union_lo, union_hi) unions into this
    dispatch (blocked paths route each delta row to its handle-span block)."""
    import jax.numpy as jnp

    return _misc_cached(
        _NVALID_DEV,
        ("dn", int(mask_n), int(u_lo), int(u_hi)),
        lambda: jnp.asarray(np.array([mask_n, u_lo, u_hi], dtype=np.int64)),
    )


def _probe_slice_rows(packed_list: list, kernel):
    """Large rows-kind buffers (capacity = the padded block/table) are usually
    near-empty after selection: fetch every block's meta row in ONE tiny
    transfer, then slice each block's lanes to its bucketed live width so the
    payload transfer moves live rows, not capacity. Returns (counts, sliced)."""
    import jax
    import jax.numpy as jnp

    tup = isinstance(packed_list[0], tuple)
    ibufs = [p[0] if tup else p for p in packed_list]
    if len(ibufs) == 1:
        metas = jax.device_get(ibufs[0][0, :2])[None]
    else:
        metas = jax.device_get(jnp.stack([b[0, :2] for b in ibufs]))
    sliced = []
    for p, m in zip(packed_list, metas):
        # bucketed width: one XLA slice program per size class, not per count
        w = min(kernel.out_n, bucket_size(max(2, int(m[0]))))
        sliced.append(tuple(q[:, :w] for q in p) if tup else p[:, :w])
    return [int(m[0]) for m in metas], sliced


def _emit_kernel_warnings(buf, kernel, warn) -> None:
    """Device warning counts ride the kernel's meta row (extra packed
    outputs — see dag_kernel._DeviceWarnSink); convert nonzero counts back
    into session warnings, capped like MySQL's max_error_count."""
    if warn is None:
        return
    for code, msg, slot in kernel.warn_specs:
        cnt = int(buf[0, slot]) if slot < buf.shape[0 if buf.ndim == 1 else 1] else 0
        for _ in range(min(cnt, 64)):
            warn("Warning", code, msg)


class _Phases:
    """One cop task's walk through the device path (``_ed.PHASES``). ``to``
    ends the phase before and begins the named one, so the phases tile the
    task and what runs between two of them belongs to the first. Their walls
    always add to the task's sidecar (EXPLAIN ANALYZE ``phases:``); each is
    also a span ``exec.<phase>`` when the seam records, which is asked once a
    task. A phase may come more than once (bind: before the inputs, and at
    the kernel lookup)."""

    __slots__ = ("_det", "_live", "_attr", "_t0", "_span")
    _ATTR = {p: p + "_ms" for p in _ed.PHASES}

    def __init__(self):
        self._det = _ed.current_cop()
        self._live = _tracing.live()
        self._attr = self._span = None

    def to(self, phase: str, **meta) -> None:
        now = _time.perf_counter()
        if self._attr is not None:
            self._close(now)
        self._attr, self._t0 = self._ATTR[phase], now
        if self._live:
            self._span = _tracing.region("exec." + phase, **meta).__enter__()

    def note(self, **meta) -> None:
        """Add to the open phase's span what is only known inside it."""
        if self._span is not None:
            self._span.note(**meta)

    def end(self) -> None:
        if self._attr is not None:
            self._close(_time.perf_counter())
            self._attr = None

    def _close(self, now: float) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        det = self._det
        if det is not None:
            setattr(det, self._attr, getattr(det, self._attr) + (now - self._t0) * 1000.0)


def execute_dag(store: MemStore, dag: dagpb.DAGRequest, region: Region, ranges: list[KeyRange], read_ts: int, warn=None,
                *, batch=None, leave=None):
    """One region's DAG → Chunk. With ``batch`` — ``[(region, ranges), ...]``,
    ``region`` and ``ranges`` None — the batch cop task of copr/client.py: the
    partial results of every region it can serve in one pass, as one Chunk
    (None where it served none); the regions it cannot are handed to
    ``leave([(region, ranges), ...])``, which runs them as tasks of their own."""
    det = _ed.current_cop()
    if det is None:
        try:
            return _execute_dag_device(store, dag, region, ranges, read_ts, warn, batch, leave)
        except UnsupportedForDevice:
            # the planner's legality gate keeps most host-only shapes off this
            # engine; anything it misses (unbindable constants, unpackable
            # window sorts) falls back to the host engine
            return host_execute_dag(store, dag, region, ranges, read_ts, warn)
    t0 = _time.perf_counter()
    h0 = det.host_ms
    try:
        try:
            with _tracing.region("device-exec"):
                return _execute_dag_device(store, dag, region, ranges, read_ts, warn, batch, leave)
        except UnsupportedForDevice:
            det.degraded = det.degraded or "unsupported-for-device"
            return host_execute_dag(store, dag, region, ranges, read_ts, warn)
    finally:
        # device_ms is the HOST wall of the device path (the chip's own share
        # is at most the sidecar's fetch_ms), unless the task
        # (or a shape fallback inside _execute_dag_device) ran on the host
        # engine — which attributed itself and claimed the engine label
        host_delta = det.host_ms - h0
        if host_delta <= 0.0:
            dev_ms = (_time.perf_counter() - t0) * 1000.0
            det.device_ms += dev_ms
            det.engine = "tpu"
            _metrics.COP_DEVICE_SECONDS.observe(dev_ms / 1000.0)


def _execute_dag_device(store, dag, region, ranges, read_ts, warn=None, batch=None, leave=None):
    ph = _Phases()
    try:
        if batch is not None:
            return _batch_path(ph, store, dag, batch, read_ts, warn, leave)
        return _device_path(ph, store, dag, region, ranges, read_ts, warn)
    finally:
        ph.end()


class _Part(NamedTuple):
    """One region's share of a task on the single-kernel path."""

    entry: object  # colcache.RegionColumns, at most one block of rows
    region: Region
    rarr: np.ndarray  # the ranges, padded (_ranges_array)
    delta: object  # colcache.DeltaOverlay or None


def _ranges_array(ranges: list[KeyRange], table_id: int) -> np.ndarray:
    """ranges → padded static array; rows outside any range are masked out."""
    rarr = np.zeros((MAX_RANGES, 2), dtype=np.int64)
    for i, kr in enumerate(ranges):
        rarr[i] = tablecodec.range_to_handles(kr, table_id)
    return rarr


def _batch_path(ph: _Phases, store: MemStore, dag: dagpb.DAGRequest, batch: list, read_ts: int, warn, leave):
    """The batch cop task: ``_device_path`` over many regions of one
    order-blind partial-aggregation DAG (copr/client.py decides that), with
    what does not depend on the region done once. Per region only the cheap
    part: the cached head entry, the ranges, the full-scan proof. A region
    that is not clean and small — no head entry in the cache (never read, or
    written since: a pending delta), a slot still to decode, more than
    MAX_RANGES ranges, more rows than one block, an entry that is not
    complete — leaves before anything is bound and takes ``_device_path`` as
    a task of its own, beside this one. So does every region if the batch
    fails as a whole: each then meets the fault alone, under the client's
    re-split / degrade policy. Partials stay per region; the root's final
    aggregation merges them as it merges tasks.

    What all of that comes to — who stays, the bound DAG's kernels, the
    regions' device arrays, call by call — is the same the next time the same
    DAG meets the same regions unchanged, so it is kept beside the column cache
    (``colcache.ResolvedTask``) under the DAG's fingerprint, the table and the
    batch's regions and ranges: a task that finds it, and finds every entry
    still the head it was (``ColumnCache.resolved``), sends the kept calls
    again (``_run_resolved``) and derives nothing."""
    scan = dag.executors[0]
    ph.to("bind")
    cache = cache_for(store)
    key = (dag.fingerprint(), scan.table_id, tuple([(region.region_id, *ranges) for region, ranges in batch]))
    task, how = cache.resolved(key, batch, read_ts)
    ph.note(resolved=how)
    _metrics.COP_TASK_RESOLVED.inc(how=how)
    det = _ed.current_cop()
    if det is not None:
        det.resolved = how
    if task is not None:
        return _run_resolved(ph, dag, scan, cache, key, task, batch, warn, leave)
    schema = RowSchema(scan.storage_schema)
    slots = [c.column_id for c in scan.columns if not c.is_handle]
    parts, kept, left = [], [], []
    # the resolved task's: place, key in the cache and entry of every region that stays,
    # place and key of every one that leaves; nothing is kept once a region leaves that
    # has a head (a slot to decode, an entry not complete): it may stay the next time
    served, gone, keepable = [], [], True
    for at, (region, ranges) in enumerate(batch):
        part = head = None
        try:
            head = cache.head(region, scan.table_id, read_ts) if len(ranges) <= MAX_RANGES else None
            if head is not None and all(s in head.cols for s in slots):
                # the head's quick path: no build, no decode, no merge; it counts the read
                entry, delta = cache.get_split(region, scan.table_id, schema, slots, read_ts)
                if (delta is None or not delta.n) and entry.complete and entry.n <= _BLOCK:
                    part = _Part(entry, region, _ranges_array(ranges, scan.table_id), None)
        except Exception:  # noqa: BLE001 — the region's own task meets it again, under the client's policy
            part = None
        if part is None:
            left.append((region, ranges))
            gone.append((at, (region.region_id, scan.table_id)))
            keepable = keepable and head is None
        else:
            parts.append(part)
            kept.append((region, ranges))
            served.append((at, (region.region_id, scan.table_id), part.entry))
    if left:
        leave(left)
    if not parts:
        return None
    resolving = None
    try:
        bound = Binder(cache, scan.table_id, scan.columns, _BinderView(*(p.entry for p in parts))).bind_dag(dag)
        if keepable:
            resolving = ResolvedTask(tuple(served), tuple(gone), cache.epoch, 8 * max(1, len(slots)))
        out = _exec_single(ph, store, dag, bound, scan, cache, parts, warn, resolving)
    except Exception as e:  # noqa: BLE001 — as above, for all of them
        return _batch_failed(cache, key, kept, e, leave)
    if resolving is not None and resolving.epoch == cache.epoch:
        cache.resolve(key, resolving)
        if not _DEVICE_LRU.holds(resolving.keys):
            cache.unresolve(key, resolving)  # the LRU let go of an array while the task was on its way
    if det is not None:
        det.regions = len(parts)
    return out


def _batch_failed(cache, key: tuple, kept: list, e: Exception, leave) -> None:
    """A batch that fails as a whole: its resolved task goes, every region
    meets the fault again in a task of its own."""
    cache.unresolve(key)
    lg = _ev.on(_ev.WARN)
    if lg is not None:
        lg.emit(_ev.WARN, "copr", "batch_fallback", regions=len(kept), cause=f"{type(e).__name__}: {e}")
    leave(kept)
    return None


def _run_resolved(ph: _Phases, dag: dagpb.DAGRequest, scan, cache, key: tuple, task: ResolvedTask, batch: list, warn, leave):
    """A batch task answered from its resolved task: the regions that left
    leave again, the kept calls are sent as they are. What a task owes besides
    its answer is paid as ``_batch_path`` + ``_exec_single`` pay it: every
    region's read counted (the heatmap's), the arrays' use (one touch for the
    task), the sidecar's device-cache hits by the arrays reused. The results of
    a region that overflowed its group cap come twice, the re-run's last: the
    last stands."""
    if task.left:
        leave([batch[at] for at, _ in task.left])
    det = _ed.current_cop()
    try:
        ph.to("inputs")
        cache.note_served(task)
        _DEVICE_LRU.touch(task)
        if det is not None:
            det.dev_cache_hits += len(task.keys)
        _metrics.DEVICE_CACHE.inc(len(task.keys), result="hit")
        results: list = [None] * len(task.kept)
        for (i, kernel), got in zip(task.answered, _run_all(ph, task.calls)):
            results[i] = (*got, kernel)
        out = _decode(ph, results, dag, cache, scan, warn)
    except Exception as e:  # noqa: BLE001 — as a batch that fails on its first way
        return _batch_failed(cache, key, [batch[at] for at, _, _ in task.kept], e, leave)
    if det is not None:
        det.regions = len(task.kept)
    return out


def _device_path(ph: _Phases, store: MemStore, dag: dagpb.DAGRequest, region: Region, ranges: list[KeyRange], read_ts: int, warn):
    scan = dag.executors[0]
    if scan.desc:
        # descending scans are order-sensitive row streams — the sorted-batch
        # kernel has no cheap equivalent; delegate to the host engine
        return host_execute_dag(store, dag, region, ranges, read_ts, warn)
    if len(ranges) > MAX_RANGES:
        # many-range tasks are point-lookup workloads (index joins, batch
        # gets): a covering-span fallback would degrade to a full scan, and
        # the host engine slices exactly the requested handles from the same
        # column cache — the TiKV-serves-point-reads role
        return host_execute_dag(store, dag, region, ranges, read_ts, warn)
    ph.to("bind")
    schema = RowSchema(scan.storage_schema)
    slots = [c.column_id for c in scan.columns if not c.is_handle]
    cache = cache_for(store)
    # base stays pinned across DML; committed changes ride as a bounded
    # delta operand the kernel folds in (mask superseded + union fresh)
    entry, delta = cache.get_split(region, scan.table_id, schema, slots, read_ts)
    if delta is not None and not delta.n:
        delta = None

    has_window = any(ex.tp == dagpb.WINDOW for ex in dag.executors[1:])
    if has_window and delta is not None:
        # window tie-breaks are positional inside window_core — fold the
        # delta into the base NOW instead of shipping the operand: the merge
        # carries clean-block device identities, so only dirty blocks
        # re-ship (a materialized view would re-key and evict them all)
        entry = cache.merge_now(region, scan.table_id, schema, slots, read_ts)
        delta = None
    if delta is not None:
        det = _ed.current_cop()
        if det is None:
            ph.note(delta_rows=delta.n)
        else:
            det.delta_rows += delta.n
            ph.note(delta_rows=delta.n, delta_read=det.delta_read)

    binder_entry = entry if delta is None else _BinderView(entry, delta)
    binder = Binder(cache, scan.table_id, scan.columns, binder_entry)
    bound = binder.bind_dag(dag)
    rarr = _ranges_array(ranges, scan.table_id)

    if has_window:
        _window_pack_guard(bound, entry.n)
    if has_window and entry.n > _BLOCK:
        # windows need every row of a partition in one computation — blocks
        # cannot run independently; fuse them into one multi-block program
        return _exec_fused_blocks(ph, store, dag, bound, scan, cache, entry, region, rarr, warn)
    if _should_fuse_agg(dag, entry):
        # aggregations over big tables fuse every block into ONE kernel
        # dispatch: the per-dispatch cost (~0.6 ms dispatch+sync on a v5e,
        # chip_smoke.py) would otherwise multiply by the block count, and a
        # single program needs no partial-merge pass over block results
        return _exec_fused_blocks(ph, store, dag, bound, scan, cache, entry, region, rarr, warn, delta)
    agg_complete = any(
        ex.tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG) and ex.agg_mode == dagpb.AGG_COMPLETE
        for ex in dag.executors[1:]
    )
    if entry.n > _BLOCK and not agg_complete:
        return _exec_blocks(ph, store, dag, bound, scan, cache, entry, region, rarr, warn, delta)
    return _exec_single(ph, store, dag, bound, scan, cache, [_Part(entry, region, rarr, delta)], warn)


def _grown_cap(agg_cap: int, ngroups: int, ceiling: int) -> int:
    """The group cap to retry with after an overflow. The kernel reports the
    TRUE group count, so jump straight to the power of two that holds it
    instead of compiling and re-running every ×4 step on the way: 400k groups
    took five kernels (4,096 … 1,048,576), each a ~30 s compile on a v5e and
    all five re-run on every execution; now two."""
    return min(max(agg_cap * 4, bucket_size(ngroups)), ceiling)


def _single_device_inputs(store, scan, cache, entry, region, n_pad, keys: list | None = None):
    """(handles_dev, cols_dev) for the single-kernel path, via the same LRU
    identities as repeat queries; the identities asked for go on ``keys``."""
    epoch = cache.epoch
    cacheable = entry.complete
    ver = entry.vtag_span(0, entry.n)
    hkey = (store.nonce, region.region_id, scan.table_id, -1, "s", ver, epoch, n_pad)
    handles_pair = _device_put_col(
        hkey, lambda: (entry.handles, np.ones(entry.n, bool)), n_pad, cacheable
    )
    cols_dev = []
    for c in scan.columns:
        if c.is_handle:
            cols_dev.append(handles_pair)
        else:
            ckey = (store.nonce, region.region_id, scan.table_id, c.column_id, "s", ver, epoch, n_pad)

            def mk(cid=c.column_id):
                data, valid = entry.cols[cid]
                return _narrowed(entry, cid, data), valid

            cols_dev.append(_device_put_col(ckey, mk, n_pad, cacheable))
            if keys is not None:
                keys.append(ckey)
    if keys is not None:
        keys.append(hkey)
    return handles_pair[0], cols_dev


def _map_counts(k: int, n_pad: int) -> list[int]:
    """How ``k`` regions of one kernel key go to the device: the region count
    of each call, padded up the ladder — ``[48]`` for 46, 47 or 48 regions,
    ``[64, 64, 64, 48]`` for 240 of 262,144 padded rows, ``[1]`` for one. The
    ladder is the multiples of ``_MAP_STEP`` up to ``_MAP_ROWS`` padded rows a
    call (a mapped program stacks its regions in HBM while it runs): a region
    split or merge moves a group along it a step at most, so it rarely means a
    compile on the query path, and a padding slot costs a region's device
    time: at most 7 in a call. A remainder of one, and regions too large for
    a step to fit, take the single-region program."""
    top = _MAP_ROWS // n_pad // _MAP_STEP * _MAP_STEP
    if k == 1 or not top:
        return [1] * k
    counts = [top] * (k // top)
    rest = k % top
    if rest:
        counts.append(1 if rest == 1 else -(-rest // _MAP_STEP) * _MAP_STEP)
    return counts


def _exec_single(ph, store, dag, bound, scan, cache, parts: list[_Part], warn=None, keep=None) -> Chunk:
    """Regions of at most one block each (or COMPLETE-mode aggs): one padded
    array a region. A task is one region, or the many of a batch
    (``_batch_path``; ``bound`` then holds for all of them). The regions that
    share a kernel key — padded shape, full-scan proof, group cap, and each
    lane's dtype and whether it holds a NULL: what must be equal to stack them
    — are a GROUP, answered by one call of one MAPPED program
    (``dag_kernel.get_kernel``'s ``m``: the single-region kernel run once a
    region along a leading axis) and one stacked result; ``_map_counts`` pads
    the count up a short ladder, a padding slot being a resident region's
    arrays again with ``nvalid`` 0 (no H2D, no new HBM), its result dropped
    before the decode. A group of one region — every task that is not a batch,
    a region read through its delta, an overflow's re-run, the odd region of
    another size — calls today's single-region program: there is no second
    path for it. Every call is dispatched before the ONE fetch; partials stay
    one a region, and decode into one Chunk, region after region. ``keep``, a
    batch's ``colcache.ResolvedTask``, takes what was sent and what it holds: the
    calls of every round (an overflow's re-run after the call it overflowed in),
    which result each answers, the device LRU's keys."""
    needs_agg = kernel_needs_agg(bound)
    ph.to("inputs")
    runs = []  # a region: [its kernel key (n_pad, full_scan, delta_cap, agg_cap, lanes), the arguments of its own]
    for entry, region, rarr, delta in parts:
        n_pad = bucket_size(max(entry.n, 1))
        handles_dev, cols_dev = _single_device_inputs(store, scan, cache, entry, region, n_pad, None if keep is None else keep.keys)
        dcap = 0
        dargs = ()
        if delta is not None:
            dcap = _delta_cap()
            dh, dcols, dtomb = _delta_device_inputs(store, scan, cache, delta, region)
            dargs = (dh, dcols, dtomb, _delta_counts(delta.n, 0, delta.n))
        agg_cap = min(_DEFAULT_AGG_CAP, n_pad + dcap) if needs_agg else _DEFAULT_AGG_CAP
        # _narrowed picks int32 or int64 a region: lanes stack only at one width;
        # a lane with no NULL in it (the handle's never has one) is stacked without its validity
        lanes = tuple((d.dtype, c.is_handle or entry.all_valid(c.column_id)) for c, (d, _) in zip(scan.columns, cols_dev))
        key = (n_pad, _covers_all(rarr, entry, delta), dcap, agg_cap, lanes)
        runs.append([key, (handles_dev, tuple(cols_dev), rarr, entry.n, dargs)])
    results: list = [None] * len(runs)
    todo = list(range(len(runs)))
    while todo:
        ph.to("bind")
        groups: dict = {}
        for i in todo:
            groups.setdefault(runs[i][0], []).append(i)
        sends = []  # [kernel, its key, the regions it answers]; one kernel lookup (a fingerprint of the DAG) per key and count, not per region
        for key, members in groups.items():
            n_pad, fs, dcap, agg_cap, _ = key
            # only clean regions stack: one read through its delta comes alone, and goes alone
            counts = [1] * len(members) if dcap else _map_counts(len(members), n_pad)
            at = 0
            for m in counts:
                sends.append((get_kernel(bound, n_pad, agg_cap, full_scan=fs, delta_cap=dcap, m=m), key, members[at : at + m]))
                at += m
        ph.to("inputs")
        calls = [(kernel, _call_args(kernel, [runs[i][1] for i in live], key), live) for kernel, key, live in sends]
        answered = [(i, kernel) for kernel, _, live in calls for i in live]
        if keep is not None:
            keep.calls += calls
            keep.answered += answered
        over = []
        for (i, kernel), got in zip(answered, _run_all(ph, calls)):
            ngroups = int(got[0][0, 1])
            if ngroups <= kernel.agg_cap:
                results[i] = (*got, kernel)
                continue
            # an overflow re-runs that region alone, at the cap that holds it
            n_pad, fs, dcap, agg_cap, lanes = runs[i][0]
            if agg_cap >= n_pad + dcap:
                # more groups than rows cannot happen; n_pad cap always fits
                raise RuntimeError("aggregation group overflow beyond row count")
            runs[i][0] = (n_pad, fs, dcap, _grown_cap(agg_cap, ngroups, n_pad + dcap), lanes)
            over.append(i)
        todo = over
    return _decode(ph, results, dag, cache, scan, warn)


def _call_args(kernel, regions: list, key: tuple) -> tuple:
    """The arguments of one program call over ``regions`` (each ``(handles,
    cols, rarr, n, delta args)``) of the kernel key ``key``: a region's own for
    the single-region program; for a mapped one the regions' arrays slot by
    slot — less those it need not read: the handles of a full scan, the
    validity of a lane without a NULL — its slots past the last region filled
    with the first region's arrays and ``nvalid`` 0. Ranges and counts are
    device-resident, cached by value."""
    import jax.numpy as jnp

    if kernel.m == 1:
        ((handles, cols, rarr, n, dargs),) = regions
        return (handles, cols, _device_ranges(rarr), _device_nvalid(n), *dargs)
    _, full_scan, _, _, lanes = key
    slots = regions + regions[:1] * (kernel.m - len(regions))
    counts = tuple(r[3] for r in regions) + (0,) * (kernel.m - len(regions))
    return (
        tuple(
            (None if full_scan else handles, tuple((d, None if no_null else v) for (d, v), (_, no_null) in zip(cols, lanes)))
            for handles, cols, *_ in slots
        ),
        _device_ranges(np.stack([r[2] for r in slots])),
        _misc_cached(_NVALID_DEV, ("m", counts), lambda: jnp.asarray(np.array(counts, dtype=np.int64))),
    )


def _run_all(ph, calls: list) -> list:
    """Dispatch every ``(kernel, args, live)`` without waiting, then fetch:
    one ``(buf, fbuf, count)`` on the host for each region answered — one a
    call of a single-region program; ``len(live)`` a call of a mapped one,
    unstacked from its ``(m, ...)`` buffers, the padding slots' dropped. ONE
    device→host round trip for them all: device_get batches every buffer of
    every packed result into a single transfer — one call a result, or two
    sequential np.asarray calls, would pay the round trip again and again.
    Exception: large rows-kind buffers spend a second tiny RTT on the meta row
    and transfer only the live slice (_probe_slice_rows)."""
    import jax

    ph.to("dispatch", kernel=calls[0][0].family, regions=len(calls), pad_slots=sum(kernel.m - len(live) for kernel, _, live in calls if kernel.m > 1))
    packed = [kernel.fn(*args) for kernel, args, _ in calls]
    _count_programs(calls)
    ph.to("fetch")
    for i, (kernel, _, _) in enumerate(calls):
        if kernel.kind == "rows" and kernel.out_n > 65536 and kernel.m == 1:
            _, (packed[i],) = _probe_slice_rows([packed[i]], kernel)
    fetched = jax.device_get(packed)
    # the device results end HERE, inside the phase that waited for them, and
    # under a span of its own: dropping them lets go of the interpreter lock, and
    # a task pays to get that back (at the return it was nobody's time)
    with _tracing.region("exec.release"):
        del packed
    out = []
    for (kernel, _, live), got in zip(calls, fetched):
        buf, fbuf = got if isinstance(got, tuple) else (got, None)
        if kernel.m == 1:
            out.append((buf, fbuf, int(buf[0, 0])))
        else:
            out.extend((buf[r], None if fbuf is None else fbuf[r], int(buf[r, 0, 0])) for r in range(len(live)))
    return out


def _count_programs(calls: list) -> None:
    """Program calls a task sent: the sidecar's ``programs`` and the counter,
    by form (``mapped``: one call, many regions)."""
    mapped = sum(1 for kernel, _, _ in calls if kernel.m > 1)
    det = _ed.current_cop()
    if det is not None:
        det.programs += len(calls)
    if mapped:
        _metrics.COP_PROGRAMS.inc(mapped, form="mapped")
    if len(calls) > mapped:
        _metrics.COP_PROGRAMS.inc(len(calls) - mapped, form="single")


def _decode(ph, results: list, dag, cache, scan, warn) -> Chunk:
    """``[(buf, fbuf, count, kernel), ...]`` → one Chunk, in that order."""
    ph.to("decode")
    for buf, _, _, kernel in results:
        _emit_kernel_warnings(buf, kernel, warn)
    return _chunk_from_bufs(results, dag, cache, scan)


def _exec_blocks(ph, store, dag, bound, scan, cache, entry, region, rarr, warn=None, delta=None):
    """Large regions: fixed-shape device blocks, one compile per DAG.

    Aggs/TopN dispatch every block asynchronously and stack the packed
    buffers on-device → one transfer; LIMIT-last DAGs stream blocks lazily
    with early exit (coprocessor paging).
    """
    n = entry.n
    bounds = _block_bounds(n)
    cacheable = entry.complete

    def block_inputs(bi: int):
        # on-demand (LRU-cached) puts: the LIMIT paging loop's early exit
        # also skips the H2D transfers of blocks it never reads, which
        # dominate cold-table cost
        lo, hi = bounds[bi]
        return _block_device_inputs(store, scan, cache, entry, region, bi, lo, hi, cacheable)

    ph.to("inputs")
    rarr_j = _device_ranges(rarr)
    nvalids = [hi - lo for lo, hi in bounds]
    limit_last = bool(dag.executors[1:]) and dag.executors[-1].tp == dagpb.LIMIT

    dcap = 0
    dinp = None
    dcuts = None
    if delta is not None:
        dcap = _delta_cap()
        dinp = _delta_device_inputs(store, scan, cache, delta, region)
        # route each delta row to the block whose handle span contains it:
        # delta handles are sorted, so block bi's union rows are exactly the
        # contiguous slice [dcuts[bi], dcuts[bi+1]) (block 0 reaches back to
        # -inf, the last block forward to +inf) — block outputs then stay
        # globally handle-ordered, matching the host engine's scan order
        starts = [int(entry.handles[lo]) for lo, _hi in bounds]
        dcuts = np.searchsorted(delta.handles, np.asarray(starts[1:], dtype=np.int64))
        dcuts = [0] + [int(c) for c in dcuts] + [delta.n]

    agg_cap = _DEFAULT_AGG_CAP
    fs = _covers_all(rarr, entry, delta)
    while True:
        ph.to("bind")
        kernel = get_kernel(bound, _BLOCK, agg_cap, full_scan=fs, delta_cap=dcap)

        def run_block(bi: int):
            ph.to("inputs")
            handles_dev, cols_dev = block_inputs(bi)
            args = (handles_dev, cols_dev, rarr_j, _device_nvalid(nvalids[bi]))
            if dinp is not None:
                # every block masks superseded base rows; each delta row
                # unions into exactly the block owning its handle span, so rows
                # never double-count and block outputs concat in handle order
                args += (*dinp, _delta_counts(delta.n, dcuts[bi], dcuts[bi + 1]))
            ph.to("dispatch", kernel=kernel.family)
            _count_programs([(kernel, args, None)])
            return kernel.fn(*args)

        if limit_last:
            out = _blocks_paged_limit(ph, run_block, len(bounds), kernel, dag, cache, scan, warn)
        else:
            out = _blocks_stacked(ph, run_block, len(bounds), kernel, dag, cache, scan, warn)
        if out is None:  # agg overflow in some block
            agg_cap = min(agg_cap * 4, _BLOCK + dcap)
            continue
        return out


def _blocks_stacked(ph, run_block, nb: int, kernel, dag, cache, scan, warn=None):
    """Dispatch all blocks async; stack results on-device; one transfer.
    Returns None on agg-cap overflow (caller re-runs with a bigger cap)."""
    import jax
    import jax.numpy as jnp

    packed = [run_block(bi) for bi in range(nb)]  # async dispatches
    tup = isinstance(packed[0], tuple)
    ph.to("fetch")
    if kernel.kind == "rows" and kernel.out_n > 65536:
        # rows-kind: counts first (one tiny transfer), then live slices only
        counts, gets = _probe_slice_rows(packed, kernel)
        fetched = jax.device_get(gets)
        with _tracing.region("exec.release"):
            del packed, gets
        results = [(*(got if tup else (got, None)), cnt, kernel) for cnt, got in zip(counts, fetched)]
        return _decode(ph, results, dag, cache, scan, warn)
    stacked = jnp.stack([p[0] if tup else p for p in packed])
    if tup:
        stacked = (stacked, jnp.stack([p[1] for p in packed]))
        bi_all, bf_all = jax.device_get(stacked)
    else:
        bi_all = jax.device_get(stacked)
        bf_all = None
    with _tracing.region("exec.release"):
        del packed, stacked
    if kernel.kind == "agg" and any(int(b[0, 1]) > kernel.agg_cap for b in bi_all):
        return None
    results = [(bi_all[b], bf_all[b] if bf_all is not None else None, int(bi_all[b][0, 0]), kernel) for b in range(nb)]
    return _decode(ph, results, dag, cache, scan, warn)


def _exec_fused_blocks(ph, store, dag, bound, scan, cache, entry, region, rarr, warn=None, delta=None):
    """Whole-region DAGs (windows, aggregations) over large regions: ONE
    fused multi-block program, one dispatch.

    Windows need every row of a partition in the same computation (ref: the
    Shuffle repartitioner's partition isolation, shuffle.go:86); aggregations
    fuse to amortize the per-dispatch device-link cost and skip the partial
    merge. The fused kernel concatenates the per-block device arrays (same
    LRU identities as _exec_blocks — warm tables pay no new H2D transfer).
    For windows the binder's sort bounds make the region sort a single int64
    argsort; unpackable shapes raised UnsupportedForDevice upstream."""
    ph.to("inputs")
    handles_blocks, cols_blocks, nvalids, nb = _fused_block_inputs(store, scan, cache, entry, region)
    n_total = nb * _BLOCK
    dcap = 0
    dargs = ()
    if delta is not None:
        dcap = _delta_cap()
        dh, dcols, dtomb = _delta_device_inputs(store, scan, cache, delta, region)
        dargs = (dh, dcols, dtomb, _delta_counts(delta.n, 0, delta.n))
    args = (tuple(handles_blocks), tuple(tuple(cb) for cb in cols_blocks), _device_ranges(rarr), nvalids, *dargs)

    ph.to("bind")
    agg_cap = min(_DEFAULT_AGG_CAP, n_total + dcap) if kernel_needs_agg(bound) else _DEFAULT_AGG_CAP
    fs = _covers_all(rarr, entry, delta)
    while True:
        kernel = get_kernel(bound, _BLOCK, agg_cap, nb=nb, full_scan=fs, delta_cap=dcap)
        ((buf, fbuf, count),) = _run_all(ph, [(kernel, args, None)])
        if int(buf[0, 1]) <= kernel.agg_cap:
            break
        if agg_cap >= n_total + dcap:
            raise RuntimeError("aggregation group overflow beyond row count")
        agg_cap = _grown_cap(agg_cap, int(buf[0, 1]), n_total + dcap)
        ph.to("bind")
    return _decode(ph, [(buf, fbuf, count, kernel)], dag, cache, scan, warn)


def _blocks_paged_limit(ph, run_block, nb: int, kernel, dag, cache, scan, warn=None):
    """LIMIT-last: stream blocks with grow-on-demand lookahead, stop once the
    limit is satisfiable (ref: paging page-size growth, copr/coprocessor.go:368)."""
    import jax

    limit = dag.executors[-1].limit
    results = []
    got = 0
    window = 1
    bi = 0
    # `not results` keeps LIMIT 0 well-formed: one empty-count block result
    # still carries the output schema for chunk assembly
    while bi < nb and (got < limit or not results):
        batch = list(range(bi, min(bi + window, nb)))
        packed = [run_block(i) for i in batch]
        tup = isinstance(packed[0], tuple)
        ph.to("fetch")
        if kernel.out_n > 65536:  # LIMIT-last DAGs are always rows-kind
            _, packed = _probe_slice_rows(packed, kernel)
        fetched = jax.device_get(packed)
        with _tracing.region("exec.release"):
            del packed
        for got_b in fetched:
            buf, fbuf = got_b if tup else (got_b, None)
            results.append((buf, fbuf, int(buf[0, 0]), kernel))
            got += results[-1][2]
        bi += len(batch)
        window = min(window * 2, 8)
    return _decode(ph, results, dag, cache, scan, warn)


def _chunk_from_bufs(results: list, dag, cache, scan) -> Chunk:
    """Packed kernel buffers ``[(buf, fbuf, count, kernel), ...]`` — one a
    region or block, all of one DAG — → ONE Chunk (each trimmed to its count,
    one after another; dictionaries re-attached). The output schema and the
    dictionary slots are worked out once for them all."""
    det = _ed.current_cop()
    if det is not None:
        nb = sum(
            int(getattr(buf, "nbytes", 0)) + (int(getattr(fbuf, "nbytes", 0)) if fbuf is not None else 0)
            for buf, fbuf, _, _ in results
        )
        det.d2h_bytes += nb
        _metrics.DEVICE_TRANSFER.inc(nb, dir="d2h")
    # assemble chunk: output schema comes from the *unbound* DAG (string
    # columns keep their dictionaries)
    out_fts = output_ftypes(dag)
    offsets = dag.output_offsets or list(range(len(out_fts)))
    cols = []
    for lane, off in zip(range(len(results[0][3].lane_loc)), offsets):
        datas, valids = [], []
        for buf, fbuf, count, kernel in results:
            which, idx = kernel.lane_loc[lane]
            datas.append((fbuf if which == "f" else buf)[idx][:count])
            valids.append(buf[kernel.valid_loc[lane]][:count])
        d = datas[0] if len(datas) == 1 else np.concatenate(datas)
        v = valids[0] if len(valids) == 1 else np.concatenate(valids)
        ft = out_fts[off]
        dic = None
        if ft.kind == TypeKind.STRING:
            slot = string_slot_for_output(dag, off)
            dic = cache.dictionary(scan.table_id, slot) if slot is not None else None
            d = d.astype(np.int32)
        elif ft.kind == TypeKind.FLOAT:
            d = d.astype(np.float64)
        else:
            d = d.astype(np.int64)
        cols.append(Column(d, v.astype(bool), ft, dic))
    return Chunk(cols)


def _window_pack_guard(bound: dagpb.DAGRequest, n: int) -> None:
    """Reject device windows whose sort can't pack into one int64 key at a
    scale where the multi-lane stable-sort chain is pathological (minutes of
    x64-emulated compile past ~1M rows) — the host sweep takes over."""
    from tidb_tpu.ops.window_core import packed_bits

    if n <= (1 << 20):
        return
    n_total = bucket_size(max(n, 1)) if n <= _BLOCK else -(-n // _BLOCK) * _BLOCK
    for ex in bound.executors[1:]:
        if ex.tp == dagpb.WINDOW:
            sb = [tuple(b) if b is not None else None for b in ex.sort_bounds] or None
            if packed_bits(sb, n_total) is None:
                raise UnsupportedForDevice("window sort not packable at this scale")


def kernel_needs_agg(dag: dagpb.DAGRequest) -> bool:
    return any(ex.tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG) for ex in dag.executors)


def output_ftypes(dag: dagpb.DAGRequest) -> list[FieldType]:
    """Schema of the last executor's output (before output_offsets)."""
    from tidb_tpu.expression.expr import expr_from_pb, AggDesc, _ft_from_pb

    scan = dag.executors[0]
    fts = [c.ftype for c in scan.columns]
    for ex in dag.executors[1:]:
        if ex.tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG):
            out = []
            for a_pb in ex.aggs:
                a = AggDesc.from_pb(a_pb)
                if ex.agg_mode == dagpb.AGG_COMPLETE:
                    out.append(a.ftype)
                else:
                    for pk in a.partial_kinds:
                        if pk == "count":
                            out.append(bigint_type(nullable=False))
                        elif pk == "sum":
                            out.append(AggDesc("sum", a.arg).ftype)
                        elif pk == "sumsq":
                            from tidb_tpu.types.field_type import double_type

                            out.append(double_type())
                        elif pk in ("bit_and", "bit_or", "bit_xor"):
                            out.append(bigint_type(nullable=False))
                        else:
                            out.append(a.arg.ftype if a.arg is not None else bigint_type())
            for g in ex.group_by:
                out.append(expr_from_pb(g).ftype)
            if getattr(ex, "rollup", False):
                out.extend(bigint_type(nullable=False) for _ in ex.group_by)
            fts = out
        elif ex.tp == dagpb.PROJECTION:
            fts = [expr_from_pb(e).ftype for e in ex.exprs]
        elif ex.tp == dagpb.WINDOW:
            fts = fts + [_ft_from_pb(f["ft"]) for f in ex.win_funcs]
    return fts


def string_slot_for_output(dag: dagpb.DAGRequest, offset: int):
    """Find the storage slot whose dictionary backs output column ``offset``
    (only direct ColumnRef passthroughs keep dictionaries)."""
    scan = dag.executors[0]
    # walk the executor chain tracking provenance of each output offset
    prov: list = list(range(len(scan.columns)))  # scan offset → scan offset
    for ex in dag.executors[1:]:
        if ex.tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG):
            out = []
            for a in ex.aggs:
                n_lanes = len(AggFromPb(a).partial_kinds) if ex.agg_mode != dagpb.AGG_COMPLETE else 1
                arg = a.get("arg")
                src = None
                if a["name"] in ("min", "max", "first_row") and arg is not None and arg.get("tp") == "col":
                    src = prov[arg["idx"]] if arg["idx"] < len(prov) else None
                out.extend([src] * n_lanes)
            for g in ex.group_by:
                out.append(prov[g["idx"]] if g.get("tp") == "col" and g["idx"] < len(prov) else None)
            if getattr(ex, "rollup", False):
                out.extend([None] * len(ex.group_by))  # GROUPING flags: ints
            prov = out
        elif ex.tp == dagpb.PROJECTION:
            out = []
            for e in ex.exprs:
                out.append(prov[e["idx"]] if e.get("tp") == "col" and e["idx"] < len(prov) else None)
            prov = out
        elif ex.tp == dagpb.WINDOW:
            # window outputs carry no dictionaries (string args are host-only)
            prov = prov + [None] * len(ex.win_funcs)
    src = prov[offset] if offset < len(prov) else None
    if src is None:
        return None
    return scan.columns[src].column_id


def AggFromPb(pb):
    from tidb_tpu.expression.expr import AggDesc

    return AggDesc.from_pb(pb)
