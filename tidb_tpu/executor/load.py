"""Bulk columnar loader — the IMPORT INTO / lightning analog.

Reference parity: pkg/lightning local backend + IMPORT INTO (disttask) —
bypasses per-statement SQL overhead and writes encoded rows straight through
a transaction in batches. Used by the benchmark's load, the smoke and bootstrap; IMPORT INTO is its
SQL surface.
"""

from __future__ import annotations

import time
from typing import Sequence

import numpy as np

from tidb_tpu.executor.write import index_entry, to_physical
from tidb_tpu.kv import tablecodec
from tidb_tpu.kv.rowcodec import RowSchema, encode_row
from tidb_tpu.session.session import DB
from tidb_tpu.types import TypeKind
from tidb_tpu.utils import metrics as _metrics
from tidb_tpu.utils import tracing as _tracing


def bulk_load(db: DB, table_name: str, columns: Sequence[Sequence], db_name: str = "test", batch: int = 200_000, handle_base: int | None = None, on_existing: str | None = None) -> int:
    """Load columnar data (one sequence per table column, logical values).
    Handles come from the int PK column when pk_is_handle, else autoid.

    ``handle_base`` pins the autoid handles to a pre-reserved range so a
    re-run writes the SAME keys; ``on_existing`` ('skip' for reserved ranges,
    'verify' for user-keyed PK tables) dedupes the columnar ingest against
    already-stable handles — together they make a restarted import subtask
    idempotent, and 'verify' surfaces duplicate-PK conflicts (ref: lightning
    checkpoint re-import + duplicate detection)."""
    t = db.catalog.table(db_name, table_name)
    ncols = len(t.columns)
    if len(columns) != ncols:
        raise ValueError(f"expected {ncols} columns, got {len(columns)}")
    n = len(columns[0])
    schema = RowSchema(t.storage_schema)

    phys_cols = []
    for c, vals in zip(t.columns, columns):
        k = c.ftype.kind
        if isinstance(vals, np.ndarray) and k in (TypeKind.INT, TypeKind.UINT, TypeKind.DECIMAL, TypeKind.DATE, TypeKind.DATETIME, TypeKind.DURATION):
            phys_cols.append(vals.astype(np.int64))
        elif isinstance(vals, np.ndarray) and k == TypeKind.FLOAT:
            phys_cols.append(vals.astype(np.float64))
        elif isinstance(vals, np.ndarray) and vals.dtype.kind == "S" and k == TypeKind.STRING:
            # fixed-width bytes: C-speed dictionary encode in the ingest path
            # (no NULLs — an S array cannot carry None; JSON stays on the
            # to_physical path for validation + canonical re-serialization)
            phys_cols.append(vals)
        else:
            phys_cols.append([to_physical(v, c.ftype) for v in vals])

    if t.partition is not None:
        return _bulk_load_partitioned(db, t, phys_cols, n, schema, handle_base=handle_base, on_existing=on_existing)

    if not any(idx.state != "delete_only" for idx in t.indexes):
        # columnar stable-layer ingest (TiFlash stable analog): columns go
        # into the store decoded and device-ready — no row encode at all.
        # Indexed tables keep the txn path below so index entries stay
        # transactional with their rows.
        if t.pk_is_handle:
            all_handles = np.ascontiguousarray(np.asarray(phys_cols[t.pk_offset], dtype=np.int64))
        elif handle_base is not None:
            all_handles = np.arange(handle_base, handle_base + n, dtype=np.int64)
        else:
            base = db.catalog.alloc_autoid(t.id, n)
            all_handles = np.arange(base, base + n, dtype=np.int64)
        _ingest_columnar(db, t.id, t, phys_cols, all_handles, n, schema, on_existing=on_existing)
        if t.pk_is_handle and n:
            db.catalog.rebase_autoid(t.id, int(all_handles.max()) + 1)
        return n

    loaded = 0
    i = 0
    while i < n:
        j = min(i + batch, n)
        txn = db.store.begin()
        if t.pk_is_handle:
            handles = phys_cols[t.pk_offset][i:j]
        elif handle_base is not None:
            handles = range(handle_base + i, handle_base + j)
        else:
            base = db.catalog.alloc_autoid(t.id, j - i)
            handles = range(base, base + (j - i))
        existing: dict = {}
        if on_existing == "verify":
            # duplicate-PK conflict surfacing on the txn path too — ONE
            # snapshot scan over the batch's handle span replaces a per-row
            # point get (which would be one RPC per row on a remote store)
            hs = list(handles)
            if hs:
                span = tablecodec.handle_range(t.id, int(min(hs)), int(max(hs)))
                snap = db.store.get_snapshot(db.store.current_ts())
                existing = dict(snap.scan(span))
        for r, h in zip(range(i, j), handles):
            vals = [phys_cols[c][r] for c in range(ncols)]
            rk = tablecodec.record_key(t.id, int(h))
            row = encode_row(schema, vals)
            if on_existing == "verify":
                prev = existing.get(rk)
                if prev is not None:
                    if prev == row:
                        continue  # idempotent re-run: identical row
                    raise ValueError(
                        f"duplicate key conflict on handle {int(h)}: existing row differs"
                    )
            txn.put(rk, row)
            for idx in t.indexes:
                if idx.state == "delete_only":
                    continue  # writes don't maintain delete-only indexes
                ik, iv = index_entry(t, idx, vals, int(h))
                txn.put(ik, iv)
        txn.commit()
        loaded += j - i
        i = j
    if t.pk_is_handle:
        mx = int(np.max(np.asarray(phys_cols[t.pk_offset]))) if n else 0
        db.catalog.rebase_autoid(t.id, mx + 1)
    return loaded


def _ingest_columnar(db: DB, physical_id: int, t, phys_cols, handles: np.ndarray, n: int, schema: RowSchema, on_existing: str | None = None) -> None:
    """Columns → StableBlock via MemStore.ingest_columnar. Strings dictionary-
    encode through np.unique (C-speed inverse) against the shared table
    dictionary, so blocks hand int32 code lanes straight to the device."""
    from tidb_tpu.copr.colcache import cache_for

    cache = cache_for(db.store)
    if physical_id != t.id:
        cache.set_table_alias(physical_id, t.id)
    cols: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    dicts: dict = {}
    string_slots: list[int] = []
    no_null = np.ones(n, dtype=bool)  # one for every column without a NULL: a block's lanes are never written to
    for pos, (c, vals) in enumerate(zip(t.columns, phys_cols)):
        k = c.ftype.kind
        if k in (TypeKind.STRING, TypeKind.JSON):
            string_slots.append(pos)
            dicts[pos] = cache.dictionary(t.id, pos)  # before ingest_lock
        elif isinstance(vals, np.ndarray):
            dt = np.float64 if k == TypeKind.FLOAT else np.int64
            cols[pos] = (vals.astype(dt, copy=False), no_null)
        else:
            valid = np.fromiter((v is not None for v in vals), dtype=bool, count=n)
            dt = np.float64 if k == TypeKind.FLOAT else np.int64
            data = np.fromiter(
                ((0 if v is None else v) for v in vals), dtype=dt, count=n
            )
            cols[pos] = (data, valid)
    # encode string codes and append the block under one cache lock: a
    # concurrent ensure_sorted_dict compaction between encode and ingest
    # would remap every block EXCEPT this not-yet-visible one
    with cache.ingest_lock(), _tracing.region("load.ingest", table=t.name, rows=n, strings=len(string_slots)) as span:
        t0 = time.perf_counter()
        known = sum(len(d) for d in dicts.values())
        for pos in string_slots:
            raw = phys_cols[pos]
            if isinstance(raw, np.ndarray) and raw.dtype.kind == "S":
                valid = no_null
                safe = raw
            else:
                arr = np.asarray(raw, dtype=object)
                valid = np.fromiter((v is not None for v in arr), dtype=bool, count=n)
                safe = np.where(valid, arr, b"") if n else arr
            dic = dicts[pos]
            if n:
                data = np.where(valid, dic.encode_many(safe), 0).astype(np.int32, copy=False)
            else:
                data = np.empty(0, np.int32)
            cols[pos] = (data, valid)
        t1 = time.perf_counter()
        db.store.ingest_columnar(physical_id, handles, cols, schema, dicts, on_existing=on_existing)
        t2 = time.perf_counter()
        if span is not None:
            regions = db.store.pd.regions_in_ranges([tablecodec.handle_range(physical_id, int(handles.min()), int(handles.max()))]) if n else []
            span.note(dict_new=sum(len(d) for d in dicts.values()) - known, regions=len(regions))
    _metrics.BULK_LOAD_ROWS.inc(n, table=t.name)
    _metrics.BULK_LOAD_SECONDS.inc(t1 - t0, phase="encode")
    _metrics.BULK_LOAD_SECONDS.inc(t2 - t1, phase="ingest")


def _bulk_load_partitioned(db: DB, t, phys_cols, n: int, schema: RowSchema, handle_base: int | None = None, on_existing: str | None = None) -> int:
    """Partition-routed load: rows group by partition id, then each group
    loads through the native ingest (or txn fallback) under its partition's
    physical table id."""
    p = t.partition
    raw = phys_cols[p.col_offset]
    if isinstance(raw, np.ndarray):
        pcol = raw.astype(np.int64, copy=False)
        null_mask = np.zeros(n, dtype=bool)
    else:
        null_mask = np.fromiter((v is None for v in raw), dtype=bool, count=n)
        pcol = np.fromiter((0 if v is None else int(v) for v in raw), dtype=np.int64, count=n)
    if p.type == "hash":
        pidx = pcol % len(p.defs)
    else:
        bounds = np.array(
            [d.less_than if d.less_than is not None else 2**62 for d in p.defs], dtype=np.int64
        )
        pidx = np.searchsorted(bounds, pcol, side="right")
        if int(pidx.max(initial=0)) >= len(p.defs):
            bad = int(pcol[pidx >= len(p.defs)][0])
            from tidb_tpu.catalog.catalog import CatalogError

            raise CatalogError(f"Table has no partition for value {bad}")
    pidx = np.where(null_mask, 0, pidx)  # NULL routes to the first partition

    if t.pk_is_handle:
        handles = np.ascontiguousarray(np.asarray(phys_cols[t.pk_offset], dtype=np.int64))
    elif handle_base is not None:
        handles = np.arange(handle_base, handle_base + n, dtype=np.int64)
    else:
        base = db.catalog.alloc_autoid(t.id, n)
        handles = np.arange(base, base + n, dtype=np.int64)

    from tidb_tpu.executor.write import index_entry

    has_index = any(idx.state != "delete_only" for idx in t.indexes)
    for k, d in enumerate(p.defs):
        sel = np.nonzero(pidx == k)[0]
        if len(sel) == 0:
            continue
        view = t.partition_view(d.id)
        sub_cols = [
            c[sel] if isinstance(c, np.ndarray) else [c[int(i)] for i in sel] for c in phys_cols
        ]
        sub_handles = handles[sel]
        if not has_index:
            _ingest_columnar(db, view.id, t, sub_cols, sub_handles, len(sel), schema, on_existing=on_existing)
            continue
        txn = db.store.begin()
        for j, h in enumerate(sub_handles):
            vals = [sub_cols[c][j] for c in range(len(t.columns))]
            txn.put(tablecodec.record_key(view.id, int(h)), encode_row(schema, vals))
            for idx in t.indexes:
                if idx.state == "delete_only":
                    continue
                ik, iv = index_entry(view, idx, vals, int(h))
                txn.put(ik, iv)
        txn.commit()
    if t.pk_is_handle and n:
        db.catalog.rebase_autoid(t.id, int(handles.max()) + 1)
    return n
