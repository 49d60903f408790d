"""Coprocessor client: region split → worker fan-out → streamed results.

Reference parity: pkg/store/copr/coprocessor.go (buildCopTasks :334 splits
ranges by region; copIterator :684 runs a worker pool with keep-order
channels; :87 CopClient.Send) and batch_coprocessor.go (one task a store
carrying many regions: ``CopClient``'s batch cop task). Concurrency here is a
thread pool, and what it buys is bounded by the interpreter lock: a task of the
``tpu`` engine is mostly Python (bind, input lookups, dispatch, decode), so
region tasks on the pool queue for the lock and pay a switch interval (~5 ms)
each time they let it go — at the dispatch, in ``device_get``, dropping the
device result (PERF.md §5, PR 26: 18 ms of wall for 3.2 ms of CPU a task,
eight in flight). The pool overlaps only what waits outside the interpreter:
the device's result, numpy's larger kernels, a store RPC. That is why a
statement's clean regions go to the engine as ONE task where the request
allows it. Inside that task the regions that share a padded shape are ONE call
of one MAPPED program (``ops/dag_kernel.get_kernel``'s ``m``: the
single-region kernel run once a region along a leading axis, the partials
returned stacked, one a region) — a group of one region, like every task that
is not a batch, calls the single-region program — so a statement is a few
milliseconds of Python and then a wait for the device OUTSIDE the interpreter
lock: what more connections can overlap.

The worker pool is ONE lazily-built process-wide executor (ref: the
reference's copIteratorWorker goroutines being cheap — spawning an OS thread
pool per request here cost ~1-2 ms of fixed tax on every multi-region
statement). Per-request concurrency is enforced by windowed submission, not
pool size: at most ``req.concurrency`` tasks of one request are in flight,
so a single request cannot monopolize the shared workers.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent import futures
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Iterator, Optional

from tidb_tpu.copr import dagpb
from tidb_tpu.kv.kv import KeyRange, KVError, RegionError, Request, RequestType, StoreType
from tidb_tpu.kv.memstore import MemStore, Region
from tidb_tpu.utils import execdetails as _ed
from tidb_tpu.utils import failpoint
from tidb_tpu.utils import tracing as _tracing
from tidb_tpu.utils.backoff import Backoffer, BackoffExhausted, boRegionMiss
from tidb_tpu.utils.chunk import Chunk

# engine registry: StoreType → DAG executor over one region
# (ref: kvstore.Register in cmd/tidb-server/main.go:399-409); populated
# lazily from concurrent cop tasks, so the populate takes a lock
_ENGINES: dict[StoreType, Callable] = {}
_ENGINES_MU = threading.Lock()


def _engines():
    if not _ENGINES:
        from tidb_tpu.copr import host_engine, tpu_engine

        # ONE dict.update installs both engines: a lock-free reader on the
        # fast path above must only ever observe {} or the full registry —
        # per-key inserts would let a concurrent cop task see one engine
        # and raise KeyError dispatching the other
        with _ENGINES_MU:
            if not _ENGINES:
                _ENGINES.update(
                    {
                        StoreType.HOST: host_engine.execute_dag,
                        StoreType.TPU: tpu_engine.execute_dag,
                    }
                )
    return _ENGINES


# -- shared cop worker pool -------------------------------------------------

_POOL_MU = threading.Lock()
_POOL: Optional[ThreadPoolExecutor] = None


def shared_cop_pool(concurrency_hint: int = 0) -> ThreadPoolExecutor:
    """The process-wide cop worker pool, built on first use. Sized from the
    first request's executor-concurrency hint (floored so concurrent
    sessions overlap even when the first request was narrow); per-request
    parallelism is throttled by submission windows, not pool size."""
    global _POOL
    with _POOL_MU:
        if _POOL is None:
            size = max(int(concurrency_hint), (os.cpu_count() or 4) * 2, 8)
            _POOL = ThreadPoolExecutor(max_workers=size, thread_name_prefix="cop-shared")
        return _POOL


def cop_pool_stats() -> tuple[int, int]:
    """→ (pool size, queued-task depth) of the shared cop pool — the
    queue-pressure signal the sys_snapshot health report ships fleet-wide
    (0, 0 when no cop request has built the pool yet). Reads executor
    internals (_work_queue), guarded so a stdlib change degrades to zeros
    rather than breaking introspection."""
    with _POOL_MU:
        pool = _POOL
    if pool is None:
        return 0, 0
    try:
        return pool._max_workers, pool._work_queue.qsize()
    except AttributeError:
        return 0, 0


def shutdown_shared_pool() -> None:
    """Idempotent teardown (tests / embedders); the pool lazily rebuilds on
    the next cop request."""
    global _POOL
    with _POOL_MU:
        pool, _POOL = _POOL, None
    if pool is not None:
        pool.shutdown(wait=True, cancel_futures=True)


def windowed_fanout(pool, run: Callable, items: list, window: int):
    """Run ``run(item)`` for every item on the shared pool with at most
    ``window`` of THIS request in flight, yielding results in item order.

    Work-conserving: ``window`` worker loops pull the next item the moment
    they finish one (a long task never idles the other workers, unlike
    consumer-driven admission), and the loops exit — releasing their pool
    slots — when the queue drains. Returns ``(iterator, cancel)``;
    ``cancel`` is idempotent and stops unstarted work. Shared by the
    embedded and remote cop clients."""
    from concurrent.futures import Future

    n = len(items)
    results = [Future() for _ in range(n)]
    mu = threading.Lock()
    state = {"next": 0, "closed": False}

    def worker():
        while True:
            with mu:
                if state["closed"] or state["next"] >= n:
                    return
                i = state["next"]
                state["next"] += 1
            try:
                results[i].set_result(run(items[i]))
            except BaseException as e:
                try:
                    results[i].set_exception(e)
                except futures.InvalidStateError:
                    pass  # consumer already cancelled this slot

    handles = [pool.submit(worker) for _ in range(min(window, n))]

    def cancel():
        with mu:
            state["closed"] = True
        for h in handles:
            h.cancel()
        for f in results:
            f.cancel()

    # a pool shutdown(cancel_futures=True) can cancel still-QUEUED worker
    # loops out from under us — without this hook the per-item result
    # futures would never resolve and the consumer would block forever
    def _handle_done(h):
        if h.cancelled():
            cancel()

    for h in handles:
        h.add_done_callback(_handle_done)

    def gen():
        try:
            for f in results:
                yield f.result()
        finally:
            cancel()

    return gen(), cancel


# -- cross-session point-get batcher ----------------------------------------


class PointGetBatcher:
    """Coalesces concurrent snapshot point reads against ONE store into
    batched multi-key lookups (ref: TiKV's batch-commands stream — client-go
    batch_client.go merges whatever is queued when the stream frees up).

    Opportunistic, zero added latency: the first arriving thread becomes the
    flusher and dispatches its keys immediately; readers that land while a
    flush is in flight queue up and ride the NEXT flush as one batch. N
    concurrent sessions therefore pay one RPC + one store dispatch instead
    of N, while an uncontended reader dispatches exactly as fast as before.
    A collection window (``window_s``; 0 as served) lets the flusher sleep
    sub-ms per round to grow batches at a latency cost.

    Outcomes are delivered PER KEY (bytes | None | exception): one session's
    locked key or dead shard never fails the strangers sharing its batch.
    The flusher runs on the submitting thread — no background threads to
    leak (conftest thread-hygiene stays clean)."""

    def __init__(self, store, window_s: float = 0.0):
        self._store = store
        self._mu = threading.Lock()
        self._pending: list = []  # (read_ts, key, Future)
        self._flushing = False
        self.window_s = window_s

    def get_many(self, read_ts: int, keys: list) -> list:
        """Submit this session's keys; returns values in key order, raising
        the first per-key error (same surface as sequential snapshot gets)."""
        from concurrent.futures import Future

        futs = [Future() for _ in keys]
        with self._mu:
            self._pending.extend((read_ts, k, f) for k, f in zip(keys, futs))
            lead = not self._flushing
            if lead:
                self._flushing = True
        if lead:
            self._drain()
        out = []
        for f in futs:
            v = f.result()
            if isinstance(v, BaseException):
                raise v
            out.append(v)
        return out

    def _lookup(self, pairs) -> list:
        bg = getattr(self._store, "snap_batch_get", None)
        if bg is not None:
            return bg(pairs)
        # store without a batched verb: per-key reads, per-key outcomes
        out = []
        for ts, k in pairs:
            try:
                out.append(self._store.get_snapshot(ts).get(k))
            except Exception as e:
                out.append(e)
        return out

    def _drain(self) -> None:
        from tidb_tpu.utils import metrics as _m

        while True:
            if self.window_s > 0:
                time.sleep(self.window_s)
            with self._mu:
                batch, self._pending = self._pending, []
                if not batch:
                    self._flushing = False
                    return
            try:
                vals = self._lookup([(ts, k) for ts, k, _ in batch])
            except BaseException as e:
                # whole-dispatch failure: every key in THIS flush shares it
                vals = [e] * len(batch)
            _m.POINTGET_BATCH.observe(len(batch))
            for (_, _, f), v in zip(batch, vals):
                f.set_result(v)


_BATCHER_MU = threading.Lock()


def point_batcher(store) -> PointGetBatcher:
    """The per-store batcher (lazily attached — sessions of one DB share the
    store object, so they share the batcher)."""
    b = getattr(store, "_pointget_batcher", None)
    if b is None:
        with _BATCHER_MU:
            b = getattr(store, "_pointget_batcher", None)
            if b is None:
                b = store._pointget_batcher = PointGetBatcher(store)
    return b


def batched_point_get(store, read_ts: int, keys: list) -> list:
    """Snapshot point reads through the store's cross-session batcher."""
    return point_batcher(store).get_many(read_ts, keys)


@dataclass
class CopTask:
    region: Region
    ranges: list[KeyRange]
    task_id: int
    # of a region that left a batch task: what the chaos seam made of its first
    # attempt there, where it fired once for the region already — the error it
    # raised (the attempt raises it again), or True (it passed)
    seam: object = None


@dataclass
class CopResult:
    chunk: Chunk
    task_id: int
    region_id: int
    # the task's ExecDetails sidecar (utils/execdetails.CopExecDetails);
    # always collected — EXPLAIN ANALYZE / slow log aggregate it
    details: object = None


def run_task_resilient(
    bo: Backoffer,
    run_one: Callable,
    resplit: Callable,
    region,
    ranges,
    store_type: StoreType,
    *,
    warn=None,
    degrade_reason: str,
    degrade_on: tuple,
    never_degrade: tuple = (),
    detail=None,
    trace_id=None,
) -> Chunk:
    """One cop task under the request's Backoffer — the single region-error /
    degrade policy shared by the embedded and remote cop clients.

    ``run_one(store_type, region, ranges) -> Chunk`` executes one attempt;
    ``resplit(ranges) -> [(region, ranges)]`` re-resolves routing. A
    RegionError re-splits RECURSIVELY: a second epoch change re-enters the
    same handler, bounded by the boRegionMiss budget, whose exhaustion
    surfaces the last region error typed (never the retry mechanism). A
    TPU-engine failure matching ``degrade_on`` (minus ``never_degrade``)
    falls back to the host engine for THIS task — through the same re-split
    handler, so a degrade retry never reuses stale routing.
    (ref: coprocessor.go buildCopTasks re-entry on region error)"""

    def attempt(st, region2, ranges2):
        try:
            return run_one(st, region2, ranges2)
        except RegionError as e:
            try:
                slept = bo.backoff(boRegionMiss, e)
            except BackoffExhausted as be:
                raise (be.last or e) from be
            if detail is not None:
                # sidecar attribution: the task's OWN sleeps/re-splits, never
                # the shared Backoffer's (other workers charge it too)
                detail.retries += 1
                detail.backoff_ms += slept
                detail.resplits += 1
            parts = [attempt(st, r2, k2) for r2, k2 in resplit(ranges2)]
            if not parts:
                # routing no longer covers these ranges at all (dropped
                # table, merged-away regions): surface the region verdict,
                # not a bare concat-of-nothing assertion
                raise e
            return Chunk.concat(parts) if len(parts) != 1 else parts[0]

    try:
        return attempt(store_type, region, ranges)
    except RegionError:
        raise  # exhausted re-splits: a routing verdict, not an engine failure
    except never_degrade:
        raise
    except degrade_on as e:
        if store_type != StoreType.TPU:
            raise
        # graceful degradation: one task's TPU-engine failure falls back to
        # the host engine for THAT task and is recorded — the query answers
        # instead of dying with the device
        if warn is not None:
            warn(1, 1105, f"TPU cop task on region {region.region_id} degraded to host: {e}")
        from tidb_tpu.utils import eventlog as _ev
        from tidb_tpu.utils import metrics as _m

        _m.COP_DEGRADED.inc(reason=degrade_reason)
        lg = _ev.on(_ev.WARN)
        if lg is not None:
            lg.emit(
                _ev.WARN,
                "copr",
                "degrade",
                trace_id=trace_id,
                region=region.region_id,
                reason=degrade_reason,
                cause=f"{type(e).__name__}: {e}",
            )
        if detail is not None:
            detail.degraded = f"{degrade_reason}:{type(e).__name__}"
        return attempt(StoreType.HOST, region, ranges)


class CopResponse:
    """Streaming response (kv.Response). Iterates CopResults; with
    keep_order the stream follows region order, else completion order."""

    def __init__(self, it: Iterator[CopResult], cancel: Optional[Callable] = None):
        self._it = it
        self._cancel = cancel
        self._closed = False

    def __iter__(self):
        return self._it

    def close(self):
        if not self._closed:
            self._closed = True
            if self._cancel is not None:
                # cancel this request's pending work only — the shared pool
                # serves other requests and must stay up
                self._cancel()


def _order_blind_partial(req: Request, dag: dagpb.DAGRequest) -> bool:
    """A request whose per-region results the root merges whatever their order
    and however they are grouped into tasks: for the ``tpu`` engine, a table
    scan that ends in a PARTIAL aggregation, with no window, no descending scan
    and no keep-order (the pushed-down fragments of the TPC-H scans). Only such
    a request rides a batch cop task."""
    ex = dag.executors
    return (
        req.store_type == StoreType.TPU
        and not req.keep_order
        and not req.desc
        and len(ex) > 1
        and ex[0].tp == dagpb.TABLE_SCAN
        and not ex[0].desc
        and ex[-1].tp in (dagpb.AGGREGATION, dagpb.STREAM_AGG)
        and ex[-1].agg_mode == dagpb.AGG_PARTIAL
        and not any(e.tp == dagpb.WINDOW for e in ex[1:])
    )


class CopClient:
    """kv.Client for the embedded store (both engines).

    A request is a task a region, run on the shared pool — or, where its
    results are order-blind partial aggregates for the ``tpu`` engine
    (``_order_blind_partial``), ONE batch task carrying every region (ref:
    batch_coprocessor.go, one task a store carrying many regions): what does
    not depend on the region — the bound DAG, the kernel lookup, the output
    schema — is then done once, every region's program is dispatched before
    the one fetch, and the interpreter lock changes hands a few times a
    statement, not three times a region. The engine hands back the regions it
    cannot serve that way (``tpu_engine._batch_path``); they, and any region
    the chaos seam faults, run as tasks of their own beside the batch, under
    the same re-split / degrade policy as ever."""

    def __init__(self, store: MemStore):
        self.store = store

    def send(self, req: Request) -> CopResponse:
        if req.tp != RequestType.DAG:
            raise ValueError(f"cop client handles DAG requests only, got {req.tp}")
        dag: dagpb.DAGRequest = req.data
        read_ts = req.start_ts or self.store.current_ts()

        tasks: list[CopTask] = []
        for region, ranges in self.store.pd.regions_in_ranges(req.ranges):
            tasks.append(CopTask(region, ranges, len(tasks)))
        if req.desc:
            tasks.reverse()

        if not tasks:
            return CopResponse(iter(()), None)

        concurrency = max(1, min(req.concurrency, len(tasks)))
        # one typed retry budget shared by every task of this request (ref:
        # copIterator's Backoffer per copTask batch; worker threads share it)
        bo = Backoffer(budget_ms=2000)

        def seam(store_type: StoreType, region: Region) -> None:
            # chaos seam: tests fault exact (task, engine) pairs (N-shot /
            # scripted) without touching the engines themselves
            failpoint.inject("cop_task_engine", region.region_id, store_type)

        from tidb_tpu.utils import metrics as _m
        from tidb_tpu.utils.memory import QueryKilledError, QueryOOMError

        # sidecar timing baseline, cross-thread span parent and the
        # statement's id, captured in the requesting thread (queue wait =
        # submit → worker pickup)
        t_submit = time.perf_counter()
        tracer = _tracing.effective(req.tracer)
        parent_span = tracer.current() if tracer is not None else None
        stmt = _tracing.current_stmt()

        @contextmanager
        def task_scope(det: _ed.CopExecDetails, label: str):
            """One task's sidecar collection and, inside it, its ``cop.task``
            span, which takes from the sidecar what the task came to."""
            det.queue_ms = (time.perf_counter() - t_submit) * 1000.0
            with _ed.collecting(det, tracer=tracer, stmt=stmt), _tracing.region(
                "cop.task", parent=parent_span, label=label if tracer is not None else None,
                region=det.region_id, queue_us=int(det.queue_ms * 1000.0),
            ) as span:
                cpu0 = time.thread_time() if span is not None else 0.0
                yield
                if span is not None:
                    span.note(
                        cpu_us=int((time.thread_time() - cpu0) * 1e6), h2d=det.h2d_bytes, d2h=det.d2h_bytes,
                        engine=det.engine, regions=det.regions, programs=det.programs,
                    )

        def finish(det: _ed.CopExecDetails, t0: float, chunk: Chunk, path: str) -> None:
            # processing = task wall minus its own backoff sleeps
            det.proc_ms = max((time.perf_counter() - t0) * 1000.0 - det.backoff_ms, 0.0)
            _m.COP_REGIONS.inc(det.regions, path=path)
            ring = getattr(self.store, "cop_ring", None)
            if ring is not None:
                # per-store cop-digest ring (embedded fleet members only —
                # attached by ShardedStore): the same per-TABLE digest the
                # wire servers record, so the balancer's hot boost sees
                # embedded and wire fleets identically
                from tidb_tpu import config as _config

                tid = dag.executors[0].table_id if dag.executors else 0
                ring.record(
                    f"cop table={tid} region={det.region_id}" + (f" regions={det.regions}" if det.regions != 1 else ""),
                    det.proc_ms / 1000.0,
                    len(chunk),
                    user="store",
                    slow_threshold_s=_config.current().store_slow_cop_ms / 1000.0,
                    digest_val=f"cop:{tid}|cop table={tid}",
                )

        def run(task: CopTask) -> CopResult:
            rid = task.region.region_id
            det = _ed.CopExecDetails(rid)
            t0 = time.perf_counter()

            def run_engine(store_type: StoreType, region: Region, ranges: list[KeyRange]) -> Chunk:
                met, task.seam = task.seam, None  # the first attempt's alone
                if isinstance(met, BaseException):
                    raise met
                if met is None:
                    seam(store_type, region)
                return _engines()[store_type](self.store, dag, region, ranges, read_ts, warn=req.warn)

            with task_scope(det, f"cop.r{rid}"):
                chunk = run_task_resilient(
                    bo,
                    run_engine,
                    self.store.pd.regions_in_ranges,
                    task.region,
                    task.ranges,
                    req.store_type,
                    warn=req.warn,
                    degrade_reason="embedded",
                    # RuntimeError is the device-failure shape (XlaRuntimeError
                    # subclasses it); anything broader would silently mask TPU
                    # engine BUGS behind a correct host answer
                    degrade_on=(RuntimeError,),
                    # data/txn verdicts and kills: degrading engines would not help
                    never_degrade=(KVError, QueryKilledError, QueryOOMError),
                    detail=det,
                    trace_id=tracer.trace_id if tracer is not None else None,
                )
            finish(det, t0, chunk, "single")
            return CopResult(chunk, task.task_id, rid, det)

        def fan_out(ts: list[CopTask], window: int):
            """→ (results in task order, cancel) of tasks run one after another
            on this thread, or on the shared pool, at most ``window`` of THIS
            request in flight there."""
            if window == 1:
                return (run(t) for t in ts), None
            return windowed_fanout(shared_cop_pool(window), run, ts, window)

        if len(tasks) == 1 or not _order_blind_partial(req, dag):
            # shared pool, windowed: at most ``concurrency`` tasks of THIS
            # request occupy workers at once. Yielding in task order (not
            # completion order) costs nothing — the reader gathers every result
            # before returning — and keeps ORDER BY tie-breaks deterministic
            # across runs and engines (a stable root sort preserves the concat
            # order of equal keys, so completion-order concat would make ties
            # racy)
            return CopResponse(*fan_out(tasks, concurrency))

        # the batch task runs on the requesting thread as the reader pulls;
        # beside it, on the pool, a task for each region that left it
        alone: list = []  # (results, cancel) of each hand-over, in the order they left

        def leave(parts: list, met=True) -> None:
            ts = [CopTask(region, ranges, len(tasks) + i, met) for i, (region, ranges) in enumerate(parts)]
            # with a window of one they wait, unstarted, until the batch is through
            alone.append(fan_out(ts, concurrency))

        def run_batch() -> Optional[CopResult]:
            det = _ed.CopExecDetails(tasks[0].region.region_id)
            t0 = time.perf_counter()
            with task_scope(det, f"cop.r{det.region_id}+{len(tasks) - 1}"):
                batch = []
                for t in tasks:
                    try:  # the seam fires once a region, as on the other path
                        seam(StoreType.TPU, t.region)
                    except Exception as e:  # noqa: BLE001 — raised again, and judged, in the region's own task
                        leave([(t.region, t.ranges)], e)
                    else:
                        batch.append((t.region, t.ranges))
                chunk = None
                if batch:
                    chunk = _engines()[StoreType.TPU](
                        self.store, dag, None, None, read_ts, warn=req.warn, batch=batch, leave=leave
                    )
                if chunk is None:
                    det.regions = 0  # every region left: there is no result, the span says so
            if chunk is None:
                return None
            finish(det, t0, chunk, "batched")
            return CopResult(chunk, 0, det.region_id, det)

        def cancel():
            for _, c in alone:
                if c is not None:
                    c()

        def gen():
            try:
                res = run_batch()
                if res is not None:
                    yield res
                for results, _ in alone:
                    yield from results
            finally:
                cancel()

        return CopResponse(gen(), cancel)
