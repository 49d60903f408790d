"""The benchmark's own spans around the calls into each layer, put there at
run time from this file: the program is not edited.

  stmt    the client call (wire + session + planner + everything below)
  cop     one CopClient.send and the draining of its response (copr/)
  mpp     one MPPGatherExec.execute: lanes read, the fragment program over the mesh, merge (parallel/)
  exec    one tpu_engine.execute_dag (bind, H2D, kernel dispatch, fetch, decode); trace only
  writer  one write transaction, BEGIN to COMMIT acknowledged

Every span is timed on the host clock; in a traced run it is also written
into the profiler's trace (`jax.profiler.TraceAnnotation`), on the device
operations' clock. The cop span carries the task's ExecDetails sidecar, the
program's own counters: engine, degraded, h2d bytes, delta rows, merges.
The mpp span carries the gather's, MPPExecDetails (devices of the mesh it ran
on, retries, programs built, the store that ran it, bytes exchanged between
stages, the per-shard breakdown), and whether the gather raised: after
MPPRetryExhausted the session plans the statement again for the host.
"""

from __future__ import annotations

import contextlib
import threading
import time

PREFIX = "bench:"


class Recorder:
    def __init__(self):
        self.annotate = False
        self.cop: list[dict] = []
        self.mpp: list[dict] = []
        self._mu = threading.Lock()

    def span(self, kind: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(PREFIX + kind)

    def add_cop(self, rec: dict) -> None:
        with self._mu:
            self.cop.append(rec)

    def add_mpp(self, rec: dict) -> None:
        with self._mu:
            self.mpp.append(rec)

    def drain(self) -> tuple[list[dict], list[dict]]:
        """(cop records, mpp records) since the last drain."""
        with self._mu:
            cop, self.cop = self.cop, []
            mpp, self.mpp = self.mpp, []
        return cop, mpp


def _details(det) -> dict:
    return {
        "engine": det.engine, "degraded": det.degraded, "h2d_bytes": det.h2d_bytes,
        "delta_rows": det.delta_rows, "merges": det.merges,
    }


NO_DETAILS = {"ndev": None, "retries": None, "compiles": None, "store": None, "stage_bytes": [], "shards": []}


def _mpp_details(det) -> dict:
    return {
        "ndev": det.ndev, "retries": det.retries, "compiles": det.compiles, "store": det.store,
        "stage_bytes": list(det.stage_bytes), "shards": [list(s) for s in det.shards],
    }


def install(rec: Recorder) -> None:
    """Wrap CopClient.send, the tpu engine's execute_dag and
    MPPGatherExec.execute. The response of a send is lazy (tasks run as the
    reader pulls them), so the cop span covers send plus every pull, not the
    call alone; a gather returns its merged chunk, so its span is the call."""
    from tidb_tpu.copr import client as cop_client
    from tidb_tpu.kv.kv import StoreType
    from tidb_tpu.parallel import gather

    real_send = cop_client.CopClient.send

    def send(self, req):
        thread = threading.get_ident()
        t_first = time.perf_counter()
        with rec.span("cop"):
            resp = real_send(self, req)
        inner = iter(resp)
        wall = [time.perf_counter() - t_first]
        tasks: list[dict] = []

        def pulled():
            while True:
                t0 = time.perf_counter()
                try:
                    with rec.span("cop"):
                        res = next(inner)
                except StopIteration:
                    break
                finally:
                    wall[0] += time.perf_counter() - t0
                if res.details is not None:
                    tasks.append(_details(res.details))
                yield res
            rec.add_cop({"thread": thread, "t0": t_first, "t1": time.perf_counter(), "wall_s": wall[0], "tasks": tasks})

        resp._it = pulled()
        return resp

    cop_client.CopClient.send = send

    engines = cop_client._engines()
    real_exec = engines[StoreType.TPU]

    def execute_dag(*args, **kwargs):
        with rec.span("exec"):  # in the trace only: `idle_by_span` reads it
            return real_exec(*args, **kwargs)

    engines[StoreType.TPU] = execute_dag

    real_gather = gather.MPPGatherExec.execute

    def execute(self):
        # the program hands its details to the session when the gather has run
        # (`record_mpp_detail`): what the list gains during the call is this gather's
        seen = len(self.session.mpp_details)
        g = {"thread": threading.get_ident(), "t0": time.perf_counter(), "raised": None}
        try:
            with rec.span("mpp"):
                return real_gather(self)
        except BaseException as e:
            g["raised"] = type(e).__name__
            raise
        finally:
            g["t1"] = time.perf_counter()
            mine = self.session.mpp_details[seen:]
            g.update(_mpp_details(mine[-1]) if mine else NO_DETAILS)
            rec.add_mpp(g)

    gather.MPPGatherExec.execute = execute
