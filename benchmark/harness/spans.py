"""The benchmark's own spans around the calls into each layer, put there at
run time from this file: the program is not edited.

  stmt    the client call (wire + session + planner + everything below)
  cop     one CopClient.send and the draining of its response (copr/)
  exec    one tpu_engine.execute_dag (bind, H2D, kernel dispatch, fetch, decode); trace only
  writer  one write transaction, BEGIN to COMMIT acknowledged

Every span is timed on the host clock; in a traced run it is also written
into the profiler's trace (`jax.profiler.TraceAnnotation`), on the device
operations' clock. The cop span carries the task's ExecDetails sidecar, the
program's own counters: engine, degraded, h2d bytes, delta rows, merges.
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
        self._mu = threading.Lock()

    def span(self, kind: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(PREFIX + kind)

    def add_cop(self, rec: dict) -> None:
        with self._mu:
            self.cop.append(rec)

    def drain(self) -> list[dict]:
        with self._mu:
            cop, self.cop = self.cop, []
        return cop


def _details(det) -> dict:
    return {
        "engine": det.engine, "degraded": det.degraded, "h2d_bytes": det.h2d_bytes,
        "delta_rows": det.delta_rows, "merges": det.merges,
    }


def install(rec: Recorder) -> None:
    """Wrap CopClient.send and the tpu engine's execute_dag. The response of
    a send is lazy (tasks run as the reader pulls them), so the cop span
    covers send plus every pull, not the call alone."""
    from tidb_tpu.copr import client as cop_client
    from tidb_tpu.kv.kv import StoreType

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
