"""Drive one run of the harness with the timed path broken underneath:

    python3 benchmark/tests/faults.py <fault> <run.py arguments>

With `--platform cpu --scale <s>` among the arguments it runs off the chip
(the tests do); without them it runs a cell at its own size on the chip, and
its result line is a fault's reading, never a measurement.

The faults are planted in the program (at run time, from here), below the
client: the harness must see `correct` come out false for each.

  none            nothing broken (the same drive must come out correct)
  altered_answer  the tpu engine's result altered where it is produced: the
                  first numeric cell of the first row of every chunk, plus one
  half_rows       the tpu engine scans only the lower half of the handle range
  host_engine     the host engine answers in the tpu engine's place (what a
                  quiet fallback would do): answers right, device not used
  stale_snapshot  every cop request reads at the snapshot of the request before
                  it: writes acknowledged in between are missing from answers
  mpp_fewer_devices  every MPP gather is held to one device fewer than the
                  cell's `chips` (`parallel/mesh.FORCE_NDEV`). With one chip
                  that leaves none: the gather gives up (MPPRetryExhausted) and
                  the session answers from the host executor. Answers right,
                  the join not on the cell's devices
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def plant(fault: str, chips: int = 1) -> None:
    from tidb_tpu.copr import client as cop_client
    from tidb_tpu.copr import host_engine
    from tidb_tpu.kv.kv import StoreType

    engines = cop_client._engines()
    real = engines[StoreType.TPU]
    if fault == "altered_answer":
        def altered(*a, **kw):
            chunk = real(*a, **kw)
            for col in chunk.columns:
                if col.dictionary is None and len(col.data):
                    col.data = col.data.copy()
                    col.data[0] += 1
                    break
            return chunk

        engines[StoreType.TPU] = altered
    elif fault == "half_rows":
        from tidb_tpu.copr.colcache import cache_for
        from tidb_tpu.kv import tablecodec

        def half(store, dag, region, ranges, read_ts, warn=None):
            tid = dag.executors[0].table_id
            entry = cache_for(store)._entries.get((region.region_id, tid))
            if entry is not None and entry.n:
                mid = int(entry.handles[entry.n // 2])
                ranges = [tablecodec.handle_range(tid, None, mid)]
            return real(store, dag, region, ranges, read_ts, warn=warn)

        engines[StoreType.TPU] = half
    elif fault == "host_engine":
        engines[StoreType.TPU] = host_engine.execute_dag
    elif fault == "stale_snapshot":
        real_send = cop_client.CopClient.send
        last = {}

        def send(self, req):
            now = req.start_ts or self.store.current_ts()
            req.start_ts = last.get("ts", now)  # the snapshot of the request before
            last["ts"] = now
            return real_send(self, req)

        cop_client.CopClient.send = send
    elif fault == "mpp_fewer_devices":
        from tidb_tpu.parallel import mesh

        mesh.FORCE_NDEV = chips - 1
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run

    if "--platform" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # as run.py does, before the program is imported: it reads both at import
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(run.CACHE, "xla"))
    plant(sys.argv[1], run.find_cell(run.parse_args(sys.argv[2:]).workload)[1]["chips"])
    sys.exit(run.main(sys.argv[2:]))
