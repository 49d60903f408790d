"""load: rows the bulk loader ingested a second of its own work, over the
whole process: the sum of `tidb_tpu_bulk_load_rows_total` over the sum of
`tidb_tpu_bulk_load_seconds_total` (`encode`: string columns to dictionary
codes; `ingest`: `MemStore.ingest_columnar` with its change-log notes and
region splits), read from the program's registry in the run's own process.
The load is most of a run's set-up, and every run pays it. None where the
program has no such counters (a commit from before them), loaded nothing
or counted no time."""
UNIT = "rows/s"


def read(ctx, registry=None):
    if registry is None:
        from tidb_tpu.utils.metrics import REGISTRY as registry
    counters = registry.snapshot()
    rows = counters.get("tidb_tpu_bulk_load_rows_total")
    seconds = counters.get("tidb_tpu_bulk_load_seconds_total")
    if rows is None or seconds is None:
        return None
    n = sum(v for _, v in rows["values"])
    s = sum(v for _, v in seconds["values"])
    return n / s if n and s else None
