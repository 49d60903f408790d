"""`load_rows_per_s` (PR 35) on registries made by hand: rows over seconds,
all tables and both phases summed; None, without raising, where the program
has no such counters (PR 35's parent), loaded nothing or counted no time."""
import types

import pytest

from layer_metrics import load_rows_per_s
from tidb_tpu.utils.metrics import Registry

CTX = types.SimpleNamespace()


def registry(rows=None, seconds=None):
    reg = Registry()
    if rows is not None:
        c = reg.counter("tidb_tpu_bulk_load_rows_total", "", ("table",))
        for table, n in rows.items():
            c.inc(n, table=table)
    if seconds is not None:
        c = reg.counter("tidb_tpu_bulk_load_seconds_total", "", ("phase",))
        for phase, s in seconds.items():
            c.inc(s, phase=phase)
    reg.counter("tidb_tpu_copr_task_total", "", ("engine",)).inc(3, engine="tpu")
    return reg


def test_rows_over_seconds_tables_and_phases_summed():
    reg = registry({"customer": 1_500_000, "orders": 15_000_000, "lineitem": 60_000_000}, {"encode": 40.0, "ingest": 11.0})
    assert load_rows_per_s.read(CTX, reg) == pytest.approx(76_500_000 / 51.0)
    assert load_rows_per_s.UNIT == "rows/s"


def test_it_reads_nothing_from_a_program_without_the_counters():
    assert load_rows_per_s.read(CTX, registry()) is None
    assert load_rows_per_s.read(CTX, registry(rows={"lineitem": 5})) is None
    assert load_rows_per_s.read(CTX, registry(seconds={"encode": 1.0})) is None


def test_no_rows_or_no_seconds_is_nothing_to_read():
    assert load_rows_per_s.read(CTX, registry({"lineitem": 100}, {"encode": 0.0, "ingest": 0.0})) is None
    assert load_rows_per_s.read(CTX, registry({}, {"encode": 2.0})) is None


def test_it_reads_the_process_registry_by_default():
    from tidb_tpu.utils import metrics

    before = load_rows_per_s.read(CTX)
    metrics.BULK_LOAD_ROWS.inc(1000, table="t")
    metrics.BULK_LOAD_SECONDS.inc(0.5, phase="ingest")
    after = load_rows_per_s.read(CTX)
    assert after is not None and after != before
