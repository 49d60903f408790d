"""`generators/tpch_kept_heap.py` (PR 35): `tpch.py`'s tables value for
value; the allocator told to keep its heap, in a child, since the setting
lasts as long as its process; a program without the array loader refused
before a table is made."""
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT
from generators import tpch, tpch_kept_heap

CONFIG = {"scale_factor": 0.002, "tables": {"customer": {}, "orders": {}, "lineitem": {}}}

IN_A_CHILD = f"""
import sys
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
import numpy as np
from generators import tpch, tpch_kept_heap
config = {CONFIG!r}
assert tpch_kept_heap.keep_heap() is True
made, plain = tpch_kept_heap.generate(2147483777, config), tpch.generate(2147483777, config)
assert list(made) == list(plain)
for t in plain:
    for a, b in zip(made[t], plain[t]):
        assert a.dtype == b.dtype and np.array_equal(a, b)
# an array far above glibc's mapping threshold now lies in the heap, and the next one takes its place
a = np.ones(16 << 20, dtype=np.int64); at = a.ctypes.data; del a
assert np.ones(16 << 20, dtype=np.int64).ctypes.data == at
print("same tables, heap kept")
"""


def test_the_tables_are_tpchs_and_the_heap_is_kept():
    p = subprocess.run([sys.executable, "-c", IN_A_CHILD], capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and "same tables, heap kept" in p.stdout, p.stderr[-2000:]


def test_the_harness_finds_what_it_reads_of_a_generator():
    assert tpch_kept_heap.COLUMNS is tpch.COLUMNS and tpch_kept_heap.refresh_transactions is tpch.refresh_transactions


def test_this_program_loads_by_arrays():
    assert tpch_kept_heap.loads_by_arrays() is True


def test_a_program_without_the_array_loader_is_refused_before_a_table_is_made(monkeypatch, capsys):
    from tidb_tpu.utils.chunk import Dictionary

    monkeypatch.delattr(Dictionary, "encode_many")
    monkeypatch.setattr(tpch, "generate", lambda *a: pytest.fail("generated"))
    monkeypatch.setattr(tpch_kept_heap, "keep_heap", lambda: pytest.fail("set the allocator"))
    assert tpch_kept_heap.loads_by_arrays() is False
    with pytest.raises(SystemExit) as e:
        tpch_kept_heap.generate(1, dict(CONFIG, scale_factor=10))
    assert e.value.code == 4
    assert "loads row at a time" in capsys.readouterr().err


def test_the_configuration_names_it():
    import json
    import os

    with open(os.path.join(BENCH, "configs", "tpch_sf10.json")) as f:
        assert json.load(f)["generator"] == "tpch_kept_heap"
    assert np.iinfo(np.int32).max == tpch_kept_heap.KEEP_TOP_BYTES
