"""`generators/tpch.py`'s tables, value for value, for a deployment too large
for a run that maps and unmaps every large array: made in a process whose
allocator is first told to keep its heap (`mallopt`), and only for a program
whose loader can end the run inside the check's limit.

Why a generator does this: as `tpch_limited.py` says, the harness has no seam
of its own for a policy on the process, the configuration names its generator,
and `generate` is the first thing a run calls with the configuration in hand.

Why keep the heap. glibc hands every array above 32 MiB a mapping of its own
and returns it on free, so each numpy temporary over a 60M-row column (480 MB)
is paged in anew, one fault a 4 KiB page, and the chip machine's sandbox makes
a fault dear. The same reference over the same 60M rows answered the window's
16 (statement, parameter set) pairs in 36 s after the profiler had left 11 GiB
of freed heap behind, and in 126-128 s without (my chip runs, PR 35: traced
against untraced, parent and change alike). With `M_MMAP_MAX` 0 the arrays of
the MAIN thread (the generator, the load, the reference) come from the heap,
which `M_TRIM_THRESHOLD` then keeps instead of returning its top; the server's
threads keep their own arenas, whose large arrays stay mapped as before. A
service's unit file sets the same through `MALLOC_MMAP_MAX_` /
`MALLOC_TRIM_THRESHOLD_`; the driver starts the process, so it is set here.

Why refuse a program. The check ends a run at 360 s and refuses the PR whose
run it ended. Generating, the window and the reference are ~220 s of that at
scale factor 10 whatever the program does; a loader that walks every row and
every distinct string in Python (every commit before PR 35: 2.3 s a million
rows on the chip's host, 182 s for these 76.5M rows, the run 447 s) cannot end
in what is left. Such a program is told so at once, before a table is made,
with exit code 4 (not 1, a crash; not 2, the harness finding no device). It is
known by what the array loader brought, `Dictionary.encode_many`: a program
that renames it changes `loads_by_arrays` too.
"""

from __future__ import annotations

import ctypes
import sys

from generators import tpch
from generators.tpch import COLUMNS, refresh_transactions  # noqa: F401  (the harness reads them here)


M_TRIM_THRESHOLD, M_MMAP_MAX = -1, -4  # <malloc.h>
KEEP_TOP_BYTES = 2**31 - 1  # `mallopt` takes an int: the heap keeps up to 2 GiB of free top


def keep_heap() -> bool:
    """Tell glibc's allocator to serve large requests from the heap and to keep
    the heap's top. False where the C library has no `mallopt`."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    return bool(mallopt(M_MMAP_MAX, 0)) and bool(mallopt(M_TRIM_THRESHOLD, KEEP_TOP_BYTES))


def loads_by_arrays() -> bool:
    from tidb_tpu.utils.chunk import Dictionary

    return hasattr(Dictionary, "encode_many")


def generate(seed: int, config: dict) -> dict:
    if not loads_by_arrays():
        print("benchmark: generator tpch_kept_heap: this program loads row at a time (no Dictionary.encode_many): "
              f"at scale factor {config['scale_factor']} its run cannot end inside the check's limit", file=sys.stderr)
        raise SystemExit(4)
    keep_heap()
    return tpch.generate(seed, config)
