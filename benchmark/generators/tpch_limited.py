"""`generators/tpch.py`'s tables, made in a process that is first held to
`ADDRESS_SPACE_LIMIT` bytes of address space (RLIMIT_AS).

Why a generator does this: the harness has no seam of its own for a limit on
the process, the configuration names its generator, and `generate` is the
first thing a run calls with the configuration in hand. The data is
`generators/tpch.py`'s, value for value.

Why a limit at all: a deployment's SQL node runs under one (a container's, or
`tidb_server_memory_limit`), and a plan that asks it for terabytes ends with
an error, not with the node. The chip machine's sandbox grants any `mmap` and
ends the process at 40 GiB resident instead: a program that plans TPC-H Q3 as a
host cross join of `customer x orders` (PR 28's, 9 x 10^11 pairs at SF2: numpy
asks for 6.55 TiB) was killed there 40 s into filling it (my chip runs, PR 29);
held to 2 TiB it ended with `MySQLError (1105) Unable to allocate 6.55 TiB` and
exit code 1. `SET max_execution_time` does not reach it: the program checks
its deadline between cop tasks, not inside `np.repeat`.
"""

from __future__ import annotations

import resource

from generators import tpch
from generators.tpch import COLUMNS, refresh_transactions  # noqa: F401  (the harness reads them here)


ADDRESS_SPACE_LIMIT = 1 << 40  # 1 TiB: a run of the cell uses ~24 GiB of address space, the cross join asks for 6.55 TiB


def generate(seed: int, config: dict) -> dict:
    limit = ADDRESS_SPACE_LIMIT
    _, hard = resource.getrlimit(resource.RLIMIT_AS)
    if hard != resource.RLIM_INFINITY:
        limit = min(limit, hard)
    resource.setrlimit(resource.RLIMIT_AS, (limit, hard))
    return tpch.generate(seed, config)
