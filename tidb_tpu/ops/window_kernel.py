"""Device window-function kernel: one jitted XLA program per window shape.

Reference parity: pkg/executor WindowExec + the Shuffle intra-node
repartitioner (shuffle.go:86) — but instead of per-partition Go loops, the
whole operator evaluates as ONE sorted-batch program over padded lanes (the
shared core in ops/window_core.py):

  sort rows by (partition keys, order keys)  →  partition/peer segment
  boundaries  →  ranking = positional arithmetic on segment starts;
  framed aggregates = prefix-sum differences (count/sum/avg) and segmented
  running scans (min/max)  →  inverse-permutation gather restores row order.

No scatter anywhere (TPU policy, see ops/dag_kernel.py). Frames supported
on-device: whole partition, RANGE UNBOUNDED..CURRENT (peers share), ROWS
UNBOUNDED..CURRENT, and bounded ROWS for the prefix-sum aggregates. The
executor falls back to the host sweep for anything else (bounded-frame
MIN/MAX, string order keys, non-constant ntile/lead offsets).

Scale: when the caller supplies integer value bounds for every sort lane
(numpy min/max — one cheap host pass), the sort packs into a single int64
key (window_core.sort_perm), so the kernel stays fast far past the old
4M-row multi-lane-sort ceiling.
"""

from __future__ import annotations

import threading

from tidb_tpu.ops.window_core import SUPPORTED, window_program  # noqa: F401 (re-export)

# measured-cost device-vs-host choice (replaces the old hard 2M-row floor).
# The three link constants are what chip_smoke.py measured on one TPU v5 lite
# (v5e) under jax 0.9.0 / libtpu 0.0.34, 2026-09-26: a dispatch that ends in a
# host fetch ≈ 0.9 ms (0.6 ms to a bare sync); H2D ≈ 0.2 ns/byte (5.7 GB/s at
# 64 MiB); D2H ≈ 1 ns/byte (0.3–1.1 measured between 1 and 256 MiB — the
# slower direction, so the conservative end). The per-row compute constants
# below them are older readings (July 2026, same chip generation) and have
# not been re-taken. A shape's FIRST compile is what gates small batches: the
# fused window program of the smoke compiled in 71 s at 8M rows and 96 s at
# 20M, so uncompiled shapes only go to the device when the batch is big
# enough that the compile amortizes across a session's reuse.
DEV_FIXED_S = 0.9e-3
H2D_NS_PER_BYTE = 0.2
D2H_NS_PER_BYTE = 1.0
DEV_ROW_NS_PER_FUNC = 15.0
HOST_ROW_NS_PER_FUNC = 500.0
HOST_SORT_ROW_NS = 150.0
COMPILE_GATE_ROWS = 2_000_000
# packed single-key sorts scale to one full device batch; without bounds the
# multi-lane sort's compile cost explodes under x64 emulation past one block
DEVICE_MAX_ROWS = 1 << 25
MULTILANE_MAX_ROWS = 1 << 22


def device_beats_host(n: int, n_lanes_up: int, n_funcs: int, compiled: bool) -> bool:
    """Calibrated cost comparison (ref: the reference's row-count-driven
    Shuffle concurrency choice, shuffle.go:86 — redesigned as a measured
    device/host cost model)."""
    if not compiled and n < COMPILE_GATE_ROWS:
        return False  # never buy a 70-100 s compile for a small batch
    nf = max(n_funcs, 1)
    dev = DEV_FIXED_S + n * (
        H2D_NS_PER_BYTE * 9 * n_lanes_up  # upload: (data+valid) per lane
        + D2H_NS_PER_BYTE * 9 * nf  # download: (data, valid) per function
        + DEV_ROW_NS_PER_FUNC * nf
    ) * 1e-9
    host = n * (HOST_ROW_NS_PER_FUNC * nf + HOST_SORT_ROW_NS) * 1e-9
    return dev < host


def is_compiled(spec: tuple, n_pad: int, bounds: "tuple | None" = ...) -> bool:
    """Is this window shape compiled at this batch size? With ``bounds``
    given, an EXACT cache-key check (the compile key includes the widened
    sort bounds — a near-miss variant still costs a full compile); without,
    an any-variant pre-check used before bounds are known."""
    with _MU:
        if bounds is not ...:
            return (spec, n_pad, bounds) in _CACHE
        return any(k[0] == spec and k[1] == n_pad for k in _CACHE)

_CACHE: dict = {}
_MU = threading.Lock()


def get_window_fn(spec: tuple, n_pad: int, bounds: tuple = None):
    key = (spec, n_pad, bounds)
    with _MU:
        fn = _CACHE.get(key)
    if fn is None:
        fn = _build(spec, n_pad, bounds)
        with _MU:
            _CACHE[key] = fn
    return fn


def _build(spec: tuple, n_pad: int, bounds):
    """spec = (n_part_keys, order_descs, frame_tag, funcs) — see
    window_core.derive_specs for the funcs tuple layout. ``bounds``: per
    part+order sort lane (lo, hi) or None (enables the packed sort)."""
    import jax
    import jax.numpy as jnp

    n_part, order_descs, frame_tag, funcs = spec
    n = n_pad

    def fn(part_lanes, order_lanes, arg_lanes, nvalid):
        mask = jnp.arange(n) < nvalid
        # re-expand the compacted arg tuple (has-arg lanes only) to the
        # per-func layout window_program expects (None = no argument)
        it = iter(arg_lanes)
        full_args = [next(it) if f[1] else None for f in funcs]
        outs, perm, _sm = window_program(
            jax,
            jnp,
            mask=mask,
            part_lanes=list(part_lanes),
            order_lanes=list(order_lanes),
            order_descs=order_descs,
            frame_tag=frame_tag,
            specs=funcs,
            arg_lanes=full_args,
            n=n,
            bounds=list(bounds) if bounds is not None else None,
        )
        inv = jnp.argsort(perm)
        # restore original row order
        flat = []
        for d, v in outs:
            flat.append(d[inv])
            flat.append(v[inv])
        return tuple(flat)

    return jax.jit(fn)
