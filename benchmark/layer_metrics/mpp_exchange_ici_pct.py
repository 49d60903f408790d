"""kernels: the exchange's share of its roofline: the least time the
interconnect could take for the bytes a chip sends a statement
(`mpp_exchange_bytes_per_stmt` / ndev / the chip's published ICI bytes/s,
`peaks_ici.json`) over the time the chip's collective operations took
(`mpp_exchange_ms`). The bytes are the buffers' (padding counts) and the time
is every collective's, so the share cannot pass 100%. A device kind the table
lacks is an error. None on a rehearsal and where either part has nothing to read."""
import json
import os

from layer_metrics import mpp_exchange_bytes_per_stmt, mpp_exchange_ms

UNIT = "%"


def read(ctx):
    if ctx.platform == "cpu":
        return None  # a rehearsal has no interconnect to take a share of
    total = mpp_exchange_bytes_per_stmt.read(ctx)
    ms = mpp_exchange_ms.read(ctx)
    ndev = sorted({g["ndev"] for g in ctx.mpp if g["raised"] is None and g["ndev"]})
    if not total or not ms or len(ndev) != 1:
        return None
    with open(os.path.join(ctx.here, "peaks_ici.json")) as f:
        peaks = json.load(f)
    if ctx.device_kind not in peaks:
        raise KeyError(f"peaks_ici.json has no entry for device kind {ctx.device_kind!r}")
    least_s = total / ndev[0] / peaks[ctx.device_kind]["ici_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
