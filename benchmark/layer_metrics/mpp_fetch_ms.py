"""mpp gather, `tidb:mpp.fetch` (`mpp_phase.ms`): `jax.device_get` of the program's outputs: blocked on the
device, then D2H. The wait for the chip, seen from the host."""
from layer_metrics import mpp_phase

UNIT = "ms"


def read(ctx):
    return mpp_phase.ms(ctx, "fetch")
