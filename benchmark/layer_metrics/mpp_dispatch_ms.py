"""mpp gather, `tidb:mpp.dispatch` (`mpp_phase.ms`): the host's enqueue of the fragment program
(`jit_mpp_<family>`), under the mesh's lock."""
from layer_metrics import mpp_phase

UNIT = "ms"


def read(ctx):
    return mpp_phase.ms(ctx, "dispatch")
