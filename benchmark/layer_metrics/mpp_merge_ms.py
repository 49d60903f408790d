"""mpp gather, `tidb:mpp.merge` (`mpp_phase.ms`): the fetched group slots (or TopN heads) made into the
result chunk on the host (`groups` on the span)."""
from layer_metrics import mpp_phase

UNIT = "ms"


def read(ctx):
    return mpp_phase.ms(ctx, "merge")
