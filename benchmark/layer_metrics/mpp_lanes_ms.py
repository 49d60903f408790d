"""mpp gather, `tidb:mpp.lanes` (`mpp_phase.ms`): the readers' lanes made ready: the column cache read, padded and
put on the device on a miss, found resident on a hit (`rows_valid`, `rows_padded`, `h2d`, `cache` on the span)."""
from layer_metrics import mpp_phase

UNIT = "ms"


def read(ctx):
    return mpp_phase.ms(ctx, "lanes")
