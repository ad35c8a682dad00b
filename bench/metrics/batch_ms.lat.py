"""99th percentile, over the traced window's requests, of the time
from their micro-batch's dispatch to its output being ready: the
served micro-batch (feed, forward, fold)."""

import numpy as np


def read(ctx):
    b = ctx.extra.get("batch_ms")
    return float(np.quantile(b, 0.99)) if b is not None and b.size \
        else None
