"""99th percentile, over the traced window's requests, of the time
from a request's due time to the dispatch of its micro-batch: the
dispatch queue (harness loop and micro-batching)."""

import numpy as np


def read(ctx):
    q = ctx.extra.get("queue_ms")
    return float(np.quantile(q, 0.99)) if q is not None and q.size \
        else None
