"""Mean host time to put one batch on the device (``jnp.asarray`` of
each array) per train step in the traced window: the ``feed`` span."""

import numpy as np


def read(ctx):
    d = ctx.spans.durations_s("feed")
    return float(np.mean(d)) * 1e3 if d else None
