"""Mean host time of ``OnlineServer.observe`` (the Eq. 7 fold) per
micro-batch in the traced window: the harness's ``observe`` span."""

import numpy as np


def read(ctx):
    d = ctx.spans.durations_s("observe")
    return float(np.mean(d)) * 1e3 if d else None
