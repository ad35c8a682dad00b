"""Mean host time of the program's ``serve.fold`` span (the body of
``OnlineServer.observe``: valid mask, eager Eq. 7 chain, re-tier check)
per micro-batch in the traced window.  Unlike ``observe_ms.serve`` it
leaves out the harness's ``int(hits)`` read and call overhead."""

import numpy as np

from bench.lib import program_spans


def read(ctx):
    folds = program_spans.in_window(ctx, {program_spans.FOLD})
    if not folds:
        return None
    return float(np.mean([e - s for _, _, _, s, e in folds])) * 1e-6
