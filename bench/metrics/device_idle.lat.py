"""Share of the traced window in which no operation ran on the
device: 1 - union of the operations' intervals / window."""


def read(ctx):
    return ctx.trace_data.idle_share * 100.0
