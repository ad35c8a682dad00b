"""Seconds of set-up spent building the serving store, from the
program's timeblocks before the window: ``store.plan`` (priority
profile, Eq. 8 thresholds), ``store.snap`` (every row snapped to its
tier), ``store.pack`` (the host pack) and ``store.place`` (device
placement dispatch), less the compiles and cache loads inside them,
which ``compile_s.serve`` counts."""

from bench.lib import program_spans


def read(ctx):
    built = program_spans.before_window(ctx, program_spans.STORE)
    if not built:
        return None
    return program_spans.self_seconds(
        built, program_spans.before_window(ctx, program_spans.COMPILES))
