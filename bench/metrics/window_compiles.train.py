"""Compiles and compile-cache loads that ended inside the traced
window: every shape is warmed in set-up, so this reads 0 unless a call
recompiles per batch."""

from bench.lib import program_spans


def read(ctx):
    done = program_spans.in_window(ctx, program_spans.COMPILES)
    return None if done is None else len(done)
