"""Seconds of set-up spent compiling (``jax.compile``) or loading from
the persistent compilation cache (``jax.cache_load``), from the
program's compile log before the window."""

from bench.lib import program_spans


def read(ctx):
    done = program_spans.before_window(ctx, program_spans.COMPILES)
    return program_spans.seconds(done) if done else None
