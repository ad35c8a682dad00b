"""Share of the traced window in which the device ran no operation
while ``serve.fold`` or one of its children was the innermost program
span open: the part of ``device_idle.serve`` that the host's Eq. 7 fold
holds the chip idle.  The program's spans are put on the trace's clock
by the harness spans both record (``program_spans.offset_ns``)."""

from bench.lib import program_spans


def read(ctx):
    return program_spans.idle_under(ctx, program_spans.FOLD)
