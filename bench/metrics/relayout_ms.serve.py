"""Device time per micro-batch and per chip of the whole-table passes
in the jitted program: the lane-dense view of the tier tables that XLA
builds in the forward on every call (``kernels/rows.lane_dense``) where
the store is not placed lane-dense, as the row-sharded store's shards
are not.  Counted: operations of that program, other than the Pallas
kernels, whose output holds a table's worth of elements (2**20 or
more); the per-request work (head, index math) is smaller by orders of
magnitude.  Summed over the cell's devices, divided by their number."""

import math
import re

MODULE = "jit_fwd"
TABLE_ELEMENTS = 1 << 20


def out_elements(name: str) -> int:
    rest = re.sub(r"\{[^}]*\}", "", name.partition(" = ")[2])
    out = (rest[:rest.find(")") + 1] if rest.startswith("(")
           else rest.split(" ")[0])
    return sum(math.prod(int(d) for d in dims.split(",") if d)
               for dims in re.findall(r"\[([\d,]*)\]", out))


def is_relayout(name: str) -> bool:
    return "custom-call(" not in name and \
        out_elements(name) >= TABLE_ELEMENTS


def read(ctx):
    n = ctx.counts.get("batches")
    t = ctx.trace_data.op_time(is_relayout, module=MODULE)
    return t / ctx.trace_data.devices / n * 1e3 if n and t > 0 else None
