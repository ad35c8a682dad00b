"""Share of the HBM roofline the ``bag_grad`` scatter kernel reaches:
the (slots, D) fp32 cotangents and each slot's coefficient and id in,
one read-modify-write of every addressed row (``_bytes_bag_grad``),
over its device time, against the chip's HBM bandwidth."""

from bench.lib.peaks import chip_peaks


# the scatter kernel's custom call is named after its Pallas call
# (``transpose_jvp_jit__bag_grad_tiled_call___`` as the gather's VJP)
KERNEL = "bag_grad_tiled_call"


def is_kernel(name: str) -> bool:
    return KERNEL in name.partition(" = ")[0]


def bytes_needed(slots: int, dim: int) -> int:
    return slots * dim * 4 + slots * 8 + 2 * slots * dim * 4


def read(ctx):
    t = ctx.trace_data.op_time(is_kernel)
    if t <= 0:
        return None
    slots = ctx.counts["steps"] * ctx.counts["batch"] * len(
        ctx.sizes["cardinalities"])
    bw = chip_peaks(ctx.devices[0].device_kind)["hbm_bw"]
    return bytes_needed(slots, ctx.sizes["embed_dim"]) / t / bw * 100.0
