"""Share of the HBM roofline the serving gather kernels reach: the
bytes the per-tier ``dequant_bag`` calls need over their device time
summed over the cell's chips, against one chip's HBM bandwidth.  Each
chip makes one call per tier; every call reads every slot's id, scale
and weight words and writes the (slots, D) fp32 output, while each row
is read once, by the call of its tier on the chip that holds it (a
slot another chip owns has weight 0 and no DMA).  Bytes per call as
``_bytes_dequant`` counts them."""

from bench.lib.peaks import chip_peaks

ITEMSIZE = (1, 2, 4)      # int8, half, fp32 rows


# the gather kernel's custom call is named after its Pallas call
# (``_tiled_call``, ``jvp_jit__tiled_call__`` under autodiff)
KERNEL = "_tiled_call"
OTHER = "bag_grad"


def is_kernel(name: str) -> bool:
    op = name.partition(" = ")[0]
    return KERNEL in op and OTHER not in op


def bytes_needed(slots_by_tier, dim: int, chips: int) -> int:
    slots = sum(slots_by_tier)
    return sum(n * dim * it + chips * (slots * 12 + slots * dim * 4)
               for n, it in zip(slots_by_tier, ITEMSIZE))


def read(ctx):
    t = ctx.trace_data.op_time(is_kernel)
    tiers = ctx.counts.get("slots_by_tier")
    if t <= 0 or not tiers:
        return None
    bw = chip_peaks(ctx.devices[0].device_kind)["hbm_bw"]
    nbytes = bytes_needed(tiers, ctx.sizes["embed_dim"], len(ctx.devices))
    return nbytes / t / bw * 100.0
