"""Share of the HBM roofline the train step's forward gather kernel
reaches: one fp32 row per slot plus its id, scale and weight words in,
(slots, D) fp32 out (``_bytes_dequant`` at itemsize 4), over its device
time, against the chip's HBM bandwidth."""

from bench.lib.peaks import chip_peaks


# the gather kernel's custom call is named after its Pallas call
# (``_tiled_call``, ``jvp_jit__tiled_call__`` under autodiff)
KERNEL = "_tiled_call"
OTHER = "bag_grad"


def is_kernel(name: str) -> bool:
    op = name.partition(" = ")[0]
    return KERNEL in op and OTHER not in op


def bytes_needed(slots: int, dim: int) -> int:
    return slots * (dim * 4 + 12) + slots * dim * 4


def read(ctx):
    t = ctx.trace_data.op_time(is_kernel)
    if t <= 0:
        return None
    slots = ctx.counts["steps"] * ctx.counts["batch"] * len(
        ctx.sizes["cardinalities"])
    bw = chip_peaks(ctx.devices[0].device_kind)["hbm_bw"]
    return bytes_needed(slots, ctx.sizes["embed_dim"]) / t / bw * 100.0
