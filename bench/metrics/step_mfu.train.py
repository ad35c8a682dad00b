"""The whole train step's share of the chip: examples/s in the traced
window times the least time one example needs, the larger of its
forward and backward FLOPs (3x the head's forward) over peak FLOP/s and
its minimal bytes over HBM bandwidth.  Minimal bytes: its rows read
once and written once with their adagrad cells, its inputs, and the
head's weights with Adam's two moments read and written once per
batch."""

from bench.lib.peaks import chip_peaks


def per_example(ctx):
    ref = ctx.reference()
    sizes = ctx.sizes
    f, d = len(sizes["cardinalities"]), sizes["embed_dim"]
    rows = f * (2 * d * 4 + 2 * 4)
    head = ref.head_params(sizes) * 4 * 3 * 2 / ctx.counts["batch"]
    return 3 * ref.head_flops(sizes), rows + ref.input_bytes(sizes) + head


def read(ctx):
    peaks = chip_peaks(ctx.devices[0].device_kind)
    flops, nbytes = per_example(ctx)
    rate = ctx.counts["steps"] * ctx.counts["batch"] / ctx.counts["window_s"]
    return rate * max(flops / peaks["flops"],
                      nbytes / peaks["hbm_bw"]) * 100.0
