"""The whole serve step's share of the cell's chips: requests/s in the
traced window times the least time one request needs, the larger of
its FLOPs over peak FLOP/s and its minimal bytes over HBM bandwidth,
over the number of chips (each chip's peaks, times the chips).
Minimal bytes: the request's rows as their tiers hold them (payload and
scale), its inputs, and the head's weights read once per micro-batch.
For these models the bytes bound it."""

from bench.lib.peaks import chip_peaks

ROW_BYTES = (lambda d: d + 4, lambda d: 2 * d + 4, lambda d: 4 * d)


def per_request(ctx):
    ref = ctx.reference()
    sizes = ctx.sizes
    d = sizes["embed_dim"]
    reqs = ctx.counts["requests"]
    rows = sum(n * f(d) for n, f in
               zip(ctx.counts["slots_by_tier"], ROW_BYTES)) / reqs
    head = ref.head_params(sizes) * 4 / ctx.mix["micro_batch"]
    return ref.head_flops(sizes), rows + ref.input_bytes(sizes) + head


def read(ctx):
    if not ctx.counts.get("slots_by_tier"):
        return None
    peaks = chip_peaks(ctx.devices[0].device_kind)
    flops, nbytes = per_request(ctx)
    qps = ctx.counts["requests"] / ctx.counts["window_s"]
    return qps * max(flops / peaks["flops"], nbytes / peaks["hbm_bw"]) \
        / len(ctx.devices) * 100.0
