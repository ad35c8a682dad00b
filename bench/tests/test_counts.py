"""The FLOP and byte counts the per-layer metrics use, against counts
worked out by hand from the configs' widths, and the per-chip readers
on one chip and on four."""

from types import SimpleNamespace

import pytest

from bench.lib import peaks, registry


def _metric(name):
    return registry.metric_reader(name)


def test_dlrm_head_forward_flops_per_request():
    sizes = registry.config("dlrm-rm2")["sizes"]
    ref = registry.reference(registry.config("dlrm-rm2"))
    bottom = 13 * 512 + 512 * 256 + 256 * 64
    top_in = 64 + 27 * 26 // 2                      # 415
    top = top_in * 512 + 512 * 512 + 512 * 256 + 256 * 1
    interactions = 351 * 64
    assert ref.head_flops(sizes) == 2 * (bottom + top + interactions)
    assert ref.head_flops(sizes) == 1_565_056


def test_wide_deep_head_forward_flops_per_request():
    sizes = registry.config("wide-deep")["sizes"]
    ref = registry.reference(registry.config("wide-deep"))
    deep = 1280 * 1024 + 1024 * 512 + 512 * 256 + 256 * 1
    assert ref.head_flops(sizes) == 2 * deep + 40 + 1


def test_rows_by_tier_bytes():
    """26 rows of D=64: 64 bytes int8, 128 half, 256 fp32."""
    g = _metric("gather_roofline.serve")
    slots = 26
    for tier, row_bytes in enumerate((64, 128, 256)):
        by_tier = [0, 0, 0]
        by_tier[tier] = slots
        # three tier calls, each reading every slot's 12 bytes of words
        # and writing the (slots, 64) fp32 output
        words_out = 3 * (slots * 12 + slots * 64 * 4)
        assert g.bytes_needed(by_tier, 64, 1) == \
            slots * row_bytes + words_out


def test_step_mfu_serve_row_bytes():
    m = _metric("step_mfu.serve")
    assert [f(64) for f in m.ROW_BYTES] == [64 + 4, 128 + 4, 256]


def test_train_kernel_bytes():
    g = _metric("gather_roofline.train")
    s = _metric("scatter_roofline.train")
    slots = 8192 * 26
    assert g.bytes_needed(slots, 64) == slots * (256 + 12) + slots * 256
    assert s.bytes_needed(slots, 64) == slots * 256 + slots * 8 \
        + 2 * slots * 256


def test_peaks_keyed_by_kind():
    p = peaks.chip_peaks("TPU v5 lite")
    assert (p["flops"], p["hbm_bw"]) == (197e12, 819e9)
    try:
        peaks.chip_peaks("TPU v9 imaginary")
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown device kind must raise")


# a traced window of the serve cell at dlrm-rm2's widths: 240 micro-
# batches of 512, their slots by tier, and device seconds summed over
# the cell's devices
BY_TIER = [2_000_000, 700_000, 494_720]
SERVE_COUNTS = {"requests": 240 * 512, "batches": 240, "window_s": 4.0,
                "slots_by_tier": BY_TIER}
OP_S = 0.876


def _serve_ctx(chips):
    cfg = registry.config("dlrm-rm2")
    dev = SimpleNamespace(device_kind="TPU v5 lite")
    trace = SimpleNamespace(devices=chips,
                            op_time=lambda match, module=None: OP_S)
    return SimpleNamespace(
        devices=[dev] * chips, sizes=cfg["sizes"],
        mix=registry.traffic("serve-zipf.sat"), counts=dict(SERVE_COUNTS),
        trace_data=trace, reference=lambda: registry.reference(cfg))


def test_serve_readers_on_one_chip_keep_their_formulas():
    """One chip: each reader gives what it gave before it read per
    chip, worked out here from the same counts."""
    ctx = _serve_ctx(1)
    p = peaks.chip_peaks("TPU v5 lite")
    mfu = _metric("step_mfu.serve")
    flops, nbytes = mfu.per_request(ctx)
    qps = SERVE_COUNTS["requests"] / SERVE_COUNTS["window_s"]
    assert mfu.read(ctx) == qps * max(flops / p["flops"],
                                      nbytes / p["hbm_bw"]) * 100.0
    slots = sum(BY_TIER)
    want = sum(n * 64 * it + slots * 12 + slots * 64 * 4
               for n, it in zip(BY_TIER, (1, 2, 4)))
    assert _metric("gather_roofline.serve").read(ctx) == \
        want / OP_S / p["hbm_bw"] * 100.0
    assert _metric("relayout_ms.serve").read(ctx) == OP_S / 240 * 1e3


@pytest.mark.parametrize("chips", [2, 4])
def test_serve_readers_per_chip(chips):
    """``chips`` chips: the whole step's share over that many chips'
    peaks, the relayout per chip, and the gather's rows read once while
    each chip's three tier calls read every slot's words and write
    every slot's output."""
    one, many = _serve_ctx(1), _serve_ctx(chips)
    mfu = _metric("step_mfu.serve")
    assert mfu.read(many) == pytest.approx(mfu.read(one) / chips,
                                           rel=1e-12)
    relayout = _metric("relayout_ms.serve")
    assert relayout.read(many) == pytest.approx(relayout.read(one) / chips,
                                                rel=1e-12)
    g = _metric("gather_roofline.serve")
    slots = sum(BY_TIER)
    rows = 2_000_000 * 64 + 700_000 * 128 + 494_720 * 256
    per_call = slots * 12 + slots * 64 * 4
    assert g.bytes_needed(BY_TIER, 64, chips) == rows + chips * 3 * per_call
    bw = peaks.chip_peaks("TPU v5 lite")["hbm_bw"]
    assert g.read(many) == pytest.approx(
        (rows + chips * 3 * per_call) / OP_S / bw * 100.0, rel=1e-12)
