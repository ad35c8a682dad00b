"""The FLOP and byte counts the per-layer metrics use, against counts
worked out by hand from the configs' widths."""

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
        assert g.bytes_needed(by_tier, 64) == slots * row_bytes + words_out


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
