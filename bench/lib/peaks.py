"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A kind not in the table is an error, never a default.
"""

CHIP_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9,
                    "ici_bw": 1600e9 / 8},
}


def chip_peaks(kind: str) -> dict:
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r} "
                         f"(known: {sorted(CHIP_PEAKS)})") from None
