"""Device checks, the compile cache and the device record."""

from __future__ import annotations

import os

from bench.lib.registry import ROOT

# fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = ROOT / ".bench_cache" / "jax"


class NoChip(RuntimeError):
    pass


def use_compile_cache() -> str:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an LRU cache fails every later write once one entry
    # lacks its access-time file, and then every run compiles again
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices, or NoChip: no CPU fallback, no
    interpreted kernels, no fewer chips than the cell asks for.  They
    are the cell's chips: a serve cell on more than one forms its
    ``"model"`` mesh from them (``Ctx.mesh``); a train cell takes one
    chip and refuses more."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from None
    if jax.default_backend() != "tpu":
        raise NoChip(f"backend is {jax.default_backend()!r}, not 'tpu'")
    if os.environ.get("REPRO_PALLAS_INTERPRET", "").strip() not in ("",
                                                                     "0"):
        raise NoChip("Pallas interpretation is forced")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def device_record(devices) -> dict:
    import jax
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices) if d0.platform != "cpu"
            else len(jax.devices()),
            "memory_peak_bytes": max(peaks) if peaks else 0}


def bytes_in_use(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("bytes_in_use", 0))
