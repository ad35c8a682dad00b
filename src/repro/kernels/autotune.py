"""Measured autotune cache for Pallas kernel block sizes.

``dequant_bag.ops.resolve_block_b`` (and
``bag_matmul.ops.resolve_bm_block_sizes``) layer a measured pick over
their analytic VMEM-budget model.  This module is that layer: a timing
sweep over candidate tilings per ``(backend, kernel, dtype, B, K, D)``
key, persisted to a versioned JSON cache so serving processes never
pay the sweep.  An entry holds the kernel's named blocks
(``block_b``; ``block_b`` and ``block_h`` for bag_matmul) and the
measured time.

Contract:

  * The serving path only ever **reads** the cache
    (``lookup_cached``); a cold miss falls back to the analytic pick.
    Runtime never times kernels inline.
  * Sweeps run out-of-band — ``benchmarks.kernels --seed-cache`` on the
    target backend, or the CI ``autotune-smoke`` job on the interpret
    backend — and write through ``store``.
  * Cache location: ``REPRO_AUTOTUNE_CACHE`` env var, else
    ``results/autotune.json`` under the repo root.  An empty env value
    disables the cache entirely.
  * Invalidation: a file whose ``schema`` field is not
    ``autotune_cache/v1`` — or that does not parse, or whose entry is
    malformed — is ignored wholesale (analytic fallback, never an
    error).  Keys embed backend + shape + dtype, so a mesh/backend
    change is a key miss, not a stale hit.

The in-memory copy reloads when the file's mtime or path changes, so
a sweep seeded by another process is picked up without a restart.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax

from repro import REPO_ROOT

CACHE_SCHEMA = "autotune_cache/v1"
DEFAULT_CACHE_PATH = str(REPO_ROOT / "results" / "autotune.json")

_ENV = "REPRO_AUTOTUNE_CACHE"


def cache_path() -> str | None:
    """Resolved cache file path; None when the cache is disabled."""
    p = os.environ.get(_ENV)
    if p is None:
        return DEFAULT_CACHE_PATH
    return p or None  # empty string disables


def backend_name() -> str:
    """Cache-key backend: the compiled target, or "interpret" when the
    kernels run under the Pallas interpreter (block timings there are
    interpreter timings, not TPU timings — they must never be served
    to a compiled backend, hence the distinct key)."""
    from repro.kernels import should_interpret
    return "interpret" if should_interpret(None) else jax.default_backend()


def cache_key(kernel: str, dtype: str, b: int, k: int, d: int,
              extra: str = "") -> str:
    return (f"{backend_name()}|{kernel}|{dtype}"
            f"|b={int(b)}|k={int(k)}|d={int(d)}{extra}")


# --------------------------------------------------------------------- I/O

# (path, mtime_ns) -> entries dict; one stat() per lookup, one read per
# file change
_loaded: dict = {"path": None, "mtime": None, "entries": {}}


def _read_entries(path: str) -> dict:
    try:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict) or doc.get("schema") != CACHE_SCHEMA:
            return {}
        entries = doc.get("entries")
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError):
        # missing, unreadable or corrupt cache: behave as empty
        return {}


def _entries() -> dict:
    path = cache_path()
    if path is None:
        return {}
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        mtime = None
    if _loaded["path"] != path or _loaded["mtime"] != mtime:
        _loaded["entries"] = _read_entries(path) if mtime is not None else {}
        _loaded["path"] = path
        _loaded["mtime"] = mtime
    return _loaded["entries"]


def lookup_cached(kernel: str, dtype: str, b: int, k: int, d: int,
                  extra: str = "", fields: tuple[str, ...] = ("block_b",)
                  ) -> tuple[int, ...] | None:
    """The entry's ``fields`` (block sizes) for the key, or None on a
    miss or a malformed entry."""
    e = _entries().get(cache_key(kernel, dtype, b, k, d, extra))
    if not isinstance(e, dict):
        return None
    vals = tuple(e.get(f) for f in fields)
    if all(isinstance(v, int) and v >= 1 for v in vals):
        return vals
    return None


def store(kernel: str, dtype: str, b: int, k: int, d: int,
          blocks: dict[str, int], us: float,
          extra: str = "") -> str | None:
    """Write one measured entry (``blocks``: name -> size) through to
    the cache file (atomic replace, other entries preserved).  Returns
    the path written."""
    path = cache_path()
    if path is None:
        return None
    entries = dict(_read_entries(path))
    entries[cache_key(kernel, dtype, b, k, d, extra)] = {
        **{name: int(v) for name, v in blocks.items()}, "us": float(us)}
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"schema": CACHE_SCHEMA, "entries": entries}, f,
                  indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    _loaded["mtime"] = None  # force reload on next lookup
    return path


# ----------------------------------------------------------------- sweeps


def time_us(fn: Callable[[], jax.Array], iters: int = 3,
            warmup: int = 1) -> float:
    """min-of-N wall time of ``fn`` in microseconds (block_until_ready)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def sweep(run: Callable[..., Callable[[], jax.Array]],
          candidates: list[tuple[int, ...]], iters: int = 3) -> dict:
    """Time ``run(*blocks)()`` for every candidate tiling.

    Returns ``{"best": blocks, "best_us": t, "sweep": [...]}`` with one
    ``{"blocks", "us"}`` row per candidate.  Candidates that fail to
    build/launch are recorded with ``us: None`` and excluded from
    ``best`` (a tiling the backend rejects must never win).
    """
    rows = []
    best, best_us = None, float("inf")
    for blocks in candidates:
        blocks = tuple(blocks)
        try:
            us = time_us(run(*blocks), iters=iters)
        except Exception:
            rows.append({"blocks": list(blocks), "us": None})
            continue
        rows.append({"blocks": list(blocks), "us": us})
        if us < best_us:
            best, best_us = blocks, us
    if best is None:
        raise RuntimeError("autotune sweep: every candidate failed")
    return {"best": best, "best_us": best_us, "sweep": rows}


def candidate_block_b(b: int, k: int, d: int, itemsize: int = 1
                      ) -> list[int]:
    """Bag-block candidates around the analytic pick, analytic first.

    Every candidate obeys the 8-row tile rule, so each one times the
    block it names.  The analytic pick is always among them, so a
    measured winner is by construction no slower than the analytic
    model on the swept backend — the invariant ``bench_kernel/v1``
    asserts.
    """
    from repro.kernels import rows
    from repro.kernels.dequant_bag.ops import (_VMEM_SCRATCH_BUDGET,
                                               _auto_block_b)
    ab = _auto_block_b(b, k, d, itemsize, _VMEM_SCRATCH_BUDGET)
    limit = rows.legal_block_b(b)
    rest = {rows.legal_block_b(x) for x in (ab // 2, ab * 2, 8, limit)}
    return [ab] + sorted(x for x in rest if x != ab and x <= limit)
