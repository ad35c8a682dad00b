"""Public op: fused dequant embedding-bag over the tier-partitioned store.

``packed_bag_lookup`` runs one fused tiled kernel per tier (tier-local
indices come straight from the PackedStore indirection) and sums the
three partial bags — slots belonging to other tiers are masked by zero
weights, which the tiled kernel skips without issuing their row DMAs.
``packed_lookup_fused`` is the per-index (K = 1) specialisation: the
serving gather with no (B*K, D) fp32 intermediate, bit-identical to
``packed_store.lookup``.

The bag block comes from ``resolve_block_b``, which layers four
sources (highest wins): explicit call argument, the
``REPRO_DEQUANT_BLOCK_B`` env override, a **measured autotune cache**
entry (``kernels.autotune`` — a timing
sweep persisted per backend/kernel/dtype/shape, seeded out-of-band by
``benchmarks.kernels --seed-cache``), and finally the analytic
VMEM-budget model.  A cold cache miss therefore costs nothing: the
analytic pick is the answer, never an inline sweep.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core.packed_store import _IDX_MASK, _TIER_SHIFT, PackedStore
from repro.kernels import rows, use_kernel
from repro.kernels.dequant_bag.kernel import dequant_bag_pallas
from repro.kernels.dequant_bag.ref import dequant_bag_ref

Array = jax.Array

# VMEM budget for one grid step's working set — the fp32 output tile,
# the double-buffered row landing ring and the gathered scale/weight
# blocks; ~2 MiB leaves plenty of the ~16 MiB/core VMEM for the
# pipeline's other blocks
_VMEM_SCRATCH_BUDGET = 2 << 20

# default depth of the row-DMA landing ring (see kernel._tiled_kernel);
# REPRO_DEQUANT_NBUF overrides
_DEFAULT_NBUF = 4


def resolve_nbuf(nslots: int) -> int:
    """Landing-ring depth: env ``REPRO_DEQUANT_NBUF`` or the default,
    clamped to [1, nslots] (a tile never needs more buffers than it
    has row DMAs)."""
    env = os.environ.get("REPRO_DEQUANT_NBUF")
    nbuf = max(1, int(env)) if env else _DEFAULT_NBUF
    return max(1, min(nbuf, nslots))


@functools.lru_cache(maxsize=512)
def _auto_block_b(b: int, k: int, d: int, itemsize: int,
                  vmem_budget: int) -> int:
    """Largest power-of-two bag block (>= 8, the output tile's sublane
    rule) whose fp32 output tile, double-buffered, plus the landing
    ring of whole lane-dense rows of a width-``d`` table fits
    ``vmem_budget``, with at most ``rows.MAX_BLOCK_SLOTS`` slots per
    block (SMEM)."""
    dp, r = rows.row_layout(d)
    width = r * dp
    lanes = -(-dp // rows.LANES) * rows.LANES  # VMEM pads to full lanes
    g = rows.SUBLANES * max(1, 4 // itemsize)
    nbuf = resolve_nbuf(max(1, b) * k)

    def fits(bb: int) -> bool:
        working = (2 * bb * lanes * 4                # fp32 output tile
                   + nbuf * g * width * itemsize)    # row landing ring
        return (working <= vmem_budget
                and bb * k <= rows.MAX_BLOCK_SLOTS)

    limit = rows.legal_block_b(max(1, b))
    block_b = rows.SUBLANES
    while block_b * 2 <= limit and fits(block_b * 2):
        block_b *= 2
    return block_b


def _cache_dtype(itemsize: int, dtype: str | None) -> str:
    if dtype is not None:
        return dtype
    return {1: "int8", 2: "bfloat16", 4: "float32"}.get(
        itemsize, f"itemsize{itemsize}")


def resolve_block_b(b: int, k: int, d: int, itemsize: int = 1,
                    block_b: int | None = None,
                    vmem_budget: int = _VMEM_SCRATCH_BUDGET,
                    kind: str = "dequant_bag",
                    dtype: str | None = None) -> int:
    """The bag block (B_block) the tiled kernels run with.

    Precedence: explicit argument, then ``REPRO_DEQUANT_BLOCK_B`` (read
    per call, so changing it mid-process takes effect), then a measured
    autotune-cache hit for ``(backend, kind, dtype, b, k, d)``
    (``kernels.autotune``; read-only — a miss never triggers a sweep),
    then the analytic pick.  Whatever the source, the result is rounded
    up to the 8-row tile rule (``rows.legal_block_b``): the value
    returned is the block that runs.  Rows always move whole, so B is
    the only tiled dimension.
    """
    if block_b is not None and block_b < 1:
        raise ValueError(f"block_b must be >= 1, got {block_b}")
    if block_b is None:
        env_b = os.environ.get("REPRO_DEQUANT_BLOCK_B")
        if env_b:
            block_b = max(1, int(env_b))
        else:
            from repro.kernels import autotune
            cached = autotune.lookup_cached(
                kind, _cache_dtype(itemsize, dtype), b, k, d)
            block_b = (cached[0] if cached is not None
                       else _auto_block_b(b, k, d, itemsize, vmem_budget))
    return rows.legal_block_b(block_b)


def pick_block_b(b: int, k: int, d: int, itemsize: int = 1,
                 vmem_budget: int = _VMEM_SCRATCH_BUDGET) -> int:
    """B_block picker for the tiled kernel.

    Analytic layer: the largest power of two (>= 8) <= the 8-padded B
    whose working set — fp32 out tile + landing ring of whole
    lane-dense rows — fits the VMEM budget.  Measured autotune-cache
    hits and the env override layer on top (``resolve_block_b``).
    """
    return resolve_block_b(b, k, d, itemsize, vmem_budget=vmem_budget)


def dequant_bag_tpu(payload: Array, scales: Array | None, indices: Array,
                    weights: Array | None = None,
                    use_pallas: bool = True,
                    interpret: bool | None = None,
                    block_b: int | None = None) -> Array:
    if not use_pallas:
        return dequant_bag_ref(payload, scales, indices, weights)
    return dequant_bag_pallas(payload, scales, indices, weights,
                              interpret=interpret, block_b=block_b)


def _tier_split(packed: PackedStore, indices: Array):
    code = jnp.take(packed.indirect, indices, axis=0)
    return code >> _TIER_SHIFT, code & _IDX_MASK


def packed_bag_lookup(packed: PackedStore, indices: Array,
                      weights: Array | None = None,
                      use_pallas: bool = True,
                      interpret: bool | None = None) -> Array:
    """Bag-sum lookup over a PackedStore.  indices (B, K) -> (B, D) fp32.

    Each tier's rows are gathered by its own fused tiled kernel call
    with tier-local indices; slots belonging to other tiers get weight 0
    and are skipped in-kernel (no DMA issued).  Optional ``weights``
    (B, K) multiply per slot.
    """
    tier, loc = _tier_split(packed, indices)

    out = jnp.zeros((indices.shape[0], packed.dim), jnp.float32)
    for t, name, payload, scales in (
            (0, "int8", packed.payload8, packed.scale8),
            (1, "half", packed.payload16, packed.scale16),
            (2, "fp32", packed.payload32, None)):
        with jax.named_scope(f"gather_{name}"):
            w = (tier == t).astype(jnp.float32)
            if weights is not None:
                w = w * weights
            li = jnp.clip(loc, 0, payload.shape[0] - 1)
            out = out + dequant_bag_tpu(payload, scales, li, w,
                                        use_pallas=use_pallas,
                                        interpret=interpret)
    return out


def packed_lookup_fused(packed: PackedStore, indices: Array,
                        use_pallas: bool | None = None,
                        interpret: bool | None = None) -> Array:
    """Fused per-index serving gather.  int (...,) -> fp32 (..., D).

    The K = 1 specialisation of ``packed_bag_lookup``: one tiled kernel
    call per tier, no (N, D) per-tier fp32 intermediates and no
    three-way select — each slot's row is produced by exactly one tier's
    kernel (the others skip it), so the sum is **bit-identical** to
    ``packed_store.lookup``.

    ``use_pallas=None`` resolves through ``kernels.use_kernel``: the
    kernel on a TPU backend, always; the jnp oracle only off the TPU
    under interpretation (where the interpreter would throttle
    serving).
    """
    if not use_kernel(use_pallas, interpret):
        from repro.core.packed_store import lookup
        return lookup(packed, indices)
    flat = indices.reshape(-1, 1)
    out = packed_bag_lookup(packed, flat, use_pallas=True,
                            interpret=interpret)
    return out.reshape(*indices.shape, packed.dim)
