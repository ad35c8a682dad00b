"""Public op: fused dequant-bag -> first-matmul over the PackedStore.

``packed_bag_matmul(packed, indices, w)`` computes
``emb.reshape(B, F*D) @ w`` without materialising ``emb``: one fused
kernel call per tier (other-tier slots weight-0-skipped, exactly the
``packed_bag_lookup`` dispatch), partial (B, H) products summed.  The
per-slot dequant inside the kernel is bit-identical to
``packed_bag_lookup``'s; the bag accumulation can differ from
``packed_bag_lookup`` by 1 ulp (the lookup kernel's accumulate may
contract to an FMA — see the kernel docstring), and the downstream
matmul accumulates in fp32, so the fused result matches the unfused
bag->MLP reference to fp32 tolerance (bit-exactly at K=1 or with
unit slot weights).

``int8_direct=True`` additionally routes the int8 tier through the
kernel's scale-after-matmul specialisation (raw int8-converted rows on
the MXU, per-row ``scale * weight`` applied to the product) — the
"int8-in where all slots share a tier" path: slots of other tiers are
weight-masked out of that call anyway, so the specialisation is always
sound and saves the (B_block, D) dequant multiply.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core.packed_store import _IDX_MASK, _TIER_SHIFT, PackedStore
from repro.kernels import rows, use_kernel
from repro.kernels.bag_matmul.kernel import bag_matmul_pallas

Array = jax.Array

# the working set here adds the (K, D, H_block) weight block and the
# (B_block, D) fp32 rows scratch on top of dequant_bag's; budget
# accordingly (half of ~16 MiB/core VMEM)
_BM_VMEM_BUDGET = 8 << 20


@functools.lru_cache(maxsize=512)
def _auto_block_h(h: int) -> int:
    """Output-column block: the largest 128-aligned divisor of H that
    is <= 512; for awkward H > 512 with none, the 128-aligned block
    <= 512 that wastes the fewest padded columns (ties -> larger);
    otherwise the largest divisor <= 512."""
    divisors = [x for x in range(1, min(h, 512) + 1) if h % x == 0]
    aligned = [x for x in divisors if x % 128 == 0]
    if aligned:
        return max(aligned)
    if h > 512:
        return min((x for x in range(128, 513, 128)),
                   key=lambda x: (-(-h // x) * x - h, -x))
    return max(divisors)


def _bm_auto_block_b(b: int, k: int, d: int, block_h: int,
                     itemsize: int) -> int:
    """Largest power-of-two bag block (>= 8, the tile rule) whose VMEM
    working set fits ``_BM_VMEM_BUDGET`` (SMEM slot cap as in
    ``dequant_bag.ops._auto_block_b``)."""
    from repro.kernels.dequant_bag.ops import resolve_nbuf
    dp, r = rows.row_layout(d)
    g = rows.SUBLANES * max(1, 4 // itemsize)
    nbuf = resolve_nbuf(max(1, b))
    lanes_h = -(-block_h // rows.LANES) * rows.LANES
    lanes_d = -(-dp // rows.LANES) * rows.LANES
    fixed = (2 * k * dp * lanes_h * 4              # w block
             + nbuf * g * r * dp * itemsize)       # landing ring

    def fits(bb: int) -> bool:
        working = (fixed
                   + bb * lanes_d * 4              # fp32 rows scratch
                   + 2 * bb * lanes_h * 4)         # fp32 out tile
        return (working <= _BM_VMEM_BUDGET
                and bb * k <= rows.MAX_BLOCK_SLOTS)

    limit = rows.legal_block_b(max(1, b))
    block_b = rows.SUBLANES
    while block_b * 2 <= limit and fits(block_b * 2):
        block_b *= 2
    return block_b


def resolve_bm_block_sizes(b: int, k: int, d: int, h: int,
                           itemsize: int = 1,
                           block_b: int | None = None,
                           block_h: int | None = None,
                           dtype: str | None = None) -> tuple[int, int]:
    """(B_block, H_block) for the fused kernel.

    Same layering as ``dequant_bag.ops.resolve_block_b``: explicit
    argument > ``REPRO_BAGMM_BLOCK_B`` / ``REPRO_BAGMM_BLOCK_H`` env >
    measured autotune-cache hit (kind ``bag_matmul``, keyed on
    (B, K, D) with the output width folded in) > analytic pick.
    """
    from repro.kernels import autotune
    from repro.kernels.dequant_bag.ops import _cache_dtype
    for name, v in (("block_b", block_b), ("block_h", block_h)):
        if v is not None and v < 1:
            raise ValueError(f"{name} must be >= 1, got {v}")
    env_b = os.environ.get("REPRO_BAGMM_BLOCK_B")
    env_h = os.environ.get("REPRO_BAGMM_BLOCK_H")
    cached = None
    if block_b is None and block_h is None and not env_b and not env_h:
        cached = autotune.lookup_cached("bag_matmul",
                                        _cache_dtype(itemsize, dtype),
                                        b, k, d, extra=f"|h={h}",
                                        fields=("block_b", "block_h"))
    if block_h is None:
        if env_h:
            block_h = max(1, int(env_h))
        elif cached is not None:
            block_h = cached[1]
        else:
            block_h = _auto_block_h(h)
    if block_b is None:
        if env_b:
            block_b = max(1, int(env_b))
        elif cached is not None:
            block_b = cached[0]
        else:
            block_b = _bm_auto_block_b(b, k, d, int(block_h), itemsize)
    return int(block_b), int(block_h)


def _as_w3(w: Array, k: int, d: int) -> Array:
    if w.ndim == 2:
        if w.shape[0] != k * d:
            raise ValueError(f"w rows {w.shape[0]} != K*D {k * d}")
        return w.reshape(k, d, w.shape[1])
    if w.ndim == 3:
        return w
    raise ValueError(f"w must be (K*D, H) or (K, D, H), got {w.shape}")


def packed_bag_matmul(packed: PackedStore, indices: Array, w: Array,
                      weights: Array | None = None,
                      use_pallas: bool | None = None,
                      interpret: bool | None = None,
                      int8_direct: bool = False) -> Array:
    """indices (B, F), w (F*D, H) or (F, D, H) -> (B, H) fp32.

    The fused form of ``packed_bag_lookup(...).reshape(B, F*D) @ w``
    for per-field bags (the serving layout: slot f holds field f's
    row): the (B, F*D) fp32 embedding activations never round-trip
    through HBM.  ``use_pallas=None`` auto-selects the kernel on
    compiled backends and the unfused jnp reference under
    interpretation, mirroring ``packed_lookup_fused``.
    """
    b, f = indices.shape
    d = packed.dim
    w3 = _as_w3(w, f, d)
    use_pallas = use_kernel(use_pallas, interpret)
    if not use_pallas:
        from repro.core.packed_store import lookup
        rows = lookup(packed, indices)
        if weights is not None:
            rows = rows * weights[..., None].astype(jnp.float32)
        return jnp.einsum("bfd,fdh->bh", rows, w3.astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    code = jnp.take(packed.indirect, indices, axis=0)
    tier, loc = code >> _TIER_SHIFT, code & _IDX_MASK
    out = jnp.zeros((b, w3.shape[-1]), jnp.float32)
    for t, payload, scales in (
            (0, packed.payload8, packed.scale8),
            (1, packed.payload16, packed.scale16),
            (2, packed.payload32, None)):
        wt = (tier == t).astype(jnp.float32)
        if weights is not None:
            wt = wt * weights
        li = jnp.clip(loc, 0, payload.shape[0] - 1)
        out = out + bag_matmul_pallas(payload, scales, li, wt, w3,
                                      interpret=interpret,
                                      scale_after=int8_direct and t == 0)
    return out
