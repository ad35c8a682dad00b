"""Public op: fused hashed gather-and-combine over a chunk pool.

``slot_plan`` turns bag indices into the kernel's scalar-prefetched
addressing — per-(bag, chunk) pool slots plus sign-folded coefficients
— and ``hashed_gather`` dispatches the fused Pallas kernel or the jnp
oracle with the same auto-select rule as the dequant-bag family (the
oracle under interpretation, the kernel where the backend compiles it).

The bag block layers the measured autotune cache
(``kernels.autotune``, kind ``hashed_gather``) over the shared
analytic VMEM model; pool rows move whole, so only B_block is
resolved.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import use_kernel
from repro.kernels.dequant_bag.ops import resolve_block_b
from repro.kernels.hashed_gather.kernel import hashed_gather_pallas
from repro.kernels.hashed_gather.ref import hash_slots, hashed_gather_ref

Array = jax.Array


def resolve_hashed_block_b(b: int, t: int, z: int, itemsize: int = 4,
                           block_b: int | None = None,
                           dtype: str | None = None) -> int:
    """B_block (requests) for the hashed kernel: ``resolve_block_b``
    under the ``hashed_gather`` autotune-cache key, with a request's
    ``t`` slots as K and the chunk width Z as D."""
    return resolve_block_b(b, t, z, itemsize, block_b,
                           kind="hashed_gather", dtype=dtype)


def slot_plan(indices: Array, weights: Array | None, *,
              num_chunks: int, num_hashes: int, num_slots: int,
              seed: int = 0) -> tuple[Array, Array]:
    """Bag indices (B, K) [+ weights (B, K)] -> kernel addressing.

    Returns (slots, coeff), both (B, C*K*NH): chunk-major slot columns
    (all of chunk c's K*NH draws contiguous, matching the kernel's
    per-chunk grid step) and sign-folded coefficients.  Differentiable
    w.r.t. ``weights`` (the hash itself is integer-only).
    """
    b, k = indices.shape
    slots, signs = hash_slots(indices, num_chunks=num_chunks,
                              num_hashes=num_hashes,
                              num_slots=num_slots, seed=seed)
    # (B, K, C, NH) -> (B, C, K, NH) -> (B, C*K*NH)
    slots = slots.transpose(0, 2, 1, 3).reshape(b, -1)
    if weights is None:
        coeff = signs
    else:
        coeff = signs * weights.astype(jnp.float32)[:, :, None, None]
    coeff = coeff.transpose(0, 2, 1, 3).reshape(b, -1)
    return slots, coeff


def hashed_gather(pool: Array, scales: Array, slots: Array,
                  coeff: Array, *, num_chunks: int,
                  use_pallas: bool | None = None,
                  interpret: bool | None = None,
                  block_b: int | None = None,
                  nbuf: int | None = None) -> Array:
    """Dispatch the fused kernel or the jnp oracle (same contract as
    ``hashed_gather_ref``).  ``use_pallas=None`` auto-selects: the
    kernel when the backend compiles it for real, the oracle under
    interpretation."""
    use_pallas = use_kernel(use_pallas, interpret)
    if not use_pallas:
        return hashed_gather_ref(pool, scales, slots, coeff,
                                 num_chunks=num_chunks)
    return hashed_gather_pallas(pool, scales, slots, coeff,
                                num_chunks=num_chunks,
                                interpret=interpret, block_b=block_b,
                                nbuf=nbuf)


__all__ = [
    "hash_slots",
    "hashed_gather",
    "resolve_hashed_block_b",
    "slot_plan",
]
