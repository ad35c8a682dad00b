"""Differentiable hashed gather: the compositional training hot path.

``hashed_bag_lookup_train`` / ``hashed_lookup_train`` run the *serving*
kernel in training: the forward is the fused chunk-pool
gather-and-combine (``hashed_gather_pallas`` with unit pool scales over
the fp32 training pool) and the backward scatter-adds the chunked
cotangent into the pool through the existing ``bag_grad`` scatter
kernel — each (bag, chunk) pair is one bag of ``K * num_hashes`` slots
over the (S, Z) pool, so the transpose IS ``dequant_bag``'s transpose
on reshaped operands, bit-for-bit the same RMW kernel with the same
(b, c, t) lexicographic accumulation order.

Cotangents:

  * pool    — ``bag_grad`` Pallas scatter kernel (jnp ``segment_sum``
              oracle as the interpret/XLA fallback),
  * weights — flows through ``slot_plan``'s sign fold outside the
              ``custom_vjp`` (per-slot chunk-cotangent dots),
  * indices — integer: float0 (non-differentiable; re-hashed, never
              stored).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import use_kernel
from repro.kernels.dequant_bag.autodiff import bag_grad_tpu
from repro.kernels.hashed_gather.kernel import hashed_gather_pallas
from repro.kernels.hashed_gather.ref import hashed_gather_ref
from repro.kernels.hashed_gather.ops import slot_plan

Array = jax.Array


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _hashed_train(pool: Array, slots: Array, coeff: Array,
                  num_chunks: int, use_pallas: bool,
                  interpret: bool | None,
                  block_b: int | None) -> Array:
    ones = jnp.ones((pool.shape[0],), jnp.float32)
    if not use_pallas:
        return hashed_gather_ref(pool, ones, slots, coeff,
                                 num_chunks=num_chunks)
    return hashed_gather_pallas(pool, ones, slots, coeff,
                                num_chunks=num_chunks,
                                interpret=interpret, block_b=block_b)


def _hashed_train_fwd(pool, slots, coeff, num_chunks, use_pallas,
                      interpret, block_b):
    out = _hashed_train(pool, slots, coeff, num_chunks, use_pallas,
                        interpret, block_b)
    return out, (pool, slots, coeff)


def _hashed_train_bwd(num_chunks, use_pallas, interpret, block_b,
                      res, g):
    pool, slots, coeff = res
    b = g.shape[0]
    z = pool.shape[1]
    t = slots.shape[1] // num_chunks
    # each (bag, chunk) is one T-slot bag over the pool: the pool
    # cotangent is exactly bag_grad on the chunked reshape
    g2 = g.astype(jnp.float32).reshape(b * num_chunks, z)
    s2 = slots.reshape(b * num_chunks, t)
    c2 = coeff.reshape(b * num_chunks, t)
    dpool = bag_grad_tpu(g2, None, s2, c2, pool.shape[0],
                         use_pallas=use_pallas, interpret=interpret)
    rows = jnp.take(pool, slots, axis=0).astype(jnp.float32)
    gc = g.astype(jnp.float32).reshape(b, num_chunks, 1, z)
    dcoeff = jnp.einsum("bcez,bctz->bct", gc,
                        rows.reshape(b, num_chunks, t, z)
                        ).reshape(b, num_chunks * t)
    dslots = np.zeros(slots.shape, dtype=jax.dtypes.float0)
    return dpool.astype(pool.dtype), dslots, dcoeff


_hashed_train.defvjp(_hashed_train_fwd, _hashed_train_bwd)


def hashed_bag_lookup_train(pool: Array, indices: Array,
                            weights: Array | None = None, *,
                            num_chunks: int, num_hashes: int,
                            seed: int = 0,
                            use_pallas: bool | None = None,
                            interpret: bool | None = None,
                            block_b: int | None = None) -> Array:
    """Differentiable hashed embedding bag through the serving kernel.

    pool (S, Z) fp32, indices (B, K) -> (B, C*Z) fp32 bag sums;
    ``weights`` (B, K) multiply per slot (0 skips the slot's chunk DMA
    in both directions).  Gradients w.r.t. ``pool`` run the scatter-add
    Pallas kernel; w.r.t. ``weights`` the sign-folded chunk-dot path.
    """
    use_pallas = use_kernel(use_pallas, interpret)
    slots, coeff = slot_plan(indices, weights, num_chunks=num_chunks,
                             num_hashes=num_hashes,
                             num_slots=pool.shape[0], seed=seed)
    return _hashed_train(pool, slots, coeff, num_chunks,
                         bool(use_pallas), interpret, block_b)


def hashed_lookup_train(pool: Array, indices: Array, *,
                        num_chunks: int, num_hashes: int,
                        seed: int = 0,
                        use_pallas: bool | None = None,
                        interpret: bool | None = None) -> Array:
    """Differentiable hashed gather: int (...,) -> fp32 (..., C*Z).

    The K = 1 bag specialisation — the training form of the hashed
    serving materialization, matching it bit-for-bit (same hash family,
    same per-chunk accumulation order).
    """
    flat = indices.reshape(-1, 1)
    out = hashed_bag_lookup_train(pool, flat, num_chunks=num_chunks,
                                  num_hashes=num_hashes, seed=seed,
                                  use_pallas=use_pallas,
                                  interpret=interpret)
    return out.reshape(*indices.shape, out.shape[-1])


__all__ = [
    "hashed_bag_lookup_train",
    "hashed_lookup_train",
]
