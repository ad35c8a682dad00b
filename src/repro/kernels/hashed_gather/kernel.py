"""Fused chunk-pool gather + sign/scale combine on the bag kernel.

The hashed-store serving hot path.  XLA lowers a hashed materialization
to gather(pool) -> gather(scale) -> multiply -> reshape -> segment-sum,
materialising the (B, C*T, Z) chunk intermediate in HBM.  Each output
chunk ``out[b, c*Z:(c+1)*Z]`` is a bag of the ``T = K * num_hashes``
pool rows in ``slots[b, c*T:(c+1)*T]``, scaled by the pool scales and
the sign-folded coefficients — exactly ``dequant_bag``'s operation over
the (S, Z) pool, with (bag, chunk) pairs as the bags.  So the forward
runs ``dequant_bag_pallas`` on reshaped operands: one kernel for both
stores, the same landing ring, lane-dense row view and zero-coefficient
DMA skip, and the same per-bag t order as the jnp oracle's per-chunk
reduction.  The backward (``autodiff``) is ``bag_grad`` on the same
reshape.
"""

from __future__ import annotations

import jax

from repro.kernels.dequant_bag.kernel import dequant_bag_pallas

Array = jax.Array


def hashed_gather_pallas(pool: Array, scales: Array, slots: Array,
                         coeff: Array, *, num_chunks: int,
                         interpret: bool | None = None,
                         block_b: int | None = None,
                         nbuf: int | None = None) -> Array:
    """pool (S, Z), scales (S,), slots/coeff (B, C*T) -> (B, C*Z) fp32.

    ``block_b`` counts requests (each one ``num_chunks`` kernel bags)
    and defaults to ``ops.resolve_hashed_block_b`` (measured autotune
    cache under the ``hashed_gather`` key, analytic VMEM model
    underneath); ``nbuf`` and ``interpret`` as in ``dequant_bag``.
    """
    b = slots.shape[0]
    t = slots.shape[1] // num_chunks
    z = pool.shape[1]
    from repro.kernels.hashed_gather.ops import resolve_hashed_block_b
    # a request holds num_chunks * t slots of the kernel's bag block
    block_b = resolve_hashed_block_b(b, num_chunks * t, z,
                                     pool.dtype.itemsize, block_b,
                                     dtype=str(pool.dtype))
    out = dequant_bag_pallas(pool, scales,
                             slots.reshape(b * num_chunks, t),
                             coeff.reshape(b * num_chunks, t),
                             interpret=interpret,
                             block_b=block_b * num_chunks, nbuf=nbuf)
    return out.reshape(b, num_chunks * z)
