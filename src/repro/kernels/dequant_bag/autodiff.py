"""Differentiable fused embedding-bag: the QAT training hot path.

``bag_lookup_train`` / ``lookup_train`` run the *serving* kernels in
training: the forward is the tiled dequant-bag gather
(``dequant_bag_pallas`` with unit scales over the fp32 tier-exact QAT
table — bit-identical to what the packed serving store would produce,
because ``qat_store.snap`` keeps every row on its tier's representable
grid), and the backward is the scatter-add transpose kernel
(``bag_grad_pallas``), registered via ``jax.custom_vjp``.  Training and
serving therefore exercise the same kernel family — the paper's
low-precision-training story closed end to end.

Cotangents:

  * table   — the Pallas scatter kernel (tiled grid, K looped
              in-kernel, slot contributions segment-summed into per-row
              gradients); jnp ``segment_sum`` oracle as XLA fallback,
  * weights — per-slot row-cotangent dots (jnp; weights are masks in
              the serving layout, so this path is cold),
  * indices — integer: float0 (non-differentiable).

``use_pallas=None`` auto-selects like the serving ops: the fused
kernels where the backend compiles them (TPU), the bit-equivalent jnp
oracles under interpretation.  The row-sharded form lives in
``repro.dist.packed.sharded_lookup_train`` (per-shard custom_vjp under
``shard_map``; the psum transposes to a replicated cotangent).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import use_kernel
from repro.kernels.dequant_bag.kernel import (
    bag_grad_pallas,
    bag_grad_pallas_rowgrid,
    dequant_bag_pallas,
)
from repro.kernels.dequant_bag.ref import bag_grad_ref, dequant_bag_ref

Array = jax.Array


def bag_grad_tpu(g: Array, scales: Array | None, indices: Array,
                 weights: Array | None, vocab: int,
                 use_pallas: bool = True,
                 interpret: bool | None = None,
                 block_b: int | None = None) -> Array:
    """Scatter-add bag transpose with the forward ops' dispatch shape:
    the tiled Pallas kernel, or the jnp ``segment_sum`` oracle."""
    if not use_pallas:
        return bag_grad_ref(g, scales, indices, weights, vocab)
    return bag_grad_pallas(g, scales, indices, weights, vocab,
                           interpret=interpret, block_b=block_b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bag_train(table: Array, indices: Array, weights: Array,
               use_pallas: bool, interpret: bool | None,
               block_b: int | None) -> Array:
    ones = jnp.ones((table.shape[0],), jnp.float32)
    if not use_pallas:
        return dequant_bag_ref(table, ones, indices, weights)
    return dequant_bag_pallas(table, ones, indices, weights,
                              interpret=interpret, block_b=block_b)


def _bag_train_fwd(table, indices, weights, use_pallas, interpret,
                   block_b):
    out = _bag_train(table, indices, weights, use_pallas, interpret,
                     block_b)
    return out, (table, indices, weights)


def _bag_train_bwd(use_pallas, interpret, block_b, res, g):
    table, indices, weights = res
    dtable = bag_grad_tpu(g, None, indices, weights, table.shape[0],
                          use_pallas=use_pallas, interpret=interpret,
                          block_b=block_b)
    rows = jnp.take(table, indices, axis=0).astype(jnp.float32)
    dweights = jnp.einsum("bkd,bd->bk", rows, g.astype(jnp.float32))
    didx = np.zeros(indices.shape, dtype=jax.dtypes.float0)
    return dtable.astype(table.dtype), didx, dweights


_bag_train.defvjp(_bag_train_fwd, _bag_train_bwd)


def bag_lookup_train(table: Array, indices: Array,
                     weights: Array | None = None, *,
                     use_pallas: bool | None = None,
                     interpret: bool | None = None,
                     block_b: int | None = None) -> Array:
    """Differentiable embedding bag through the serving kernels.

    table (V, D) fp32, indices (B, K) -> (B, D) fp32 bag sums;
    ``weights`` (B, K) multiply per slot (0 skips the slot's DMA in
    both directions).  Gradients w.r.t. ``table`` run the scatter-add
    Pallas kernel; w.r.t. ``weights`` the jnp row-dot path.
    """
    use_pallas = use_kernel(use_pallas, interpret)
    b, k = indices.shape
    if weights is None:
        weights = jnp.ones((b, k), jnp.float32)
    return _bag_train(table, indices, weights, bool(use_pallas),
                      interpret, block_b)


def lookup_train(table: Array, indices: Array, *,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None) -> Array:
    """Differentiable gather: int (...,) -> fp32 (..., D).

    The K = 1 bag specialisation — no accumulation, so the forward is
    bit-identical to ``jnp.take`` on the tier-exact table and the
    backward is a pure scatter-add.  This is the training form of
    ``packed_store.lookup_fused``.
    """
    flat = indices.reshape(-1, 1)
    out = bag_lookup_train(table, flat, use_pallas=use_pallas,
                           interpret=interpret)
    return out.reshape(*indices.shape, table.shape[1])


__all__ = [
    "bag_grad_tpu",
    "bag_grad_pallas",
    "bag_grad_pallas_rowgrid",
    "bag_lookup_train",
    "lookup_train",
]
