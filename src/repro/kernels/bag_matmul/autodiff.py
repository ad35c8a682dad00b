"""Differentiable fused bag->matmul: training runs the serving kernel.

``bag_matmul_train`` mirrors ``dequant_bag.autodiff.bag_lookup_train``
one fusion level up: the forward is the serving ``bag_matmul`` kernel
over the fp32 tier-exact QAT table (unit scales), and the backward
reuses the serving scatter-add kernel for the table cotangent —
each slot's row gradient ``weight[b,k] * (g[b] @ w3[k]^T)`` is
scattered by ``bag_grad`` with the slots flattened to (B*K, 1) bags.
Weight-matrix and per-slot-weight cotangents take the jnp einsum path
(dense, not memory-bound).  ``use_pallas=None`` auto-selects like the
serving ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import use_kernel
from repro.kernels.bag_matmul.kernel import bag_matmul_pallas
from repro.kernels.bag_matmul.ref import bag_matmul_ref
from repro.kernels.dequant_bag.autodiff import bag_grad_tpu

Array = jax.Array


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bm_train(table: Array, indices: Array, weights: Array, w3: Array,
              use_pallas: bool, interpret: bool | None) -> Array:
    ones = jnp.ones((table.shape[0],), jnp.float32)
    if not use_pallas:
        return bag_matmul_ref(table, ones, indices, weights, w3)
    return bag_matmul_pallas(table, ones, indices, weights, w3,
                             interpret=interpret)


def _bm_train_fwd(table, indices, weights, w3, use_pallas, interpret):
    out = _bm_train(table, indices, weights, w3, use_pallas, interpret)
    return out, (table, indices, weights, w3)


def _bm_train_bwd(use_pallas, interpret, res, g):
    table, indices, weights, w3 = res
    b, k = indices.shape
    v, d = table.shape
    g = g.astype(jnp.float32)
    w3f = w3.astype(jnp.float32)
    # per-slot row cotangent g'[b,k] = g[b] @ w3[k]^T; the scatter into
    # the table runs the serving bag_grad kernel with every slot its
    # own one-index bag and the slot weight as the coefficient
    gk = jnp.einsum("bh,kdh->bkd", g, w3f)
    dtable = bag_grad_tpu(gk.reshape(b * k, d), None,
                          indices.reshape(-1, 1),
                          weights.reshape(-1, 1).astype(jnp.float32),
                          v, use_pallas=use_pallas, interpret=interpret)
    rows = jnp.take(table, indices, axis=0).astype(jnp.float32)
    wf = weights.astype(jnp.float32)
    dw3 = jnp.einsum("bkd,bh->kdh", rows * wf[..., None], g)
    dweights = jnp.einsum("bkd,kdh,bh->bk", rows, w3f, g)
    didx = np.zeros(indices.shape, dtype=jax.dtypes.float0)
    return (dtable.astype(table.dtype), didx, dweights,
            dw3.astype(w3.dtype))


_bm_train.defvjp(_bm_train_fwd, _bm_train_bwd)


def bag_matmul_train(table: Array, indices: Array, w: Array,
                     weights: Array | None = None, *,
                     use_pallas: bool | None = None,
                     interpret: bool | None = None) -> Array:
    """Differentiable fused bag->matmul through the serving kernels.

    table (V, D) fp32, indices (B, K), w (K*D, H) or (K, D, H)
    -> (B, H) fp32.  Equals
    ``bag_lookup-per-field.reshape(B, K*D) @ w`` with the (B, K*D)
    activations never materialised; gradients w.r.t. ``table`` run the
    Pallas scatter kernel.
    """
    use_pallas = use_kernel(use_pallas, interpret)
    b, k = indices.shape
    d = table.shape[1]
    if weights is None:
        weights = jnp.ones((b, k), jnp.float32)
    w3 = w.reshape(k, d, -1) if w.ndim == 2 else w
    return _bm_train(table, indices, weights, w3, bool(use_pallas),
                     interpret)
