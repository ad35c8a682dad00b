"""Pure-jnp oracles for the fused dequant embedding-bag lookup and its
scatter-add backward."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.rows import take_rows

Array = jax.Array


def dequant_bag_ref(payload: Array, scales: Array | None, indices: Array,
                    weights: Array | None = None) -> Array:
    """payload (V, D) int8|bf16|fp32, scales (V,) fp32 or None (unit
    scales), indices (B, K) -> bags (B, D) fp32:
    out[b] = sum_k scale[i_bk] * payload[i_bk].

    weights: optional (B, K) per-slot weights (0 masks padding slots).
    ``payload`` may be a ``kernels.rows.LaneDense`` (a placed store's).
    """
    rows = take_rows(payload, indices).astype(jnp.float32)
    if scales is not None:
        rows = rows * jnp.take(scales, indices, axis=0)[..., None]
    if weights is not None:
        rows = rows * weights[..., None]
    return rows.sum(axis=1)


def bag_grad_ref(g: Array, scales: Array | None, indices: Array,
                 weights: Array | None, vocab: int) -> Array:
    """Transpose of ``dequant_bag_ref`` w.r.t. the payload: scatter-add.

    g (B, D) fp32 cotangent, indices (B, K) -> dtable (vocab, D) fp32:

        dtable[i] = sum_{(b,k): idx[b,k] == i} weight[b,k] * scale[i] * g[b]

    ``scales=None`` means unit scales (the fp32 training table);
    ``weights=None`` means unit weights.  This is the XLA fallback and
    the oracle for the Pallas scatter kernel — a ``segment_sum`` over
    the flattened slot contributions, so duplicated rows accumulate in
    XLA's reduction order (the kernel accumulates in (b, k)
    lexicographic order; the two agree to fp32 tolerance, exactly when
    no row is duplicated within a batch).
    """
    b, k = indices.shape
    coeff = jnp.ones((b, k), jnp.float32) if weights is None \
        else weights.astype(jnp.float32)
    if scales is not None:
        coeff = coeff * jnp.take(scales, indices, axis=0)
    contrib = (coeff[..., None] * g.astype(jnp.float32)[:, None, :])
    return jax.ops.segment_sum(contrib.reshape(b * k, -1),
                               indices.reshape(-1), num_segments=vocab)
