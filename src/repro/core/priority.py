"""Frequency-based row priority scores (SHARK Eq. 7).

    w_r^(t+1) = (1 - beta) * w_r^(t) + beta * (alpha * c+ + c-)

where c+ / c- are the number of positive / negative examples in the batch
whose feature values hit row r.  alpha (=2 in the paper) up-weights
positives, beta (=0.99) is the time-decay rate.  The decay applies to every
row each batch (Eq. 7 is written per row per step); rows not touched this
batch simply have c+ = c- = 0.

On TPU this is a dense segment-sum over the batch's flattened row indices —
no host round trip, no hash map (the paper's PS stack updates scores host-
side).  For sharded tables each shard computes counts for its local rows
from the *global* index stream (indices are replicated); see
repro/dist/sharding.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class PriorityConfig(NamedTuple):
    alpha: float = 2.0   # importance weight of positive examples
    beta: float = 0.99   # time-decay rate


def batch_counts(indices: Array, labels: Array, vocab: int,
                 valid: Array | None = None) -> tuple[Array, Array]:
    """Per-row positive/negative hit counts for one batch.

    indices: int32 (B, F) or (B,) or flat (B*F,) paired with per-sample
      ``labels`` (B,) in {0, 1}.  Multi-hot bags should pass the flattened
      indices with labels repeated per bag element.
    valid: optional bool mask matching ``indices`` (padding exclusion).

    Returns (c_pos, c_neg), each float32 (vocab,).
    """
    if indices.ndim == 2:
        b, f = indices.shape
        lab = jnp.broadcast_to(labels[:, None], (b, f)).reshape(-1)
        idx = indices.reshape(-1)
        val = None if valid is None else valid.reshape(-1)
    else:
        idx = indices.reshape(-1)
        lab = labels.reshape(-1)
        val = None if valid is None else valid.reshape(-1)
    pos = lab.astype(jnp.float32)
    neg = 1.0 - pos
    if val is not None:
        m = val.astype(jnp.float32)
        pos, neg = pos * m, neg * m
    c_pos = jax.ops.segment_sum(pos, idx, num_segments=vocab)
    c_neg = jax.ops.segment_sum(neg, idx, num_segments=vocab)
    return c_pos, c_neg


@jax.named_scope("priority_update")
def priority_update(w: Array, c_pos: Array, c_neg: Array,
                    cfg: PriorityConfig = PriorityConfig()) -> Array:
    """One Eq. 7 step.  w, c_pos, c_neg: (vocab,) float32."""
    target = cfg.alpha * c_pos + c_neg  # alpha*c+ + c-
    return (1.0 - cfg.beta) * w + cfg.beta * target


def priority_update_from_batch(w: Array, indices: Array, labels: Array,
                               cfg: PriorityConfig = PriorityConfig(),
                               valid: Array | None = None) -> Array:
    c_pos, c_neg = batch_counts(indices, labels, w.shape[0], valid)
    return priority_update(w, c_pos, c_neg, cfg)


@jax.named_scope("access_counts")
def access_counts(indices: Array, vocab: int,
                  valid: Array | None = None) -> Array:
    """Label-free per-row hit counts for a serving batch.

    Online traffic has no labels at lookup time (clicks arrive minutes
    later, if ever), so every access counts as one unlabeled example.
    indices: int any shape; returns float32 (vocab,).
    """
    idx = indices.reshape(-1)
    ones = jnp.ones(idx.shape, jnp.float32)
    if valid is not None:
        ones = ones * valid.reshape(-1).astype(jnp.float32)
    return jax.ops.segment_sum(ones, idx, num_segments=vocab)


def serve_update(w: Array, indices: Array,
                 cfg: PriorityConfig = PriorityConfig(),
                 valid: Array | None = None) -> Array:
    """Serving-time Eq. 7 fold: accesses enter the EMA as c- (c+ = 0).

    This is what keeps the tier assignment tracking *live* traffic after
    training stops — the repro.serve loop calls it per request batch and
    periodically re-tiers from the updated scores (packed_store.
    repack_delta).
    """
    c = access_counts(indices, w.shape[0], valid)
    return priority_update(w, jnp.zeros_like(c), c, cfg)


def steady_state_priority(rate_pos: Array, rate_neg: Array,
                          cfg: PriorityConfig = PriorityConfig()) -> Array:
    """Fixed point of Eq. 7 under stationary per-batch hit rates.

    w* = beta * (alpha*rate+ + rate-) / (1 - (1-beta)) = alpha*rate+ + rate-
    modulo the beta mixing; with beta=0.99 the EMA converges to
    ~(alpha*rate+ + rate-).  Used by tests and by the tier planner to seed
    priorities from dataset statistics without a warm-up epoch.
    """
    return cfg.alpha * rate_pos + rate_neg
