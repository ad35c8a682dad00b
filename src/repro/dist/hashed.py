"""Row-sharded chunk pool for the ROBE-style ``HashedStore``.

The hashed backend's memory is one (S, Z) pool; at pool sizes that
outgrow a device, ``shard_hashed`` row-shards the pool (and its
per-slot scales) over the "model" axis, and the lookups run the same
mine-mask + psum scheme as ``dist.packed``:

  1. every device hashes the (replicated) indices to GLOBAL pool slots
     — the hash family is stateless, so no slot table is exchanged,
  2. slots a device owns gather through the fused ``hashed_gather``
     kernel with everyone else's coefficients zeroed (the kernel skips
     zero-weight chunk DMAs entirely),
  3. one (B, D) psum assembles the replicated materialized rows.

``sharded_hashed_lookup_train`` is the differentiable twin: the local
op is the ``custom_vjp`` serving kernel, so the backward scatter-adds
into exactly the pool rows each shard owns and the psum transposes to
a replicated cotangent — no gradient collective over the pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.hashed_gather.autodiff import _hashed_train
from repro.kernels.hashed_gather.ops import hashed_gather, slot_plan
from repro.kernels import use_kernel

Array = jax.Array


def _pad_rows(x: Array, n: int) -> Array:
    s = x.shape[0]
    sp = -(-s // n) * n
    if sp != s:
        x = jnp.pad(x, [(0, sp - s)] + [(0, 0)] * (x.ndim - 1))
    return x


def shard_hashed(hs, mesh, axis: str = "model"):
    """Place a ``HashedStore`` with the pool row-sharded over ``axis``
    (padded up to a multiple of the axis size; the hash family only
    emits slots < the GLOBAL ``num_slots``, so padding rows are
    unaddressable).  The priority vector stays replicated — the serve
    fold and cache ranking read it host-side."""
    n = mesh.shape[axis]

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return hs._replace(
        pool=put(_pad_rows(hs.pool, n), P(axis, None)),
        pool_scale=put(_pad_rows(hs.pool_scale[:, None], n)[:, 0],
                       P(axis)),
        priority=put(hs.priority, P()))


def _local_coeff(slots: Array, coeff: Array, s_loc: int, axis: str):
    """Global slots -> (local slots, coefficients with other shards'
    entries zeroed).  The zero coefficient makes the kernel skip the
    slot's chunk DMA, so each pool row is read by exactly one shard."""
    i = jax.lax.axis_index(axis)
    loc = slots - i * s_loc
    mine = (loc >= 0) & (loc < s_loc)
    lc = jnp.clip(loc, 0, s_loc - 1)
    return lc, jnp.where(mine, coeff, 0.0)


def sharded_hashed_lookup(hs, cfg, indices: Array, *, mesh,
                          axis: str = "model",
                          use_pallas: bool | None = None) -> Array:
    """Distributed hashed materialization: int (...,) -> fp32 (..., D),
    replicated.  ``hs`` must be placed by ``shard_hashed``."""
    use_pallas = use_kernel(use_pallas)
    idx = jnp.asarray(indices)
    flat = idx.reshape(-1, 1)
    slots, coeff = slot_plan(flat, None, num_chunks=cfg.num_chunks,
                             num_hashes=cfg.num_hashes,
                             num_slots=cfg.num_slots, seed=cfg.seed)

    def local(pool, scale, sl, co):
        lc, cm = _local_coeff(sl, co, pool.shape[0], axis)
        out = hashed_gather(pool, scale, lc, cm,
                            num_chunks=cfg.num_chunks,
                            use_pallas=use_pallas)
        return jax.lax.psum(out, axis)

    out = shard_map(local, mesh=mesh,
                    in_specs=(P(axis, None), P(axis), P(), P()),
                    out_specs=P(), check_vma=False)(
        hs.pool, hs.pool_scale, slots, coeff)
    return out.reshape(*idx.shape, cfg.dim)


def sharded_hashed_lookup_train(pool: Array, indices: Array, *,
                                num_chunks: int, num_hashes: int,
                                num_slots: int, seed: int = 0,
                                mesh, axis: str = "model",
                                use_pallas: bool | None = None
                                ) -> Array:
    """Differentiable row-sharded hashed gather over the fp32 training
    pool: int (...,) -> fp32 (..., D), replicated.  ``num_slots`` is
    the GLOBAL pool size (the sharded ``pool`` argument may carry
    divisibility padding rows)."""
    use_pallas = use_kernel(use_pallas)
    idx = jnp.asarray(indices)
    flat = idx.reshape(-1, 1)
    slots, coeff = slot_plan(flat, None, num_chunks=num_chunks,
                             num_hashes=num_hashes,
                             num_slots=num_slots, seed=seed)

    def local(p, sl, co):
        lc, cm = _local_coeff(sl, co, p.shape[0], axis)
        out = _hashed_train(p, lc, cm, num_chunks, bool(use_pallas),
                            None, None)
        return jax.lax.psum(out, axis)

    out = shard_map(local, mesh=mesh,
                    in_specs=(P(axis, None), P(), P()),
                    out_specs=P(), check_vma=False)(pool, slots, coeff)
    return out.reshape(*idx.shape, out.shape[-1])


__all__ = [
    "shard_hashed",
    "sharded_hashed_lookup",
    "sharded_hashed_lookup_train",
]
