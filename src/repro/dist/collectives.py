"""Hand-written shard_map collectives.

``split_kv_decode_attention`` is the distributed decode hot path: the KV
cache is sharded along the sequence axis (each device owns S/n cache
slots), every device attends its local slots with a local log-sum-exp,
and one psum renormalizes the partial softmaxes — the flash-attention
combine rule across devices instead of across chunks:

    out = sum_i exp(m_i - m) * num_i / sum_i exp(m_i - m) * l_i

where (m_i, l_i, num_i) are the per-shard (max, denominator, weighted-V
accumulator) and m = pmax_i m_i.  Exactly matches a full softmax over
the valid cache prefix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def split_kv_decode_attention(mesh, q, k, v, cache_len, scale,
                              axis: str = "model"):
    """Split-KV single-token decode attention.

    q: (B, H, D) current query; k, v: (B, S, H, D) cache, sharded along S
    over ``axis``; cache_len: scalar — slots with position > cache_len
    are masked.  Returns (B, H, D), replicated.
    """
    n = mesh.shape[axis]
    s = k.shape[1]
    if s % n:
        raise ValueError(f"cache length {s} not divisible by "
                         f"{axis}={n}")

    def local(q, k, v, cache_len):
        i = jax.lax.axis_index(axis)
        s_loc = k.shape[1]
        pos = i * s_loc + jnp.arange(s_loc)
        sc = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
        valid = (pos <= cache_len)[None, None, :]
        sc = jnp.where(valid, sc, NEG_INF)
        m = jnp.max(sc, axis=-1)                       # (B, H) local max
        p = jnp.where(valid, jnp.exp(sc - m[..., None]), 0.0)
        l = p.sum(axis=-1)                             # local denominator
        num = jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32))
        m_glob = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_glob)                     # shard renorm
        num = jax.lax.psum(num * corr[..., None], axis)
        den = jax.lax.psum(l * corr, axis)
        return (num / den[..., None]).astype(q.dtype)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, axis), P(None, axis), P()),
        out_specs=P(), check_vma=False)(q, k, v, cache_len)
