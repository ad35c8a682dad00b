"""Row-sharded serving path for the tier-partitioned PackedStore.

At terabyte-table scale the packed payloads cannot live on one device.
``shard_packed`` row-shards every payload/scale array over the "model"
axis and replicates the 4-byte ``indirect`` word (V * 4 bytes — the only
per-row state every device needs).  ``sharded_lookup`` /
``sharded_bag_lookup`` then run the SHARK serving gather as:

  1. every device decodes tier/local-index from the replicated indirect,
  2. gathers + dequantizes the rows IT owns (others contribute zeros),
  3. one psum assembles full embeddings (lookup) or per-bag sums (bag).

For the bag path the psum moves (num_bags, D) floats — independent of
bag sizes — so the collective cost per request does not grow with the
number of indices, which is what lets the +30% QPS survive distribution.
Padding rows added for divisibility are never addressed: ``indirect``
only encodes real local indices.

Step 2 has two realisations: the jnp gather/where path
(``_local_rows``, the oracle) and the fused tiled Pallas kernel
(``_local_bags_fused``) in which each tier's gather + dequant + bag is
ONE kernel call with other-shard/other-tier slots weight-0-skipped —
no (N, D) per-tier fp32 intermediates.  ``use_pallas=None``
auto-selects the kernel on TPU, the oracle where Pallas would be
interpreted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.packed_store import _IDX_MASK, _TIER_SHIFT, PackedStore
from repro.core.tiers import Tier
from repro.kernels import use_kernel
from repro.kernels.rows import LaneDense, lane_dense_host

Array = jax.Array


def _pad_rows(x: Array, n: int) -> Array:
    v = x.shape[0]
    vp = -(-v // n) * n
    if vp != v:
        x = jnp.pad(x, [(0, vp - v)] + [(0, 0)] * (x.ndim - 1))
    return x


def packed_pspecs(axis: str = "model") -> PackedStore:
    """PartitionSpec tree: payloads/scales row-sharded, indirect
    replicated."""
    return PackedStore(
        payload8=P(axis, None), scale8=P(axis),
        payload16=P(axis, None), scale16=P(axis),
        payload32=P(axis, None), indirect=P())


def shard_packed(packed: PackedStore, mesh,
                 axis: str = "model") -> PackedStore:
    """Place a PackedStore row-sharded over ``axis`` (payloads padded up
    to a multiple of the axis size; padding rows are unaddressable)."""
    n = mesh.shape[axis]
    specs = packed_pspecs(axis)

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return PackedStore(*(put(_pad_rows(leaf, n) if spec != P() else leaf,
                             spec)
                         for leaf, spec in zip(packed, specs)))


def place_packed(packed: PackedStore, mesh=None,
                 axis: str = "model") -> PackedStore:
    """Device placement matching the serving path: ``shard_packed``
    under a mesh; otherwise every leaf ``device_put`` from the host,
    the three payloads as ``kernels.rows.LaneDense`` (laid out once
    here, on the host, so the gather kernels read them as placed).

    The ONE placement helper the online server, the hierarchical
    store's hot level and the shadow-swap staging share (``serve.shadow``
    pre-places the finished shadow store with this before the atomic
    swap, so the swap itself is a pointer flip, not a transfer):
    dispatch is asynchronous in both modes — the host returns before
    the copy lands and jit sequences the transfer before first use.
    """
    if mesh is not None:
        return shard_packed(packed, mesh, axis)

    def put(name, leaf):
        x = np.asarray(leaf)
        if name.startswith("payload"):
            return LaneDense(jax.device_put(lane_dense_host(x)), *x.shape)
        return jax.device_put(x)

    return PackedStore(*(put(name, leaf)
                         for name, leaf in zip(PackedStore._fields, packed)))


def shard_nbytes(packed: PackedStore, n: int) -> int:
    """Per-device bytes of ``packed`` row-sharded ``n`` ways.

    Each payload/scale array pads up to a multiple of ``n`` and
    contributes ``1/n`` of its padded bytes per device; the ``indirect``
    word is replicated in full.  This is the quantity the hierarchical
    store's budget planner charges against the per-device HBM budget
    (``repro.store.budget.hot_shard_bytes`` computes the same number
    from tier counts before the store exists — the two are
    cross-checked by tests).
    """
    total = 0
    for leaf, spec in zip(packed, packed_pspecs()):
        rows = leaf.shape[0]
        per_row = leaf.size // max(rows, 1) * leaf.dtype.itemsize
        if spec == P():                       # replicated
            total += rows * per_row
        else:
            total += -(-rows // n) * per_row  # padded shard share
    return int(total)


def unshard_packed(packed: PackedStore) -> PackedStore:
    """Host copy with the divisibility padding rows trimmed.

    Inverse of ``shard_packed`` up to the unaddressable pad rows: live
    row counts per tier are recovered from the replicated ``indirect``
    (local indices are dense 0..count-1), payload/scale arrays are cut
    back to them, and emptied tiers keep a 1-row placeholder so shapes
    stay non-degenerate.  This is what ``packed_store.repack_delta``
    needs during online re-tiering under a mesh: trim -> delta-repack on
    host -> ``shard_packed`` the result back out.
    """
    host = jax.device_get(packed)
    ind = np.asarray(host.indirect)
    counts = np.bincount(ind >> _TIER_SHIFT, minlength=3)[:3]

    def trim(x, c):
        return jnp.asarray(np.asarray(x)[:max(int(c), 1)])

    return PackedStore(
        payload8=trim(host.payload8, counts[0]),
        scale8=trim(host.scale8, counts[0]),
        payload16=trim(host.payload16, counts[1]),
        scale16=trim(host.scale16, counts[1]),
        payload32=trim(host.payload32, counts[2]),
        indirect=jnp.asarray(ind))


def _local_rows(pk: PackedStore, indices: Array, axis: str) -> Array:
    """Rows this shard owns, dequantized fp32; zeros elsewhere."""
    code = jnp.take(pk.indirect, indices, axis=0)
    tier = code >> _TIER_SHIFT
    loc = code & _IDX_MASK
    i = jax.lax.axis_index(axis)

    def gather(payload, scale, tier_value):
        v_loc = payload.shape[0]
        l = loc - i * v_loc
        mine = (tier == tier_value) & (l >= 0) & (l < v_loc)
        lc = jnp.clip(l, 0, v_loc - 1)
        rows = jnp.take(payload, lc, axis=0).astype(jnp.float32)
        if scale is not None:
            rows = rows * jnp.take(scale, lc, axis=0)[..., None]
        return jnp.where(mine[..., None], rows, 0.0)

    return (gather(pk.payload8, pk.scale8, Tier.INT8.value)
            + gather(pk.payload16, pk.scale16, Tier.HALF.value)
            + gather(pk.payload32, None, Tier.FP32.value))


def _local_bags_fused(pk: PackedStore, indices: Array, axis: str,
                      weights: Array | None = None) -> Array:
    """Tier-split gather + dequant + bag for the rows this shard owns,
    as one fused tiled kernel call per tier — the (N, D) dequantized
    per-tier intermediates of ``_local_rows`` never materialise.

    indices (B, K) -> (B, D); rows other shards own contribute zero
    weight, so the kernel skips their DMAs entirely.
    """
    from repro.kernels.dequant_bag.ops import dequant_bag_tpu

    code = jnp.take(pk.indirect, indices, axis=0)
    tier = code >> _TIER_SHIFT
    loc = code & _IDX_MASK
    i = jax.lax.axis_index(axis)

    ones32 = jnp.ones((pk.payload32.shape[0],), jnp.float32)
    out = jnp.zeros((indices.shape[0], pk.payload32.shape[-1]),
                    jnp.float32)
    for t, payload, scale in ((Tier.INT8.value, pk.payload8, pk.scale8),
                              (Tier.HALF.value, pk.payload16, pk.scale16),
                              (Tier.FP32.value, pk.payload32, ones32)):
        v_loc = payload.shape[0]
        l = loc - i * v_loc
        mine = (tier == t) & (l >= 0) & (l < v_loc)
        w = mine.astype(jnp.float32)
        if weights is not None:
            w = w * weights
        lc = jnp.clip(l, 0, v_loc - 1)
        out = out + dequant_bag_tpu(payload, scale, lc, w,
                                    use_pallas=True)
    return out


def sharded_lookup(packed: PackedStore, indices: Array, *, mesh,
                   axis: str = "model",
                   use_pallas: bool | None = None) -> Array:
    """Distributed ``packed_store.lookup``: int (...,) -> fp32 (..., D),
    replicated.

    ``use_pallas=None`` auto-selects: each shard runs the fused tiled
    kernel (K = 1 bags, bit-identical to the jnp path) on TPU, the
    gather/where jnp path where Pallas would be interpreted.
    """
    use_pallas = use_kernel(use_pallas)

    def local(pk, idx):
        if use_pallas:
            flat = idx.reshape(-1, 1)
            rows = _local_bags_fused(pk, flat, axis)
            rows = rows.reshape(*idx.shape, rows.shape[-1])
        else:
            rows = _local_rows(pk, idx, axis)
        return jax.lax.psum(rows, axis)

    return shard_map(local, mesh=mesh,
                     in_specs=(packed_pspecs(axis), P()),
                     out_specs=P(), check_vma=False)(packed, indices)


def sharded_bag_lookup_rect(packed: PackedStore, indices: Array, *,
                            mesh, axis: str = "model",
                            weights: Array | None = None,
                            use_pallas: bool | None = None) -> Array:
    """Distributed rectangular embedding-bag: (B, K) indices -> (B, D).

    The fused form of ``sharded_bag_lookup`` for fixed-shape bags (the
    serving layout): per shard, tier-split gather + dequant + bag run as
    one tiled kernel call per tier, then a single (B, D) psum — neither
    the (B*K, D) dequantized rows nor per-tier selects exist.  With
    ``use_pallas=False`` falls back to ``_local_rows`` + in-axis sum
    (the oracle the fused path is tested against).
    """
    use_pallas = use_kernel(use_pallas)

    def local(pk, idx, w):
        if use_pallas:
            bags = _local_bags_fused(pk, idx, axis, weights=w)
        else:
            rows = _local_rows(pk, idx, axis)
            if w is not None:
                rows = rows * w[..., None]
            bags = rows.sum(axis=1)
        return jax.lax.psum(bags, axis)

    pk_specs = packed_pspecs(axis)
    if weights is None:
        fn = shard_map(lambda pk, idx: local(pk, idx, None), mesh=mesh,
                       in_specs=(pk_specs, P()),
                       out_specs=P(), check_vma=False)
        return fn(packed, indices)
    return shard_map(local, mesh=mesh,
                     in_specs=(pk_specs, P(), P()),
                     out_specs=P(), check_vma=False)(
        packed, indices, weights)


def sharded_bag_matmul(packed: PackedStore, indices: Array, w: Array, *,
                       mesh, axis: str = "model",
                       weights: Array | None = None,
                       use_pallas: bool | None = None,
                       int8_direct: bool = False) -> Array:
    """Distributed ``packed_bag_matmul``: (B, F) indices + (F*D, H)
    first-layer weights -> (B, H), replicated.

    One fusion level past ``sharded_bag_lookup_rect``: each shard runs
    the fused dequant-bag->matmul kernel per tier over the rows it owns
    (other shards' slots weight-0-skipped), and the single psum moves
    the (B, H) *post-matmul* activations instead of the (B, F*D) bag
    tile — for H < F*D the collective shrinks by the same factor the
    HBM round-trip does.  The first-layer weights are replicated (they
    are model parameters, tiny next to the table).  With
    ``use_pallas=False`` falls back to ``_local_rows`` + einsum, the
    oracle the fused path is tested against.
    """
    from repro.kernels.bag_matmul.kernel import bag_matmul_pallas
    from repro.kernels.bag_matmul.ops import _as_w3
    use_pallas = use_kernel(use_pallas)
    b, f = indices.shape
    d = packed.payload32.shape[-1]
    w3 = _as_w3(w, f, d).astype(jnp.float32)

    def local(pk, idx, wts):
        code = jnp.take(pk.indirect, idx, axis=0)
        tier = code >> _TIER_SHIFT
        loc = code & _IDX_MASK
        i = jax.lax.axis_index(axis)
        if not use_pallas:
            rows = _local_rows(pk, idx, axis)
            if wts is not None:
                rows = rows * wts[..., None]
            out = jnp.einsum("bfd,fdh->bh", rows, w3,
                             preferred_element_type=jnp.float32)
            return jax.lax.psum(out, axis)
        ones32 = jnp.ones((pk.payload32.shape[0],), jnp.float32)
        out = jnp.zeros((idx.shape[0], w3.shape[-1]), jnp.float32)
        for t, payload, scale in (
                (Tier.INT8.value, pk.payload8, pk.scale8),
                (Tier.HALF.value, pk.payload16, pk.scale16),
                (Tier.FP32.value, pk.payload32, ones32)):
            v_loc = payload.shape[0]
            l = loc - i * v_loc
            mine = (tier == t) & (l >= 0) & (l < v_loc)
            wt = mine.astype(jnp.float32)
            if wts is not None:
                wt = wt * wts
            lc = jnp.clip(l, 0, v_loc - 1)
            out = out + bag_matmul_pallas(
                payload, scale, lc, wt, w3,
                scale_after=int8_direct and t == Tier.INT8.value)
        return jax.lax.psum(out, axis)

    pk_specs = packed_pspecs(axis)
    if weights is None:
        fn = shard_map(lambda pk, idx: local(pk, idx, None), mesh=mesh,
                       in_specs=(pk_specs, P()),
                       out_specs=P(), check_vma=False)
        return fn(packed, indices)
    return shard_map(local, mesh=mesh,
                     in_specs=(pk_specs, P(), P()),
                     out_specs=P(), check_vma=False)(
        packed, indices, weights)


def sharded_lookup_train(table: Array, indices: Array, *, mesh,
                         axis: str = "model",
                         use_pallas: bool | None = None) -> Array:
    """Differentiable row-sharded gather over the fp32 training table.

    int (...,) -> fp32 (..., D), replicated.  The training twin of
    ``sharded_lookup``: each shard runs ``bag_lookup_train`` (the
    custom_vjp fused gather; other shards' slots carry weight 0 and are
    skipped), one psum assembles the replicated embeddings.  Because
    the local op carries the ``jax.custom_vjp``, differentiating
    through this runs the Pallas scatter-add backward *per shard* —
    each device accumulates gradients for exactly the rows it owns, and
    the psum transposes to a replicated cotangent (no gradient
    collective over the table rows).

    The ``axis`` mesh size must divide ``table.shape[0]``
    (``FieldSpec.total_rows`` is 512-padded for exactly this).
    """
    from repro.kernels.dequant_bag.autodiff import bag_lookup_train
    use_pallas = use_kernel(use_pallas)

    def local(tbl, idx):
        v_loc = tbl.shape[0]
        i = jax.lax.axis_index(axis)
        flat = idx.reshape(-1, 1)
        loc = flat - i * v_loc
        mine = (loc >= 0) & (loc < v_loc)
        lc = jnp.clip(loc, 0, v_loc - 1)
        bags = bag_lookup_train(tbl, lc, mine.astype(jnp.float32),
                                use_pallas=use_pallas)
        return jax.lax.psum(bags, axis)

    out = shard_map(local, mesh=mesh,
                    in_specs=(P(axis, None), P()),
                    out_specs=P(), check_vma=False)(table, indices)
    return out.reshape(*indices.shape, table.shape[1])


def sharded_bag_lookup(packed: PackedStore, indices: Array,
                       segment_ids: Array, num_bags: int, *, mesh,
                       axis: str = "model",
                       weights: Array | None = None) -> Array:
    """Distributed ``packed_store.bag_lookup``: local gather + dequant +
    local segment-sum, one (num_bags, D) psum.  Replicated output."""

    def local(pk, idx, seg, w=None):
        rows = _local_rows(pk, idx, axis)
        if w is not None:
            rows = rows * w[:, None]
        bags = jax.ops.segment_sum(rows, seg, num_segments=num_bags)
        return jax.lax.psum(bags, axis)

    pk_specs = packed_pspecs(axis)
    if weights is None:
        return shard_map(local, mesh=mesh,
                         in_specs=(pk_specs, P(), P()),
                         out_specs=P(), check_vma=False)(
            packed, indices, segment_ids)
    return shard_map(local, mesh=mesh,
                     in_specs=(pk_specs, P(), P(), P()),
                     out_specs=P(), check_vma=False)(
        packed, indices, segment_ids, weights)
