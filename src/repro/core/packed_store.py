"""Tier-partitioned serving store for F-Quantization (TPU adaptation).

The paper prepends per-row "extra words" (precision tag, dim, scale —
Table 1) and stores rows at heterogeneous widths in one buffer.  That
layout needs per-row pointer chasing, which defeats the TPU's vectorised
HBM->VMEM DMA.  We instead *partition rows by tier* into three dense
arrays and keep a single int32 indirection word per row:

    payload8   int8 [V8,  D]   + scale8  fp32[V8]
    payload16  bf16 [V16, D]   + scale16 fp32[V16]   (fp16 if strict)
    payload32  fp32 [V32, D]
    indirect   int32[V]        code = tier << 28 | local_index

Memory arithmetic matches tiers.memory_bytes().  Packing happens offline
(numpy, data-dependent shapes); lookup is jitable with static shapes and is
the hot path behind the paper's +30% QPS (fused Pallas kernel in
repro/kernels/dequant_bag).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rowwise_quant as rq
from repro.core.qat_store import FQuantConfig, QATStore, current_tiers
from repro.core.tiers import Tier
from repro.kernels.rows import take_rows

Array = jax.Array

_TIER_SHIFT = 28
_IDX_MASK = (1 << _TIER_SHIFT) - 1
# rows quantized per device call: each step of the (eager) row-wise
# quantizers holds a full-size buffer, so a whole 10M-row tier at once
# would not fit one chip's HBM
_QUANT_CHUNK = 1 << 20


def _quantize_chunked(fn, rows: np.ndarray, *args, **kw):
    """``fn(rows) -> (payload, scale)`` over host ``rows`` in chunks of
    ``_QUANT_CHUNK``; row-wise, so bit-identical to one call."""
    qs, ss = [], []
    for i in range(0, rows.shape[0], _QUANT_CHUNK):
        q, s = fn(jnp.asarray(rows[i:i + _QUANT_CHUNK]), *args, **kw)
        qs.append(np.asarray(q))
        ss.append(np.asarray(s))
    return np.concatenate(qs), np.concatenate(ss)


def _scale_f32(s) -> np.ndarray:
    """Normalise a host-side scale column to fp32 (writable copy).

    numpy promotes to float64 on contact with python floats (and
    ``np.concatenate`` keeps the widest dtype), so ``pack`` and
    ``repack_delta`` funnel scale arrays through here at every entry
    point — a float64 scale column would double the serving scale
    bytes and break bit-identity between the delta and full-pack
    paths.  The fp32-out contract is pinned by a regression test.
    """
    return np.array(s, np.float32)  # copy: callers mutate in place


class PackedStore(NamedTuple):
    payload8: Array    # int8 [V8, D]
    scale8: Array      # fp32 [V8]
    payload16: Array   # bf16/fp16 [V16, D]
    scale16: Array     # fp32 [V16]
    payload32: Array   # fp32 [V32, D]
    indirect: Array    # int32 [V]

    @property
    def vocab(self) -> int:
        return self.indirect.shape[0]

    @property
    def dim(self) -> int:
        return self.payload32.shape[-1]

    def nbytes(self, by_tier: bool = False):
        """Store bytes: total (default) or the per-tier breakdown.

        ``by_tier=True`` returns ``{"int8", "half", "fp32",
        "indirect"}`` — payload+scale bytes per precision tier plus the
        shared indirection word — which is what the hierarchical
        store's budget planner consumes (``repro.store.budget``).
        Placeholder rows of empty tiers are counted: they are
        physically allocated.
        """
        size = [leaf.size * leaf.dtype.itemsize for leaf in self]
        per = {"int8": int(size[0] + size[1]),
               "half": int(size[2] + size[3]),
               "fp32": int(size[4]),
               "indirect": int(size[5])}
        if by_tier:
            return per
        return int(sum(per.values()))


def pack(store: QATStore, cfg: FQuantConfig) -> PackedStore:
    """Offline pack (numpy): partition rows by tier, quantize payloads."""
    table = np.asarray(store.table, np.float32)
    tiers = np.asarray(current_tiers(store, cfg))
    dim = table.shape[1]
    half_dtype = np.float16 if cfg.strict_fp16 else jnp.bfloat16

    idx8 = np.nonzero(tiers == Tier.INT8.value)[0]
    idx16 = np.nonzero(tiers == Tier.HALF.value)[0]
    idx32 = np.nonzero(tiers == Tier.FP32.value)[0]

    # int8 tier: RTN at pack time (serving path; paper Eq. 5-6)
    rows8 = table[idx8] if idx8.size else np.zeros((1, dim), np.float32)
    q8, s8 = _quantize_chunked(rq.quantize_rowwise, rows8, cfg.bits,
                               mode=cfg.mode)
    s8 = _scale_f32(s8[:, 0])

    rows16 = table[idx16] if idx16.size else np.zeros((1, dim), np.float32)
    def quant16(rows):
        q, s = rq.quantize_half(rows, strict_fp16=cfg.strict_fp16,
                                scaled=cfg.scaled_half)
        return q.astype(half_dtype), s    # cast on device, as XLA rounds

    q16, s16 = _quantize_chunked(quant16, rows16)
    s16 = _scale_f32(s16[:, 0])

    rows32 = table[idx32] if idx32.size else np.zeros((1, dim), np.float32)

    indirect = np.zeros(table.shape[0], np.int32)
    for tier, idx in ((Tier.INT8, idx8), (Tier.HALF, idx16),
                      (Tier.FP32, idx32)):
        indirect[idx] = (int(tier.value) << _TIER_SHIFT) | np.arange(
            idx.size, dtype=np.int32)

    return PackedStore(
        payload8=jnp.asarray(q8), scale8=jnp.asarray(s8, jnp.float32),
        payload16=jnp.asarray(q16), scale16=jnp.asarray(s16, jnp.float32),
        payload32=jnp.asarray(rows32, jnp.float32),
        indirect=jnp.asarray(indirect))


def lookup(packed: PackedStore, indices: Array) -> Array:
    """Gather + inline dequant.  indices: int (...,) -> fp32 (..., D).

    Three tier-local gathers + select.  The Pallas kernel in
    repro/kernels/dequant_bag fuses this with the bag reduction; this jnp
    version is its oracle and the XLA fallback.  A placed store's
    payloads (``kernels.rows.LaneDense``) are read row by row in their
    physical view (``rows.take_rows``).
    """
    code = jnp.take(packed.indirect, indices, axis=0)
    tier = code >> _TIER_SHIFT
    loc = code & _IDX_MASK

    v8 = packed.payload8.shape[0]
    v16 = packed.payload16.shape[0]
    v32 = packed.payload32.shape[0]
    l8 = jnp.clip(loc, 0, v8 - 1)
    l16 = jnp.clip(loc, 0, v16 - 1)
    l32 = jnp.clip(loc, 0, v32 - 1)

    e8 = (take_rows(packed.payload8, l8).astype(jnp.float32)
          * jnp.take(packed.scale8, l8, axis=0)[..., None])
    e16 = (take_rows(packed.payload16, l16).astype(jnp.float32)
           * jnp.take(packed.scale16, l16, axis=0)[..., None])
    e32 = take_rows(packed.payload32, l32)

    t = tier[..., None]
    return jnp.where(t == Tier.INT8.value, e8,
                     jnp.where(t == Tier.HALF.value, e16, e32))


def lookup_fused(packed: PackedStore, indices: Array,
                 use_pallas: bool | None = None) -> Array:
    """Serving-path ``lookup``: fused tiled Pallas gather, bit-identical.

    One fused gather+dequant+bag kernel call per tier with no (N, D)
    per-tier fp32 intermediates (see ``kernels.dequant_bag.ops``).
    ``use_pallas=None`` auto-selects the kernel on TPU and falls back to
    the jnp ``lookup`` oracle where Pallas would be interpreted.
    """
    from repro.kernels.dequant_bag.ops import packed_lookup_fused
    return packed_lookup_fused(packed, indices, use_pallas=use_pallas)


def bag_matmul(packed: PackedStore, indices: Array, w: Array,
               weights: Array | None = None,
               use_pallas: bool | None = None,
               int8_direct: bool = False) -> Array:
    """Fused bag->first-matmul: (B, F) indices + (F*D, H) weights ->
    (B, H) without materialising the (B, F*D) embedding activations.

    One fusion level past ``lookup_fused`` (see
    ``kernels.bag_matmul.ops.packed_bag_matmul``); ``use_pallas=None``
    auto-selects the fused kernel on TPU and the jnp lookup+einsum
    oracle where Pallas would be interpreted.
    """
    from repro.kernels.bag_matmul.ops import packed_bag_matmul
    return packed_bag_matmul(packed, indices, w, weights=weights,
                             use_pallas=use_pallas,
                             int8_direct=int8_direct)


def unpack(packed: PackedStore) -> Array:
    """Full dequantized table fp32[V, D] (round-trip check vs QAT snap)."""
    return lookup(packed, jnp.arange(packed.vocab))


def packed_tiers(packed: PackedStore) -> np.ndarray:
    """Per-row tier currently materialised in ``packed``: int8 host (V,)."""
    ind = np.asarray(jax.device_get(packed.indirect))
    return (ind >> _TIER_SHIFT).astype(np.int8)


def _quantize_tier(rows: np.ndarray, tier: Tier, cfg: FQuantConfig):
    """Quantize fp32 rows for one tier exactly as ``pack`` does.

    Returns (payload, scale-or-None); row-wise ops, so quantizing any
    subset of rows is bit-identical to quantizing them inside a full
    ``pack`` batch.
    """
    if tier is Tier.INT8:
        q, s = rq.quantize_rowwise(jnp.asarray(rows, jnp.float32),
                                   cfg.bits, mode=cfg.mode)
        return np.asarray(q), _scale_f32(np.asarray(s)[:, 0])
    if tier is Tier.HALF:
        half_dtype = np.float16 if cfg.strict_fp16 else jnp.bfloat16
        q, s = rq.quantize_half(jnp.asarray(rows, jnp.float32),
                                strict_fp16=cfg.strict_fp16,
                                scaled=cfg.scaled_half)
        return (np.asarray(q.astype(half_dtype)),
                _scale_f32(np.asarray(s)[:, 0]))
    return rows.astype(np.float32), None


def quantize_rows(table: np.ndarray, ids: np.ndarray, tiers: np.ndarray,
                  cfg: FQuantConfig,
                  pad_to: int | None = None) -> PackedStore:
    """Quantize fp32 ``table`` rows ``ids`` into a sub-store (position
    ``i`` = ``ids[i]``), byte-identical to what ``pack`` produces for
    them under the same per-row ``tiers``.

    Row-wise quantization means any subset quantizes bit-identically to
    quantizing inside a full ``pack`` batch — the property that lets
    the shadow re-tier (``serve.shadow``) and the hierarchical
    migration build their movers in bounded chunks and still land on
    the synchronous result.

    Shape discipline for chunked callers: the row block is zero-padded
    to the next power of two at or above ``max(pad_to, len(ids))`` and
    EVERY padded row runs through all three tier quantizers at that one
    shape; each tier's subset is then selected host-side.  Row-wise ops
    make the padding and the extra tiers bit-transparent, and a caller
    that fixes ``pad_to`` across chunks hits one compiled shape set
    instead of a fresh XLA compile per (chunk, tier) subset
    (~250ms/chunk on this container -> ~1ms).
    """
    dim = table.shape[1]
    ids = np.asarray(ids, np.int64).reshape(-1)
    n = int(ids.size)
    cap = max(n, int(pad_to or 0), 1)
    cap = 1 << (cap - 1).bit_length()
    rows = np.zeros((cap, dim), np.float32)
    if n:
        rows[:n] = table[ids]
    q8, s8 = _quantize_tier(rows, Tier.INT8, cfg)
    q16, s16 = _quantize_tier(rows, Tier.HALF, cfg)
    q32, _ = _quantize_tier(rows, Tier.FP32, cfg)
    t = np.asarray(tiers)[ids]
    out_p, out_s = [], []
    new_ind = np.zeros(n, np.int32)
    for tv, (p_all, s_all) in enumerate(
            ((q8, s8), (q16, s16), (q32, None))):
        sel = np.nonzero(t == tv)[0]
        if sel.size:
            p = p_all[sel]
            s = None if s_all is None else _scale_f32(s_all[sel])
        else:
            # 1-row placeholder, same convention as ``pack``'s emptied
            # tiers: content is never addressed through ``indirect``
            p = p_all[:1]
            s = None if s_all is None else np.ones((1,), np.float32)
        new_ind[sel] = ((tv << _TIER_SHIFT)
                        | np.arange(sel.size, dtype=np.int32))
        out_p.append(p)
        out_s.append(s)
    return PackedStore(payload8=out_p[0], scale8=out_s[0],
                       payload16=out_p[1], scale16=out_s[1],
                       payload32=out_p[2], indirect=new_ind)


def repack_delta(packed: PackedStore, store: QATStore, cfg: FQuantConfig,
                 changed_rows) -> PackedStore:
    """Incremental re-tier: migrate only tier-crossing rows (host numpy).

    ``changed_rows`` is a *candidate* set — rows whose priority may have
    crossed an Eq. 8 threshold since ``packed`` was built (pass
    ``np.arange(V)`` to check everything; the actual movers are filtered
    here).  Rows whose tier under ``current_tiers(store, cfg)`` equals
    their packed tier keep their payload slot byte-for-byte; crossing
    rows are swap-removed from the source tier (tail rows of that tier
    backfill the holes, with their ``indirect`` words rewritten) and
    re-quantized into the destination tier.

    Contract: the table rows must be unchanged since the last
    (re)pack — the serving-time situation, where only priorities move.
    Then ``unpack(repack_delta(...))`` is **bit-identical** to
    ``unpack(pack(store, cfg))``; only the row order *within* a payload
    array (invisible through ``indirect``) may differ.  Expects an
    unsharded store — bring a row-sharded one host-side first with
    ``repro.dist.packed.unshard_packed``.

    Cost: O(moved) re-quantization + O(V_tier) slicing, vs O(V) for a
    full ``pack`` — the point of re-tiering *during* traffic.
    """
    table = np.asarray(store.table, np.float32)
    dim = table.shape[1]

    indirect = np.array(jax.device_get(packed.indirect))
    old_tiers = (indirect >> _TIER_SHIFT).astype(np.int64)
    new_tiers = np.asarray(current_tiers(store, cfg)).astype(np.int64)
    cand = np.unique(np.asarray(changed_rows).astype(np.int64).reshape(-1))
    moving = cand[old_tiers[cand] != new_tiers[cand]]
    if moving.size == 0:
        return packed

    counts = np.bincount(old_tiers, minlength=3)[:3]
    payloads = [np.array(jax.device_get(p)) for p in
                (packed.payload8, packed.payload16, packed.payload32)]
    scales = [_scale_f32(jax.device_get(packed.scale8)),
              _scale_f32(jax.device_get(packed.scale16)), None]

    # reverse map: tier-local index -> global row
    inv = []
    for t in range(3):
        g = np.nonzero(old_tiers == t)[0]
        a = np.zeros(int(counts[t]), np.int64)
        a[(indirect[g] & _IDX_MASK).astype(np.int64)] = g
        inv.append(a)

    # swap-remove movers from their source tier: surviving tail rows
    # backfill the holes left below the new count
    for t in range(3):
        locs = np.sort((indirect[moving[old_tiers[moving] == t]]
                        & _IDX_MASK).astype(np.int64))
        if locs.size == 0:
            continue
        c2 = int(counts[t]) - locs.size
        holes = locs[locs < c2]
        tail = np.setdiff1d(np.arange(c2, int(counts[t])), locs,
                            assume_unique=True)
        payloads[t][holes] = payloads[t][tail]
        if scales[t] is not None:
            scales[t][holes] = scales[t][tail]
        g = inv[t][tail]
        indirect[g] = ((t << _TIER_SHIFT) | holes).astype(np.int32)
        inv[t][holes] = g
        counts[t] = c2

    payloads = [p[:int(c)] for p, c in zip(payloads, counts)]
    scales = [None if s is None else s[:int(c)]
              for s, c in zip(scales, counts)]

    # append movers to their destination tier, quantized as pack() would
    for t, tier in enumerate((Tier.INT8, Tier.HALF, Tier.FP32)):
        add = moving[new_tiers[moving] == t]
        if add.size == 0:
            continue
        newp, news = _quantize_tier(table[add], tier, cfg)
        base = int(counts[t])
        indirect[add] = ((t << _TIER_SHIFT) | np.arange(
            base, base + add.size)).astype(np.int32)
        payloads[t] = np.concatenate([payloads[t], newp], axis=0)
        if news is not None:
            scales[t] = np.concatenate([scales[t], news])
        counts[t] = base + add.size

    # emptied tiers keep pack()'s quantized-zeros 1-row placeholder
    for t, tier in enumerate((Tier.INT8, Tier.HALF, Tier.FP32)):
        if payloads[t].shape[0] == 0:
            ph, ps_ = _quantize_tier(np.zeros((1, dim), np.float32), tier,
                                     cfg)
            payloads[t] = ph
            if ps_ is not None:
                scales[t] = ps_

    return PackedStore(
        payload8=jnp.asarray(payloads[0]),
        scale8=jnp.asarray(scales[0], jnp.float32),
        payload16=jnp.asarray(payloads[1]),
        scale16=jnp.asarray(scales[1], jnp.float32),
        payload32=jnp.asarray(payloads[2], jnp.float32),
        indirect=jnp.asarray(indirect))


def live_counts(packed: PackedStore) -> np.ndarray:
    """Per-tier live row counts (int64 (3,)), excluding the 1-row
    placeholder an emptied tier keeps for shape sanity."""
    ind = np.asarray(jax.device_get(packed.indirect))
    return np.bincount(ind >> _TIER_SHIFT, minlength=3)[:3]


def extract_rows(packed: PackedStore, rows) -> PackedStore:
    """Host-side sub-store over ``rows`` (numpy leaves) — the row
    *extraction* primitive of the hierarchical store.

    Position ``i`` of the result is global row ``rows[i]``; quantized
    payload bytes and scales are carried over untouched, so any lookup
    on the sub-store is **bit-identical** to the same lookup on
    ``packed`` at the corresponding global ids.  Empty tiers keep a
    1-row zero-payload/unit-scale placeholder (never addressable).
    """
    host = jax.device_get(packed)
    ind = np.asarray(host.indirect)
    rows = np.asarray(rows, np.int64).reshape(-1)
    code = ind[rows] if rows.size else np.zeros((0,), np.int32)
    tier = code >> _TIER_SHIFT
    loc = (code & _IDX_MASK).astype(np.int64)
    dim = host.payload32.shape[-1]

    payloads = [np.asarray(host.payload8), np.asarray(host.payload16),
                np.asarray(host.payload32)]
    scales = [_scale_f32(host.scale8), _scale_f32(host.scale16), None]
    out_p, out_s = [], []
    new_ind = np.zeros(rows.size, np.int32)
    for t in range(3):
        sel = np.nonzero(tier == t)[0]
        if sel.size:
            p = payloads[t][loc[sel]]
            s = None if scales[t] is None else scales[t][loc[sel]]
        else:
            p = np.zeros((1, dim), payloads[t].dtype)
            s = None if scales[t] is None else np.ones((1,), np.float32)
        new_ind[sel] = ((t << _TIER_SHIFT)
                        | np.arange(sel.size, dtype=np.int32))
        out_p.append(p)
        out_s.append(s)
    return PackedStore(payload8=out_p[0], scale8=out_s[0],
                       payload16=out_p[1], scale16=out_s[1],
                       payload32=out_p[2], indirect=new_ind)


def merge_stores(stores) -> PackedStore:
    """N-way row concatenation (host numpy) — the row *insertion*
    primitive behind ``concat_stores``.

    Result position ``i`` is row ``i - Σ vocab(before)`` of the store
    it falls in, in list order.  One ``np.concatenate`` per tier
    (linear in total rows — a pairwise fold would re-copy earlier
    stores quadratically); placeholder rows of emptied tiers are
    dropped from the middle (later stores' local indices are rebased
    past the running live counts), quantized bytes are preserved, so
    lookups stay bit-identical to the sources.
    """
    if not stores:
        raise ValueError("merge_stores needs at least one store")
    hosts = [jax.device_get(s) for s in stores]
    counts = np.stack([live_counts(h) for h in hosts])       # (S, 3)
    offs = np.concatenate([np.zeros((1, 3), np.int64),
                           np.cumsum(counts, axis=0)])       # (S+1, 3)
    dim = np.asarray(hosts[0].payload32).shape[-1]

    fields = (("payload8", "scale8"), ("payload16", "scale16"),
              ("payload32", None))
    out_p, out_s = [], []
    for t, (pf, sf) in enumerate(fields):
        live = [i for i in range(len(hosts)) if counts[i, t]]
        if live:
            p = np.concatenate(
                [np.asarray(getattr(hosts[i], pf))[:int(counts[i, t])]
                 for i in live], axis=0)
            s = None if sf is None else np.concatenate(
                [_scale_f32(getattr(hosts[i], sf))[:int(counts[i, t])]
                 for i in live])
        else:
            p = np.zeros((1, dim),
                         np.asarray(getattr(hosts[0], pf)).dtype)
            s = None if sf is None else np.ones((1,), np.float32)
        out_p.append(p)
        out_s.append(s)

    parts = []
    for i, h in enumerate(hosts):
        ind = np.asarray(h.indirect)
        tier = ind >> _TIER_SHIFT
        loc = (ind & _IDX_MASK).astype(np.int64) + offs[i, tier]
        parts.append(((tier.astype(np.int64) << _TIER_SHIFT)
                      | loc).astype(np.int32))
    return PackedStore(payload8=out_p[0], scale8=out_s[0],
                       payload16=out_p[1], scale16=out_s[1],
                       payload32=out_p[2],
                       indirect=np.concatenate(parts))


def concat_stores(a: PackedStore, b: PackedStore) -> PackedStore:
    """Append ``b``'s rows after ``a``'s: ``merge_stores([a, b])``."""
    return merge_stores([a, b])


def bag_lookup(packed: PackedStore, indices: Array, segment_ids: Array,
               num_bags: int, weights: Array | None = None) -> Array:
    """EmbeddingBag over the packed store: sum rows per bag.

    indices, segment_ids: flat (L,); returns (num_bags, D).
    """
    rows = lookup(packed, indices)
    if weights is not None:
        rows = rows * weights[:, None]
    return jax.ops.segment_sum(rows, segment_ids, num_segments=num_bags)
