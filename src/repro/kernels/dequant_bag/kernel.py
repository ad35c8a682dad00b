"""Pallas TPU kernel: fused gather + row-wise dequant + bag reduction.

The SHARK serving hot path.  XLA lowers packed-store lookup to
gather(int8) -> convert -> gather(scale) -> multiply -> segment-sum: four
HBM-bound ops materialising the (B*K, D) dequantized rows.  This kernel
streams each needed row HBM->VMEM exactly once, dequantizes on the VPU in
fp32, and accumulates straight into the output bag tile — the (L, D)
intermediate never exists.

Tiled layout (``dequant_bag_pallas``):

  grid = (ceil(B / B_block),)          B_block a multiple of 8
  indices, scales, weights
            per-slot 1-D SMEM blocks of B_block*K slots (padded to
            1024 words; ``kernels.rows`` slot layout)
  payload   lane-dense (P, r*Dp) view in HBM (ANY); rows DMA'd manually.
            A ``rows.LaneDense`` payload (a placed serving store) is
            that view already; a logical (V, D) one is laid out by XLA
            inside the call
  out       (B_block, Dp) VMEM, accumulated in-kernel
  scratch   ``nbuf``-deep landing ring of whole physical rows (fp32) or
            packed tiles (int8 / bf16), one DMA semaphore per buffer

Each grid step streams its B_block*K slots through the ring: the first
``nbuf`` live slots' copies are issued up front; draining slot *i* then
waits its buffer, picks the logical row out of the landed rows
(``rows.read_row``), accumulates ``(row * scale) * weight`` into the
output tile, and immediately starts slot *i+nbuf*'s copy into the freed
buffer — so row DMA latency hides behind the VPU dequant math, with up
to ``nbuf`` transfers in flight.  Zero-weight (padded / other-tier)
slots skip both the start and the wait.  Ring depth defaults to
``ops.resolve_nbuf`` (env ``REPRO_DEQUANT_NBUF``).  Rows move whole:
a TPU row DMA must span the lane dimension (see ``kernels.rows``).

Accumulation is sequential in k per bag, so results are bit-identical
to the (B, K)-grid kernel (kept as ``dequant_bag_pallas_rowgrid``) and
match the jnp oracle to within the final jnp.sum reduction order
(exactly, for K = 1).  One normalisation rode along with the refactor:
both kernels now multiply ``(row * scale) * weight`` in the oracle's
order, where the original grid kernel computed ``row * (scale *
weight)`` — up to 1 ulp apart per slot — so that rowgrid-vs-tiled
bit-equality isolates the *tiling* change.

HBM traffic per live slot: one 128-lane physical row for fp32 tables,
one packed (8, 128)-word tile for int8 / bf16 (the smallest DMA the
compiler accepts at a data-dependent row); a logical (V, D) payload
adds one relayout copy of the whole table per call, a ``LaneDense``
one none.  Time on the chip: PERF.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rows, should_interpret

Array = jax.Array


def _tiled_kernel(idx_ref, scale_ref, weight_ref, payload_ref, out_ref,
                  ring, sems, *, block_b: int, k: int, nbuf: int, r: int,
                  g: int, dp: int, prows: int):
    nslots = block_b * k

    def copy(slot):
        return rows.row_copy(payload_ref, ring, sems, slot % nbuf,
                             idx_ref[slot], r=r, g=g, prows=prows)

    def start(slot):
        @pl.when(weight_ref[slot] != 0.0)
        def _():
            copy(slot).start()

    # prime the ring: the first nbuf slots' copies go in flight now
    def warm(slot, carry):
        start(slot)
        return carry

    jax.lax.fori_loop(0, min(nbuf, nslots), warm, 0)
    out_ref[...] = jnp.zeros_like(out_ref)

    def drain(slot, carry):
        w = weight_ref[slot]

        @pl.when(w != 0.0)
        def _():
            copy(slot).wait()
            row = rows.read_row(ring, slot % nbuf, idx_ref[slot], r=r, g=g,
                                dp=dp, prows=prows)
            b = slot // k
            out_ref[pl.ds(b, 1), :] += (row * scale_ref[slot]) * w

        # refill: slot+nbuf reuses this buffer, which is free exactly
        # now — its DMA (if any) was waited above.  Issued even when
        # the current slot is dead: the dead slot never touched the
        # buffer, and its prior tenant (slot-nbuf) was already drained.
        @pl.when(slot + nbuf < nslots)
        def _():
            start(slot + nbuf)
        return carry

    jax.lax.fori_loop(0, nslots, drain, 0)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "nbuf", "interpret"))
def _tiled_call(payload: Array, scales: Array | None, indices: Array,
                weights: Array, *, block_b: int, nbuf: int,
                interpret: bool) -> Array:
    v, d = payload.shape
    b, k = indices.shape
    dp, r = rows.row_layout(d)
    g = rows.dma_group(payload.dtype)
    indices = indices.astype(jnp.int32)
    sg = rows.slot_scales(scales, indices)
    weights = weights.astype(jnp.float32)

    nb = -(-b // block_b)
    bp = nb * block_b
    if bp != b:
        # grid padding: extra bags carry weight 0, so every DMA and
        # accumulate for them is skipped in-kernel
        indices = jnp.pad(indices, ((0, bp - b), (0, 0)))
        sg = jnp.pad(sg, ((0, bp - b), (0, 0)))
        weights = jnp.pad(weights, ((0, bp - b), (0, 0)))
    (indices, sg, weights), span = rows.flatten_slots(
        (indices, sg, weights), block_b)
    phys = rows.lane_dense(payload)

    out = pl.pallas_call(
        functools.partial(_tiled_kernel, block_b=block_b, k=k, nbuf=nbuf,
                          r=r, g=g, dp=dp, prows=phys.shape[0]),
        grid=(nb,),
        in_specs=[rows.slot_spec(span)] * 3
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_b, dp), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM(rows.ring_shape(nbuf, g, phys.shape[1]),
                       phys.dtype),
            pltpu.SemaphoreType.DMA((nbuf,)),
        ],
        out_shape=jax.ShapeDtypeStruct((bp, dp), jnp.float32),
        interpret=interpret,
    )(indices, sg, weights, phys)
    return out[:b, :d]


def dequant_bag_pallas(payload: Array, scales: Array | None,
                       indices: Array,
                       weights: Array | None = None,
                       interpret: bool | None = None, *,
                       block_b: int | None = None,
                       nbuf: int | None = None) -> Array:
    """payload (V, D) or ``rows.LaneDense``, scales (V,) or None (unit
    scales), indices (B, K) -> (B, D) fp32 bags.

    Bag-blocked kernel with an ``nbuf``-deep row-DMA landing ring over
    the lane-dense table view (``kernels.rows``).  ``block_b`` resolves
    through ``ops.resolve_block_b`` (measured autotune cache over the
    analytic model), rounded up to the 8-row tile rule; rows always
    move whole, so B is the only tiled dimension.  ``nbuf`` defaults to ``ops.resolve_nbuf``; ``interpret`` to backend
    auto-detection (``kernels.should_interpret``).
    """
    b, k = indices.shape
    d = payload.shape[1]
    if weights is None:
        weights = jnp.ones((b, k), jnp.float32)
    from repro.kernels.dequant_bag.ops import (resolve_block_b,
                                               resolve_nbuf)
    block_b = resolve_block_b(b, k, d, payload.dtype.itemsize, block_b,
                              kind="dequant_bag", dtype=str(payload.dtype))
    if nbuf is None:
        nbuf = resolve_nbuf(block_b * k)
    nbuf = max(1, min(int(nbuf), block_b * k))
    return _tiled_call(payload, scales, indices, weights,
                       block_b=block_b, nbuf=nbuf,
                       interpret=should_interpret(interpret))


# ---------------------------------------------------------------------------
# pre-refactor kernel layout: (B, K) grid, one (1, D) row DMA per step.
# Kept as the tiling oracle, with ONE edit vs its original form: the
# accumulate is now (row * s) * w instead of row * (s * w) — the ref's
# multiply order, <=1 ulp apart — so bit-equality with the tiled kernel
# tests the tiling alone.


def _rowgrid_kernel(idx_ref, payload_ref, scale_ref, weight_ref, out_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    row = payload_ref[...].astype(jnp.float32)      # (1, D)
    s = scale_ref[0, 0]
    w = weight_ref[0, 0]
    out_ref[...] += (row * s) * w


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rowgrid_call(payload: Array, scales: Array, indices: Array,
                  weights: Array, *, interpret: bool) -> Array:
    v, d = payload.shape
    b, k = indices.shape
    scales2 = scales.reshape(v, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, k),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, j, idx: (idx[i, j], 0)),
            pl.BlockSpec((1, 1), lambda i, j, idx: (idx[i, j], 0)),
            pl.BlockSpec((1, 1), lambda i, j, idx: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, j, idx: (i, 0)),
    )
    return pl.pallas_call(
        _rowgrid_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        interpret=interpret,
    )(indices.astype(jnp.int32), payload, scales2, weights)


def dequant_bag_pallas_rowgrid(payload: Array, scales: Array,
                               indices: Array,
                               weights: Array | None = None,
                               interpret: bool | None = None) -> Array:
    """Pre-refactor (B, K)-grid layout.  One row DMA per grid step; the
    output tile is revisited K times.  Bit-identical to the tiled
    kernel (multiply order normalised to the ref's — see above)."""
    b, k = indices.shape
    if weights is None:
        weights = jnp.ones((b, k), jnp.float32)
    return _rowgrid_call(payload, scales, indices, weights,
                         interpret=should_interpret(interpret))


# ---------------------------------------------------------------------------
# Backward: scatter-add of the bag cotangent into per-row gradients.
#
# The transpose of the forward gather: dtable[i] += coeff[b,k] * g[b]
# for every slot with idx[b,k] == i, where coeff = weight * scale.  The
# gradient lives in HBM in the lane-dense view (ANY memory space,
# aliased onto a zeros input so accumulation is read-modify-write);
# each slot's whole physical row is DMA'd into VMEM, the cotangent is
# rolled onto the logical row's lanes and accumulated, and the row is
# DMA'd back.  TPU grid steps run sequentially, so the RMW is
# race-free; slots are drained in (b, k) lexicographic order —
# identical in the tiled and rowgrid layouts, which makes the two
# kernels bit-equal and the result invariant to block_b.
#
# Unlike the forward, row DMAs here cannot be batch-issued arbitrarily
# far ahead of the waits: two slots of one tile may address the SAME
# physical row (the same logical row, or two logical rows sharing it),
# and the second read must observe the first write.  What CAN
# overlap — and does, via a two-buffer ring — is slot i+1's row *load*
# with slot i's row *store*, whenever the two slots address different
# rows: the next read races only the current write, and the row-index
# guard serializes exactly the conflicting pairs.  Same-row neighbours
# (and the slot after a dead slot) fall back to load-after-store.
# Accumulation order stays (b, k) lexicographic either way — identical
# in the tiled and rowgrid layouts, which keeps the two kernels
# bit-equal and the result invariant to block_b.


def _bag_grad_tiled_kernel(idx_ref, coeff_ref, g_ref, zeros_ref, out_ref,
                           ring, sems, *, block_b: int, k: int, r: int,
                           dp: int, prows: int):
    del zeros_ref
    nslots = block_b * k

    def clamp(slot):
        return jnp.minimum(slot, nslots - 1)  # slot == nslots

    def phys_of(slot):
        return rows.phys_row(idx_ref[clamp(slot)], r=r, prows=prows)

    def load_dma(slot):
        return rows.row_copy(out_ref, ring, sems, slot % 2,
                             idx_ref[clamp(slot)], r=r, g=1, prows=prows)

    def store_dma(slot):
        buf = slot % 2
        return pltpu.make_async_copy(ring.at[pl.ds(buf, 1), :],
                                     out_ref.at[pl.ds(phys_of(slot), 1), :],
                                     sems.at[buf])

    def scatter(slot, prefetched):
        c = coeff_ref[slot]
        nxt = slot + 1
        # the next slot's load may overlap this slot's store only when
        # it is live, in range, and addresses a DIFFERENT physical row
        # (a same-row read must observe this write; with r > 1 two
        # logical rows can share one physical row)
        can_prefetch = ((nxt < nslots) & (coeff_ref[clamp(nxt)] != 0.0)
                        & (phys_of(nxt) != phys_of(slot)))

        @pl.when((c != 0.0) & (prefetched == 0))
        def _():
            load_dma(slot).start()

        @pl.when(c != 0.0)
        def _():
            load_dma(slot).wait()
            buf = slot % 2
            gb = g_ref[pl.ds(slot // k, 1), :]
            ring[pl.ds(buf, 1), :] += c * rows.place_row(
                gb, idx_ref[slot], r=r, dp=dp, prows=prows)
            store_dma(slot).start()

            @pl.when(can_prefetch)
            def _():
                # other buffer: races only the guarded, different-row
                # store below
                load_dma(nxt).start()

            store_dma(slot).wait()

        return jnp.where((c != 0.0) & can_prefetch, 1, 0)

    jax.lax.fori_loop(0, nslots, scatter, 0)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "block_b", "interpret"))
def _bag_grad_tiled_call(g: Array, coeff: Array, indices: Array, *,
                         vocab: int, block_b: int,
                         interpret: bool) -> Array:
    b, k = indices.shape
    d = g.shape[1]
    dp, r = rows.row_layout(d)
    width = r * dp
    indices = indices.astype(jnp.int32)
    coeff = coeff.astype(jnp.float32)
    # cotangent rows padded to one physical row's width: lanes [0, d)
    # hold the value, the rest are zero so rolling it into place adds
    # nothing to the neighbouring logical rows
    g = jnp.pad(g.astype(jnp.float32), ((0, 0), (0, width - d)))

    nb = -(-b // block_b)
    bp = nb * block_b
    if bp != b:
        # grid padding: extra slots carry coeff 0 -> no DMA, no write
        indices = jnp.pad(indices, ((0, bp - b), (0, 0)))
        g = jnp.pad(g, ((0, bp - b), (0, 0)))
        coeff = jnp.pad(coeff, ((0, bp - b), (0, 0)))
    (indices, coeff), span = rows.flatten_slots((indices, coeff), block_b)
    zeros = rows.lane_dense(jnp.zeros((vocab, d), jnp.float32))

    out = pl.pallas_call(
        functools.partial(_bag_grad_tiled_kernel, block_b=block_b, k=k,
                          r=r, dp=dp, prows=zeros.shape[0]),
        grid=(nb,),
        in_specs=[rows.slot_spec(span)] * 2 + [
            pl.BlockSpec((block_b, width), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, width), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=jax.ShapeDtypeStruct(zeros.shape, jnp.float32),
        # operand 3 = the zeros buffer; aliasing it onto the output
        # turns the kernel into an in-place accumulate
        input_output_aliases={3: 0},
        interpret=interpret,
    )(indices, coeff, g, zeros)
    return rows.from_lane_dense(out, vocab, d)


def bag_grad_pallas(g: Array, scales: Array | None, indices: Array,
                    weights: Array | None, vocab: int,
                    interpret: bool | None = None, *,
                    block_b: int | None = None) -> Array:
    """g (B, D) fp32, indices (B, K) -> dtable (vocab, D) fp32.

    The scatter-add transpose of ``dequant_bag_pallas``: bag-blocked
    grid with K looped in-kernel, each slot a read-modify-write of its
    whole lane-dense physical row, pipelined two slots deep with a
    same-row conflict guard (see the kernel comment).  ``block_b``
    resolves through the shared picker under the ``bag_grad``
    autotune-cache key.
    """
    b, k = indices.shape
    d = g.shape[1]
    coeff = jnp.ones((b, k), jnp.float32) if weights is None \
        else weights.astype(jnp.float32)
    if scales is not None:
        coeff = coeff * jnp.take(scales, indices, axis=0)
    from repro.kernels.dequant_bag.ops import resolve_block_b
    block_b = resolve_block_b(b, k, d, 4, block_b, kind="bag_grad",
                              dtype="float32")
    return _bag_grad_tiled_call(g, coeff, indices, vocab=vocab,
                                block_b=block_b,
                                interpret=should_interpret(interpret))


def _bag_grad_rowgrid_kernel(idx_ref, g_ref, coeff_ref, zeros_ref,
                             out_ref, row_ref, sem):
    del zeros_ref
    i = pl.program_id(0)
    j = pl.program_id(1)
    c = coeff_ref[0, 0]

    @pl.when(c != 0.0)
    def _():
        row = idx_ref[i, j]
        src = out_ref.at[pl.ds(row, 1), :]
        load = pltpu.make_async_copy(src, row_ref, sem)
        load.start()
        load.wait()
        row_ref[...] += c * g_ref[...]
        store = pltpu.make_async_copy(row_ref, src, sem)
        store.start()
        store.wait()


@functools.partial(jax.jit, static_argnames=("vocab", "interpret"))
def _bag_grad_rowgrid_call(g: Array, coeff: Array, indices: Array, *,
                           vocab: int, interpret: bool) -> Array:
    b, k = indices.shape
    d = g.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, k),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, j, idx: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j, idx: (i, j)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        _bag_grad_rowgrid_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vocab, d), jnp.float32),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(indices.astype(jnp.int32), g.astype(jnp.float32),
      coeff.astype(jnp.float32), jnp.zeros((vocab, d), jnp.float32))


def bag_grad_pallas_rowgrid(g: Array, scales: Array | None,
                            indices: Array, weights: Array | None,
                            vocab: int,
                            interpret: bool | None = None) -> Array:
    """(B, K)-grid scatter fallback: one slot RMW per grid step, full-D
    row scratch.  Bit-identical to ``bag_grad_pallas`` (same (b, k)
    accumulation order)."""
    b, k = indices.shape
    coeff = jnp.ones((b, k), jnp.float32) if weights is None \
        else weights.astype(jnp.float32)
    if scales is not None:
        coeff = coeff * jnp.take(scales, indices, axis=0)
    return _bag_grad_rowgrid_call(g, coeff, indices, vocab=vocab,
                                  interpret=should_interpret(interpret))
