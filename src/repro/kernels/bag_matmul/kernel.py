"""Pallas TPU kernel: fused gather + dequant + bag -> first matmul.

``dequant_bag`` stops at the (B, D) bag tile, which every model then
feeds to its first dense layer — so the (B, F*D) fp32 activations
round-trip through HBM between the two ops.  This kernel carries the
fusion one layer further: the dequantized rows live only in VMEM
scratch and feed the MXU directly, so the fp32 embedding activations
never touch HBM.  Same split-the-hot-loop philosophy as the
flash-decode attention kernel referenced in SNIPPETS.md, applied to
the SHARK serving path.

Layout (``bag_matmul_pallas``):

  grid = (ceil(B / B_block), ceil(H / H_block))
  indices, scales, weights
            per-slot 1-D SMEM blocks (``kernels.rows`` slot layout)
  payload   lane-dense view in HBM (ANY); whole rows DMA'd manually
            (a ``rows.LaneDense`` payload as placed, a logical (V, D)
            one laid out inside the call)
  w3        (K, Dp, H_block) VMEM block: per-field first-layer weights
  out       (B_block, H_block) fp32, accumulated in-kernel
  scratch   rows  (B_block, Dp) fp32 dequantized field tile
            coeff (B_block, 1)  fp32 per-row scale*weight (scale_after)
            ring  payload-dtype double-buffered landing ring
            sems  (nbuf,)    one DMA semaphore per ring buffer

Per field k the kernel streams the tile's B_block rows through the
landing ring (DMA for row b+nbuf issued while row b dequantizes — the
same pipeline as ``dequant_bag``), writes ``(row * scale) * weight``
into the fp32 ``rows`` scratch (bit-identical per slot to what
``packed_bag_lookup`` produces — zero-weight slots become exact zero
rows), then fires one (B_block, D) x (D, H_block) MXU matmul and
accumulates into the output tile.  Accumulation over k is sequential,
matching the bag kernel's slot order.  One rounding caveat: the bag
sum here is round-to-storage per slot then add (the scratch write
rounds the product), whereas ``dequant_bag``'s ``out += (row*s)*w``
may contract to an FMA under XLA (single rounding) — so multi-slot
bags with non-unit weights can differ from ``packed_bag_lookup`` by
1 ulp.  K=1 and unit-weight bags are bit-identical; this kernel's
result equals exact fp32 sequential accumulation.

``scale_after=True`` is the int8-in specialisation used when every
live slot of a call shares the int8 tier: the matmul consumes the raw
converted rows and ``scale * weight`` scales the (B_block, H_block)
product per output row instead — mathematically identical (the matmul
is row-linear), one fewer (B_block, D) VPU multiply, and the MXU
input stays a pure convert of the int8 payload.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rows, should_interpret

Array = jax.Array


def _bag_matmul_kernel(idx_ref, scale_ref, weight_ref, payload_ref,
                       w_ref, out_ref, rows_ref, coeff_ref, ring, sems, *,
                       block_b: int, k: int, nbuf: int, scale_after: bool,
                       r: int, g: int, dp: int, prows: int):
    out_ref[...] = jnp.zeros_like(out_ref)

    for kk in range(k):
        def copy(b, kk=kk):
            return rows.row_copy(payload_ref, ring, sems, b % nbuf,
                                 idx_ref[b * k + kk], r=r, g=g,
                                 prows=prows)

        def start(b, kk=kk):
            @pl.when(weight_ref[b * k + kk] != 0.0)
            def _():
                copy(b).start()

        def warm(b, carry):
            start(b)
            return carry

        jax.lax.fori_loop(0, min(nbuf, block_b), warm, 0)

        def fill(b, carry, kk=kk):
            slot = b * k + kk
            w = weight_ref[slot]

            @pl.when(w != 0.0)
            def _():
                copy(b).wait()
                row = rows.read_row(ring, b % nbuf, idx_ref[slot], r=r,
                                    g=g, dp=dp, prows=prows)
                if scale_after:
                    rows_ref[pl.ds(b, 1), :] = row
                else:
                    rows_ref[pl.ds(b, 1), :] = (row * scale_ref[slot]) * w

            @pl.when(w == 0.0)
            def _():
                # dead slots must contribute exact zeros to the matmul
                # (and never leave uninitialised scratch on the MXU path)
                rows_ref[pl.ds(b, 1), :] = jnp.zeros((1, dp), jnp.float32)

            if scale_after:
                coeff_ref[pl.ds(b, 1), :] = jnp.full(
                    (1, 1), scale_ref[slot] * w, jnp.float32)

            @pl.when(b + nbuf < block_b)
            def _():
                start(b + nbuf)
            return carry

        jax.lax.fori_loop(0, block_b, fill, 0)

        prod = jnp.dot(rows_ref[...], w_ref[kk],
                       preferred_element_type=jnp.float32)
        if scale_after:
            prod = prod * coeff_ref[...]
        out_ref[...] += prod


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_h", "nbuf",
                                    "scale_after", "interpret"))
def _bag_matmul_call(payload: Array, scales: Array | None,
                     indices: Array,
                     weights: Array, w3: Array, *, block_b: int,
                     block_h: int, nbuf: int, scale_after: bool,
                     interpret: bool) -> Array:
    v, d = payload.shape
    b, k = indices.shape
    h = w3.shape[-1]
    dp, r = rows.row_layout(d)
    g = rows.dma_group(payload.dtype)
    indices = indices.astype(jnp.int32)
    sg = rows.slot_scales(scales, indices)
    weights = weights.astype(jnp.float32)
    w3 = w3.astype(jnp.float32)

    nb = -(-b // block_b)
    bp = nb * block_b
    if bp != b:
        # grid padding: extra bags carry weight 0 -> zero rows, zero out
        indices = jnp.pad(indices, ((0, bp - b), (0, 0)))
        sg = jnp.pad(sg, ((0, bp - b), (0, 0)))
        weights = jnp.pad(weights, ((0, bp - b), (0, 0)))
    nh = -(-h // block_h)
    hp = nh * block_h
    # pad the weight rows to the row width the kernel lands (zeros: the
    # padded row lanes are zero too) and the columns to whole H blocks
    w3 = jnp.pad(w3, ((0, 0), (0, dp - d), (0, hp - h)))
    (indices, sg, weights), span = rows.flatten_slots(
        (indices, sg, weights), block_b)
    phys = rows.lane_dense(payload)

    out = pl.pallas_call(
        functools.partial(_bag_matmul_kernel, block_b=block_b, k=k,
                          nbuf=nbuf, scale_after=scale_after, r=r, g=g,
                          dp=dp, prows=phys.shape[0]),
        grid=(nb, nh),
        in_specs=[rows.slot_spec(span)] * 3 + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((k, dp, block_h), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_h), lambda i, j: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((block_b, dp), jnp.float32),
            pltpu.VMEM((block_b, 1), jnp.float32),
            pltpu.VMEM(rows.ring_shape(nbuf, g, phys.shape[1]),
                       phys.dtype),
            pltpu.SemaphoreType.DMA((nbuf,)),
        ],
        out_shape=jax.ShapeDtypeStruct((bp, hp), jnp.float32),
        interpret=interpret,
    )(indices, sg, weights, phys, w3)
    return out[:b, :h]


def _legal_block_h(block_h: int, h: int) -> int:
    """The (block_b, block_h) output tile's lane rule: the whole H, or
    a multiple of 128."""
    if block_h >= h:
        return h
    return min(h, -(-block_h // rows.LANES) * rows.LANES)


def bag_matmul_pallas(payload: Array, scales: Array | None,
                      indices: Array,
                      weights: Array | None, w3: Array,
                      interpret: bool | None = None, *,
                      block_b: int | None = None,
                      block_h: int | None = None,
                      nbuf: int | None = None,
                      scale_after: bool = False) -> Array:
    """payload (V, D) or ``rows.LaneDense``, scales (V,) or None (unit
    scales), indices (B, K), w3 (K, D, H) -> (B, H) fp32.

    One fused kernel call: gather + dequant + per-field matmul
    accumulate; the (B, K, D) fp32 rows exist only in VMEM scratch.
    Block sizes default to ``ops.resolve_bm_block_sizes`` (measured
    autotune cache under the ``bag_matmul`` key, analytic fallback),
    rounded to the 8 x 128 tile rules.
    """
    b, k = indices.shape
    d = payload.shape[1]
    h = w3.shape[-1]
    if weights is None:
        weights = jnp.ones((b, k), jnp.float32)
    from repro.kernels.bag_matmul.ops import resolve_bm_block_sizes
    from repro.kernels.dequant_bag.ops import resolve_nbuf
    block_b, block_h = resolve_bm_block_sizes(
        b, k, d, h, payload.dtype.itemsize, block_b, block_h,
        dtype=str(payload.dtype))
    block_b = rows.legal_block_b(block_b)
    block_h = _legal_block_h(block_h, h)
    if nbuf is None:
        nbuf = resolve_nbuf(block_b)
    nbuf = max(1, min(int(nbuf), block_b))
    return _bag_matmul_call(payload, scales, indices, weights, w3,
                            block_b=block_b, block_h=block_h, nbuf=nbuf,
                            scale_after=bool(scale_after),
                            interpret=should_interpret(interpret))
