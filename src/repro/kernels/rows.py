"""Row-gather plumbing shared by the gather kernels (dequant_bag,
bag_grad, bag_matmul, hashed_gather).

Mosaic moves HBM data in (8, 128) tiles of 32-bit words: a DMA slice
must span the whole lane (last) dimension, and on packed dtypes its
row offset must be a multiple of the sublane packing.  A (V, 64) table
in row-major HBM is a (V, 128)-padded buffer whose 64-wide row slice
the compiler refuses.  So every kernel here reads tables through one
**lane-dense view**:

  * the row width D is padded to ``dp``: a power of two when D < 128
    (so it divides 128), a multiple of 128 otherwise;
  * ``r = 128 // dp`` logical rows share one 128-lane physical row
    (r = 1 for dp >= 128): the table, zero-padded to ``r * P`` rows,
    is cut into ``r`` consecutive blocks of ``P`` rows laid side by
    side, a (P, r * dp) view;
  * 32-bit tables move one physical row per DMA; bf16 / int8 tables
    move the aligned group of ``g`` = 16 / 32 physical rows that holds
    it (one packed (8, 128) tile), and the kernel picks the row out of
    the landed tile on the VPU.

Logical row ``i`` therefore lives in physical row ``i % P`` at lanes
``[(i // P) * dp, (i // P + 1) * dp)``; ``read_row`` rolls it down to
lanes ``[0, dp)``.  Blocks side by side (rather than interleaved rows)
let XLA build the view from the column-major layout it gives narrow
(V, 64) arrays in one transpose, with no lane-padded row-major copy
in between.

A table that is read many times and written rarely (a placed serving
store) is held in that view for good: ``LaneDense`` carries the
physical array and the logical ``(V, D)``, ``lane_dense_host`` builds
the view once on the host, and ``lane_dense`` hands a ``LaneDense``'s
array straight to the kernel.  A logical table (the training table,
rewritten every step) is still laid out by XLA on every call; each such
trace counts ``kernels.relayout_traced.<dtype>.<V>x<D>``.

Per-slot scalars (row ids, scales, weights) are flattened to 1-D and
streamed into SMEM one grid block at a time.  A 1-D SMEM block must be
a multiple of 1024 words, so each block's slot list is zero-padded to
that (padding slots carry weight 0 and are never visited).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

Array = jax.Array

LANES = 128
SUBLANES = 8
# a 1-D SMEM block must be a multiple of this many words
SLOT_ALIGN = 1024
# cap on live slots per grid block: three 4-byte SMEM streams, double
# buffered, stay under 200 KiB of the 1 MiB scalar memory
MAX_BLOCK_SLOTS = 8192


def row_layout(d: int) -> tuple[int, int]:
    """(dp, r): padded row width and logical rows per physical row."""
    if d >= LANES:
        return -(-d // LANES) * LANES, 1
    dp = 1 << max(0, d - 1).bit_length()
    return dp, LANES // dp


def dma_group(dtype) -> int:
    """Physical rows per DMA: one for 32-bit tables, one whole packed
    tile (16 bf16 / 32 int8 rows) for narrower dtypes."""
    return SUBLANES * (4 // jnp.dtype(dtype).itemsize)


def phys_rows(v: int, d: int, dtype) -> int:
    """P: physical rows of the lane-dense view of a (V, D) table,
    padded to a multiple of the DMA group so group reads stay in
    bounds."""
    dp, r = row_layout(d)
    g = dma_group(dtype)
    return -(-(-(-v // r)) // g) * g


@jax.tree_util.register_pytree_node_class
class LaneDense:
    """A (V, D) table held in its lane-dense (P, r * dp) view.

    The only leaf is ``phys``; the logical ``(v, d)`` is static.
    ``shape``, ``dtype``, ``size`` and ``nbytes`` describe the logical
    table, so code that sizes a table by them reads the same numbers
    from either form.  ``np.asarray`` gives the logical table back.
    """

    __slots__ = ("phys", "v", "d")
    ndim = 2

    def __init__(self, phys, v: int, d: int):
        self.phys = phys
        self.v = int(v)
        self.d = int(d)

    def tree_flatten(self):
        return (self.phys,), (self.v, self.d)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.v, self.d)

    @property
    def dtype(self):
        return self.phys.dtype

    @property
    def size(self) -> int:
        return self.v * self.d

    @property
    def nbytes(self) -> int:
        return self.size * np.dtype(self.dtype).itemsize

    def __array__(self, dtype=None, copy=None):
        dp, r = row_layout(self.d)
        phys = np.asarray(self.phys)
        p = phys.shape[0]
        table = phys.reshape(p, r, dp).transpose(1, 0, 2).reshape(
            r * p, dp)[:self.v, :self.d]
        return table if dtype is None else table.astype(dtype)


@jax.named_scope("lane_dense")
def lane_dense(table) -> Array:
    """(V, D) -> the (P, r * dp) physical view read by the kernels.
    A ``LaneDense`` is already that view: its array is returned as is.
    A no-op for 32-bit tables whose D is a multiple of 128 and whose V
    is a multiple of 8."""
    if isinstance(table, LaneDense):
        return table.phys
    v, d = table.shape
    dp, r = row_layout(d)
    p = phys_rows(v, d, table.dtype)
    if dp != d or p * r != v or r > 1:
        obs.inc(f"kernels.relayout_traced.{jnp.dtype(table.dtype).name}"
                f".{v}x{d}")
    if dp != d or p * r != v:
        table = jnp.pad(table, ((0, p * r - v), (0, dp - d)))
    if r == 1:
        return table
    if jnp.dtype(table.dtype).itemsize < 4:
        # the fastest form to compile and run for packed dtypes
        return jnp.concatenate([table[s * p:(s + 1) * p]
                                for s in range(r)], axis=1)
    return jnp.transpose(table.T.reshape(dp, r, p), (2, 1, 0)).reshape(
        p, r * dp)


def lane_dense_host(table: np.ndarray) -> np.ndarray:
    """Numpy twin of ``lane_dense``, byte-equal to it: block ``s`` of
    ``P`` logical rows at lanes ``[s * dp, s * dp + D)``, zeros in the
    padding."""
    table = np.asarray(table)
    v, d = table.shape
    dp, r = row_layout(d)
    p = phys_rows(v, d, table.dtype)
    out = np.zeros((p, r * dp), table.dtype)
    for s in range(r):
        block = table[s * p:(s + 1) * p]
        out[:block.shape[0], s * dp:s * dp + d] = block
    return out


@jax.named_scope("from_lane_dense")
def from_lane_dense(phys: Array, v: int, d: int) -> Array:
    """Inverse of ``lane_dense``: (P, r * dp) -> (V, D)."""
    dp, r = row_layout(d)
    if r > 1:
        p = phys.shape[0]
        phys = jnp.transpose(phys.reshape(p, r, dp), (2, 1, 0)).reshape(
            dp, r * p).T
    return phys[:v, :d]


def take_rows(table, idx: Array) -> Array:
    """``jnp.take(table, idx, axis=0)`` for a logical table or a
    ``LaneDense``; the latter reads physical row ``i % P`` and its
    segment ``i // P``, with no whole-table copy.  ``idx`` in range."""
    if not isinstance(table, LaneDense):
        return jnp.take(table, idx, axis=0)
    dp, r = row_layout(table.d)
    phys = table.phys
    if r == 1:
        return jnp.take(phys, idx, axis=0)[..., :table.d]
    p = phys.shape[0]
    both = jnp.take(phys, idx % p, axis=0).reshape(*idx.shape, r, dp)
    seg = (idx // p)[..., None, None]
    return jnp.take_along_axis(both, seg, axis=-2)[..., 0, :table.d]


def legal_block_b(block_b: int) -> int:
    """Round a bag-block size up to the 8-sublane rule of the (block_b,
    ...) output tiles."""
    return max(SUBLANES, -(-int(block_b) // SUBLANES) * SUBLANES)


def block_slots(n: int) -> int:
    """SMEM block length for ``n`` live slots: the next multiple of
    ``SLOT_ALIGN``."""
    return -(-n // SLOT_ALIGN) * SLOT_ALIGN


def slot_scales(scales, indices: Array) -> Array:
    """fp32 per-slot scales: ``scales[indices]``, or ones for
    ``scales=None`` (a unit-scale tier needs no (V,) vector of ones)."""
    if scales is None:
        return jnp.ones(indices.shape, jnp.float32)
    return jnp.take(scales, indices, axis=0).astype(jnp.float32)


def flatten_slots(arrays, block_b: int) -> tuple[list[Array], int]:
    """(Bp, S) per-slot arrays -> 1-D, one ``block_slots`` span per
    block of ``block_b`` rows (row-major slot order inside a block)."""
    bp, s = arrays[0].shape
    nb = bp // block_b
    n = block_b * s
    span = block_slots(n)
    out = [jnp.pad(a.reshape(nb, n), ((0, 0), (0, span - n))).reshape(-1)
           for a in arrays]
    return out, span


def slot_spec(span: int) -> pl.BlockSpec:
    return pl.BlockSpec((span,), lambda i, *_: (i,),
                        memory_space=pltpu.SMEM)


def ring_shape(nbuf: int, g: int, width: int) -> tuple[int, ...]:
    return (nbuf, width) if g == 1 else (nbuf, g, width)


def _split(row, r: int, prows: int):
    """Logical row -> (physical row, segment)."""
    if r == 1:
        return row, 0
    return row % prows, row // prows


def row_copy(src_hbm, ring, sems, buf, row, *, r: int, g: int,
             prows: int):
    """Async copy of the physical rows holding logical ``row`` into
    ring buffer ``buf``."""
    p, _ = _split(row, r, prows)
    if g == 1:
        return pltpu.make_async_copy(src_hbm.at[pl.ds(p, 1), :],
                                     ring.at[pl.ds(buf, 1), :],
                                     sems.at[buf])
    base = pl.multiple_of((p // g) * g, g)
    return pltpu.make_async_copy(src_hbm.at[pl.ds(base, g), :],
                                 ring.at[buf], sems.at[buf])


def read_row(ring, buf, row, *, r: int, g: int, dp: int,
             prows: int) -> Array:
    """The landed logical ``row`` as fp32 (1, dp)."""
    p, seg = _split(row, r, prows)
    if g == 1:
        x = ring[pl.ds(buf, 1), :].astype(jnp.float32)
    else:
        tile = ring[buf].astype(jnp.float32)               # (g, W)
        pick = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == p % g
        x = jnp.sum(jnp.where(pick, tile, 0.0), axis=0, keepdims=True)
    if r > 1:
        # segment -> lanes [0, dp)
        x = pltpu.roll(x, (LANES - seg * dp) % LANES, 1)[:, :dp]
    return x


def place_row(x: Array, row, *, r: int, dp: int, prows: int) -> Array:
    """(1, LANES) with lanes [0, dp) holding the value -> the same
    value at logical ``row``'s lanes of its physical row (zeros
    elsewhere, given zeros beyond dp on input)."""
    if r == 1:
        return x
    return pltpu.roll(x, _split(row, r, prows)[1] * dp, 1)


def phys_row(row, *, r: int, prows: int):
    return _split(row, r, prows)[0]
