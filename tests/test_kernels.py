"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpreted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.kernels
from repro.kernels import rows
from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.kernels import should_interpret
from repro.kernels.cin.kernel import cin_layer_pallas
from repro.kernels.cin.ref import cin_layer_ref
from repro.kernels.dequant_bag.kernel import (
    dequant_bag_pallas,
    dequant_bag_pallas_rowgrid,
)
from repro.kernels.dequant_bag.ops import (
    packed_bag_lookup,
    packed_lookup_fused,
    pick_block_b,
)
from repro.kernels.dequant_bag.ref import dequant_bag_ref
from repro.kernels.rowwise_quant.kernel import quantize_rowwise_pallas
from repro.kernels.rowwise_quant.ref import quantize_rowwise_ref


@pytest.mark.parametrize("shape", [(8, 128), (300, 128), (256, 64),
                                   (1, 256), (1000, 32)])
@pytest.mark.parametrize("mode", ["narrow", "full"])
def test_rowwise_quant_rtn_sweep(shape, mode):
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * 0.05
    q1, s1 = quantize_rowwise_pallas(x, mode=mode)
    q2, s2 = quantize_rowwise_ref(x, mode=mode)
    # values exactly on a .5 rounding boundary may land one level apart
    # between the fused kernel and the oracle (1-ulp scale difference);
    # allow <=1 level on <1% of entries, exact elsewhere.
    dq = np.abs(np.asarray(q1, np.int32) - np.asarray(q2, np.int32))
    assert dq.max() <= 1
    assert (dq != 0).mean() < 0.03
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


@pytest.mark.parametrize("shape", [(64, 128), (129, 64)])
def test_rowwise_quant_stochastic_sweep(shape):
    x = jax.random.normal(jax.random.PRNGKey(1), shape) * 0.02
    noise = jax.random.uniform(jax.random.PRNGKey(2), shape)
    q1, _ = quantize_rowwise_pallas(x, noise)
    q2, _ = quantize_rowwise_ref(x, noise)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


@pytest.mark.parametrize("payload_dtype", [jnp.int8, jnp.bfloat16,
                                           jnp.float32])
@pytest.mark.parametrize("v,d,b,k", [(64, 128, 8, 5), (32, 64, 16, 1),
                                     (128, 256, 4, 9)])
def test_dequant_bag_sweep(payload_dtype, v, d, b, k):
    key = jax.random.PRNGKey(0)
    if payload_dtype == jnp.int8:
        payload = jax.random.randint(key, (v, d), -128, 127, jnp.int8)
    else:
        payload = (jax.random.normal(key, (v, d)) * 0.1
                   ).astype(payload_dtype)
    scales = jax.random.uniform(jax.random.PRNGKey(1), (v,)) * 0.01
    idx = jax.random.randint(jax.random.PRNGKey(2), (b, k), 0, v)
    w = jax.random.uniform(jax.random.PRNGKey(3), (b, k))
    out = dequant_bag_pallas(payload, scales, idx, w)
    ref = dequant_bag_ref(payload, scales, idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def _bag_case(v, d, b, k, seed=0, payload_dtype=jnp.int8, zero_frac=0.3):
    key = jax.random.PRNGKey(seed)
    if payload_dtype == jnp.int8:
        payload = jax.random.randint(key, (v, d), -128, 127, jnp.int8)
    else:
        payload = (jax.random.normal(key, (v, d)) * 0.1
                   ).astype(payload_dtype)
    scales = jax.random.uniform(jax.random.PRNGKey(seed + 1), (v,)) * 0.01
    idx = jax.random.randint(jax.random.PRNGKey(seed + 2), (b, k), 0, v)
    w = jax.random.uniform(jax.random.PRNGKey(seed + 3), (b, k))
    w = w * (w > zero_frac)  # sprinkle zero-weight (padded) slots
    return payload, scales, idx, w


def test_dequant_bag_tiled_bit_identical_to_rowgrid():
    """The tiled (B_block) kernel accumulates each bag in the
    same k order as the pre-refactor (B, K)-grid kernel -> bit-equal."""
    for shape in [(64, 128, 8, 5), (32, 64, 16, 1), (128, 256, 7, 9),
                  (50, 24, 3, 4), (40, 48, 5, 3)]:
        for dt in (jnp.int8, jnp.bfloat16, jnp.float32):
            payload, scales, idx, w = _bag_case(*shape, payload_dtype=dt)
            tiled = dequant_bag_pallas(payload, scales, idx, w)
            rowgrid = dequant_bag_pallas_rowgrid(payload, scales, idx, w)
            np.testing.assert_array_equal(np.asarray(tiled),
                                          np.asarray(rowgrid))


def test_dequant_bag_block_size_invariance_bitwise():
    """Block geometry changes DMA batching, never accumulation order:
    any block_b (one grid block, several, a ragged last one) gives
    bit-identical bags."""
    payload, scales, idx, w = _bag_case(80, 96, 37, 6)
    base = dequant_bag_pallas(payload, scales, idx, w, block_b=8)
    for bb in (16, 24, 32, 40, 64):
        out = dequant_bag_pallas(payload, scales, idx, w, block_b=bb)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_dequant_bag_empty_bags():
    """All-zero-weight bags (fully padded requests) come back exactly
    zero — the kernel skips every DMA for them."""
    payload, scales, idx, _ = _bag_case(48, 32, 6, 4)
    w = jnp.zeros((6, 4), jnp.float32)
    out = dequant_bag_pallas(payload, scales, idx, w)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.zeros((6, 32), np.float32))
    # mixed: bags 1 and 4 empty, others live
    w = jax.random.uniform(jax.random.PRNGKey(9), (6, 4)) + 0.1
    w = w.at[1].set(0.0).at[4].set(0.0)
    out = dequant_bag_pallas(payload, scales, idx, w)
    ref = dequant_bag_ref(payload, scales, idx, w)
    np.testing.assert_array_equal(np.asarray(out)[[1, 4]],
                                  np.zeros((2, 32), np.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)


def test_dequant_bag_k1_bit_identical_to_ref():
    """K = 1 has no accumulation, so tiled == ref exactly — the property
    the fused serving lookup's bit-identity rests on."""
    for dt in (jnp.int8, jnp.bfloat16, jnp.float32):
        payload, scales, idx, w = _bag_case(64, 40, 13, 1,
                                            payload_dtype=dt)
        out = dequant_bag_pallas(payload, scales, idx, w)
        ref = dequant_bag_ref(payload, scales, idx, w)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_dequant_bag_d_not_multiple_of_block():
    """Row widths that do not divide the 128 lanes (padded into the
    lane-dense view) match the oracle for every payload dtype."""
    for d in (7, 13, 20, 100, 130):
        for dt in (jnp.int8, jnp.bfloat16, jnp.float32):
            payload, scales, idx, w = _bag_case(32, d, 9, 3,
                                                payload_dtype=dt)
            out = dequant_bag_pallas(payload, scales, idx, w)
            ref = dequant_bag_ref(payload, scales, idx, w)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)


@settings(max_examples=16, deadline=None)
@given(st.integers(1, 12), st.integers(1, 7), st.integers(1, 96),
       st.integers(0, 10_000))
def test_dequant_bag_tiled_property_vs_ref(b, k, d, seed):
    """Property: for random (B, K, D) and weights (with zeros), the
    tiled kernel under picked blocks matches the jnp oracle to fp32
    accumulation-order tolerance and the rowgrid kernel exactly."""
    v = 32
    payload, scales, idx, w = _bag_case(v, d, b, k, seed=seed % 97)
    out = dequant_bag_pallas(payload, scales, idx, w)
    ref = dequant_bag_ref(payload, scales, idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    rowgrid = dequant_bag_pallas_rowgrid(payload, scales, idx, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(rowgrid))


def _working_set(bb, d, k, itemsize):
    # mirrors ops._auto_block_b: double-buffered fp32 out tile (whole
    # lanes) + landing ring of whole lane-dense rows (one packed tile
    # per row for narrow dtypes)
    from repro.kernels import rows
    from repro.kernels.dequant_bag.ops import resolve_nbuf
    dp, r = rows.row_layout(d)
    lanes = -(-dp // 128) * 128
    g = 8 * max(1, 4 // itemsize)
    nbuf = resolve_nbuf(bb * k)
    return 2 * bb * lanes * 4 + nbuf * g * r * dp * itemsize


def test_pick_block_sizes_properties():
    for b, k, d, itemsize in [(1, 1, 1, 1), (256, 8, 512, 1),
                              (1024, 64, 384, 2), (7, 3, 250, 4),
                              (64, 1, 2048, 4)]:
        bb = pick_block_b(b, k, d, itemsize)
        # whole 8-row output tiles, never past the 8-padded batch
        assert bb % 8 == 0 and 8 <= bb <= max(8, -(-b // 8) * 8)
        # working set stays under the VMEM budget (or is minimal bb=8)
        assert bb == 8 or _working_set(bb, d, k, itemsize) <= 2 << 20
        # and the SMEM slot cap
        assert bb == 8 or bb * k <= 8192


def test_pick_block_sizes_awkward_dims():
    """Prime/odd D > 512 pads to whole lanes: the bag block is sized
    against the padded row, so wider rows never get a larger block,
    and the kernel runs correctly end to end."""
    picks = [pick_block_b(4096, 4, d, 4) for d in (64, 521, 1013, 2049)]
    assert all(bb % 8 == 0 for bb in picks)
    assert picks == sorted(picks, reverse=True), picks
    for d in (521, 1013, 999, 2049):
        bb = pick_block_b(4096, 4, d, 4)
        assert bb == 8 or _working_set(bb, d, 4, 4) <= 2 << 20
    payload, scales, idx, w = _bag_case(32, 521, 4, 3)
    out = dequant_bag_pallas(payload, scales, idx, w)
    ref = dequant_bag_ref(payload, scales, idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_pick_block_sizes_env_override(monkeypatch):
    base = pick_block_b(64, 4, 128, 1)
    monkeypatch.setenv("REPRO_DEQUANT_BLOCK_B", "24")
    # env is read per call — overrides apply even after a cached pick
    assert pick_block_b(64, 4, 128, 1) == 24
    # an override off the 8-row tile rule comes back as the block that
    # runs, not as asked
    monkeypatch.setenv("REPRO_DEQUANT_BLOCK_B", "3")
    assert pick_block_b(64, 4, 128, 1) == 8
    monkeypatch.delenv("REPRO_DEQUANT_BLOCK_B")
    assert pick_block_b(64, 4, 128, 1) == base


def test_resolve_block_sizes_call_arg_overrides(monkeypatch):
    from repro.kernels.dequant_bag.ops import resolve_block_b
    # an explicit argument beats the env override, and is rounded up
    # to the tile rule like any other source
    monkeypatch.setenv("REPRO_DEQUANT_BLOCK_B", "64")
    assert resolve_block_b(64, 4, 128, 1, block_b=16) == 16
    assert resolve_block_b(64, 4, 128, 1, block_b=5) == 8
    assert resolve_block_b(64, 4, 128, 1, block_b=17) == 24
    with pytest.raises(ValueError):
        resolve_block_b(8, 2, 16, 1, block_b=0)


def test_should_interpret_autodetect_and_overrides(monkeypatch):
    """CPU backend -> interpret by default; arg beats env beats
    detection."""
    repro.kernels._default_interpret.cache_clear()
    try:
        assert should_interpret() is True          # tests run on CPU
        assert should_interpret(False) is False    # explicit arg wins
        assert should_interpret(True) is True
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        repro.kernels._default_interpret.cache_clear()
        assert should_interpret() is False         # env forces compile
        assert should_interpret(True) is True      # arg still wins
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        repro.kernels._default_interpret.cache_clear()
        assert should_interpret() is True
    finally:
        repro.kernels._default_interpret.cache_clear()


def test_packed_lookup_fused_bit_identical():
    """The fused per-tier K=1 path == packed_store.lookup, bit for bit,
    for any index shape."""
    cfg = FQuantConfig(stochastic=False)
    stt = qs.init(jax.random.PRNGKey(0), 96, 64, scale=0.05)
    pri = jnp.concatenate([jnp.zeros(32), jnp.full(32, 1e4),
                           jnp.full(32, 1e6)])
    stt = stt._replace(priority=pri)
    stt = stt._replace(table=qs.snap(stt.table,
                                     qs.current_tiers(stt, cfg), cfg))
    packed = pack(stt, cfg)
    for shape in [(17,), (6, 7), (2, 3, 4)]:
        idx = jax.random.randint(jax.random.PRNGKey(1), shape, 0, 96)
        fused = packed_lookup_fused(packed, idx, use_pallas=True)
        orac = ps.lookup(packed, idx)
        assert fused.shape == orac.shape
        np.testing.assert_array_equal(np.asarray(fused),
                                      np.asarray(orac))
    # use_pallas=False delegates to the oracle itself
    idx = jnp.arange(9)
    np.testing.assert_array_equal(
        np.asarray(packed_lookup_fused(packed, idx, use_pallas=False)),
        np.asarray(ps.lookup(packed, idx)))
    # packed_store.lookup_fused is the same entry point
    np.testing.assert_array_equal(
        np.asarray(ps.lookup_fused(packed, idx, use_pallas=True)),
        np.asarray(ps.lookup(packed, idx)))


def test_packed_bag_lookup_weighted():
    cfg = FQuantConfig(stochastic=False)
    stt = qs.init(jax.random.PRNGKey(2), 96, 32, scale=0.05)
    pri = jnp.concatenate([jnp.zeros(32), jnp.full(32, 1e4),
                           jnp.full(32, 1e6)])
    stt = stt._replace(priority=pri)
    stt = stt._replace(table=qs.snap(stt.table,
                                     qs.current_tiers(stt, cfg), cfg))
    packed = pack(stt, cfg)
    rng = np.random.default_rng(4)
    idx = jnp.asarray(rng.integers(0, 96, (5, 6)).astype(np.int32))
    w = jnp.asarray(rng.uniform(0, 1, (5, 6)).astype(np.float32))
    out = packed_bag_lookup(packed, idx, weights=w, use_pallas=True)
    rows = np.asarray(ps.lookup(packed, idx)) * np.asarray(w)[..., None]
    np.testing.assert_allclose(np.asarray(out), rows.sum(axis=1),
                               rtol=1e-5, atol=1e-6)


def test_packed_bag_lookup_vs_jnp_path():
    from repro.core.packed_store import bag_lookup as jnp_bag
    cfg = FQuantConfig(stochastic=False)
    st = qs.init(jax.random.PRNGKey(0), 96, 64, scale=0.05)
    pri = jnp.concatenate([jnp.zeros(32), jnp.full(32, 1e4),
                           jnp.full(32, 1e6)])
    st = st._replace(priority=pri)
    st = st._replace(table=qs.snap(st.table, qs.current_tiers(st, cfg),
                                   cfg))
    packed = pack(st, cfg)
    idx = jax.random.randint(jax.random.PRNGKey(1), (6, 4), 0, 96)
    out = packed_bag_lookup(packed, idx)
    seg = jnp.repeat(jnp.arange(6), 4)
    ref = jnp_bag(packed, idx.reshape(-1), seg, 6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("b,h,m,d,o", [(17, 12, 10, 8, 24),
                                       (64, 39, 39, 10, 200),
                                       (3, 5, 7, 4, 2)])
def test_cin_sweep(b, h, m, d, o):
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (o, h, m)) * 0.1
    xk = jax.random.normal(jax.random.PRNGKey(1), (b, h, d))
    x0 = jax.random.normal(jax.random.PRNGKey(2), (b, m, d))
    out = cin_layer_pallas(w, xk, x0)
    ref = cin_layer_ref(w, xk, x0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_cin_block_invariance():
    """Different block shapes give identical results."""
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (32, 8, 8)) * 0.1
    xk = jax.random.normal(jax.random.PRNGKey(4), (40, 8, 16))
    x0 = jax.random.normal(jax.random.PRNGKey(5), (40, 8, 16))
    a = cin_layer_pallas(w, xk, x0, block_b=8, block_o=8)
    b_ = cin_layer_pallas(w, xk, x0, block_b=64, block_o=32)
    # block shape changes the fp32 accumulation order -> allclose not equal
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# lane-dense placement: the view built once on the host


LAYOUT_DTYPES = [np.int8, jnp.bfloat16, np.float32]


def _table(v, d, dtype, seed=0):
    x = np.random.default_rng(seed).standard_normal((v, d)) * 40
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", LAYOUT_DTYPES, ids=["int8", "bf16", "f32"])
@pytest.mark.parametrize("d", [32, 64, 96, 128])
def test_lane_dense_host_byte_equal_to_lane_dense(dtype, d):
    v = 1003
    x = _table(v, d, dtype)
    assert v % rows.phys_rows(v, d, x.dtype) != 0
    want = np.asarray(rows.lane_dense(jnp.asarray(x)))
    got = rows.lane_dense_host(x)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("dtype", LAYOUT_DTYPES, ids=["int8", "bf16", "f32"])
@pytest.mark.parametrize("d", [32, 64, 96, 128])
def test_from_lane_dense_round_trips(dtype, d):
    v = 1003
    x = _table(v, d, dtype, seed=1)
    ld = rows.LaneDense(jnp.asarray(rows.lane_dense_host(x)), v, d)
    back = rows.from_lane_dense(ld.phys, v, d)
    assert np.asarray(back).tobytes() == x.tobytes()
    assert np.asarray(ld).tobytes() == x.tobytes()


def test_lane_dense_flattens_with_its_logical_shape():
    x = _table(50, 64, np.int8)
    ld = rows.LaneDense(jnp.asarray(rows.lane_dense_host(x)), 50, 64)
    leaves, tree = jax.tree.flatten(ld)
    assert len(leaves) == 1 and leaves[0] is ld.phys
    back = jax.tree.unflatten(tree, leaves)
    assert isinstance(back, rows.LaneDense)
    assert (back.v, back.d) == (50, 64) and back.phys is ld.phys
    # the logical shape is static: another V is another structure
    other = rows.LaneDense(ld.phys, 49, 64)
    assert jax.tree.structure(other) != tree
    # and a jitted function sees it through a trace
    got = jax.jit(lambda t: rows.take_rows(t, jnp.arange(50)))(ld)
    assert np.asarray(got).tobytes() == x.tobytes()


def test_lane_dense_reports_the_logical_table():
    v, d = 77, 24
    x = _table(v, d, jnp.bfloat16)
    ld = rows.LaneDense(jnp.asarray(rows.lane_dense_host(x)), v, d)
    assert ld.phys.shape != (v, d)
    assert ld.shape == (v, d) and ld.ndim == 2 and ld.size == v * d
    assert ld.dtype == x.dtype
    assert ld.nbytes == x.nbytes
    assert rows.lane_dense(ld) is ld.phys


def test_relayout_counter_counts_logical_tables_only():
    from repro import obs
    x = jnp.asarray(_table(100, 64, np.int8))
    ld = rows.LaneDense(jnp.asarray(rows.lane_dense_host(np.asarray(x))),
                        100, 64)
    name = "kernels.relayout_traced.int8.100x64"
    with obs.bind(obs.Registry()) as reg:
        rows.lane_dense(ld)
        assert reg.counters.get(name, 0) == 0
        rows.lane_dense(x)
        assert reg.counters[name] == 1
        # a 32-bit table already lane-dense needs no relayout
        rows.lane_dense(jnp.zeros((64, 128), jnp.float32))
        assert set(reg.counters) == {name}


def _three_tier_store(d, seed=0):
    cfg = FQuantConfig(stochastic=False)
    stt = qs.init(jax.random.PRNGKey(seed), 96, d, scale=0.05)
    pri = jnp.concatenate([jnp.zeros(32), jnp.full(32, 1e4),
                           jnp.full(32, 1e6)])
    stt = stt._replace(priority=pri)
    stt = stt._replace(table=qs.snap(stt.table,
                                     qs.current_tiers(stt, cfg), cfg))
    return pack(stt, cfg)


@pytest.mark.parametrize("d", [24, 64])
def test_placed_store_reads_bit_equal_to_logical(d):
    """Over a store from ``place_packed`` every reader gives the bytes
    it gives over the logical host store: both kernels (interpreted)
    and the jnp oracles."""
    from repro.dist.packed import place_packed
    from repro.kernels.bag_matmul.ops import packed_bag_matmul
    host = _three_tier_store(d)
    placed = place_packed(host)
    assert all(isinstance(p, rows.LaneDense) for p in
               (placed.payload8, placed.payload16, placed.payload32))
    assert placed.nbytes() == host.nbytes()
    assert (placed.vocab, placed.dim) == (host.vocab, host.dim)
    rng = np.random.default_rng(3)
    idx = jnp.asarray(rng.integers(0, 96, (9, 5)).astype(np.int32))
    w = jnp.asarray(rng.uniform(0, 1, (9, 5)).astype(np.float32))
    w3 = jnp.asarray(rng.standard_normal((5, d, 16)).astype(np.float32))

    def same(fn):
        a, b = np.asarray(fn(placed)), np.asarray(fn(host))
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()

    same(lambda s: packed_bag_lookup(s, idx, weights=w, use_pallas=True))
    same(lambda s: packed_bag_lookup(s, idx, weights=w, use_pallas=False))
    same(lambda s: packed_lookup_fused(s, idx, use_pallas=True))
    same(lambda s: ps.lookup(s, idx))
    same(lambda s: jax.jit(ps.lookup)(s, idx))
    same(lambda s: packed_bag_matmul(s, idx, w3, use_pallas=True))
    same(lambda s: packed_bag_matmul(s, idx, w3, weights=w,
                                     use_pallas=False))
    same(lambda s: ps.bag_lookup(s, idx.reshape(-1),
                                 jnp.repeat(jnp.arange(9), 5), 9))
