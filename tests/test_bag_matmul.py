"""Fused dequant-bag -> matmul kernel (repro.kernels.bag_matmul):
oracle equality, tiling invariance, the custom_vjp training twin vs
dense autodiff, the sharded serving path, and the model fused heads.

Numerical contract (kernel.py docstring): the fused kernel equals
exact fp32 sequential accumulation; K=1 bags are bit-identical to the
unfused oracle, multi-slot bags with non-unit weights may differ from
the dequant_bag path by 1 ulp (XLA FMA-contracts its accumulate), so
those comparisons are tight-allclose, not bitwise."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.kernels.bag_matmul.kernel import bag_matmul_pallas
from repro.kernels.bag_matmul.ops import packed_bag_matmul
from repro.kernels.bag_matmul.ref import bag_matmul_ref


def _case(b, k, d, h, v=64, seed=0):
    rng = np.random.default_rng(seed)
    payload = jnp.asarray(rng.integers(-128, 128, (v, d)).astype(np.int8))
    scales = jnp.asarray(rng.uniform(0.001, 0.02, v).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, v, (b, k)).astype(np.int32))
    w = jnp.asarray(rng.uniform(0.1, 1.0, (b, k)).astype(np.float32))
    w3 = jnp.asarray(rng.standard_normal((k, d, h)).astype(np.float32)
                     * 0.1)
    return payload, scales, idx, w, w3


def _store_with_tiers(v=96, d=32, seed=0):
    st = qs.init(jax.random.PRNGKey(seed), v, d, scale=0.05)
    third = v // 3
    pri = jnp.concatenate([jnp.zeros(third), jnp.full(third, 1e4),
                           jnp.full(v - 2 * third, 1e6)])
    return st._replace(priority=pri)


def _packed(v=96, d=32, seed=0, table=None):
    cfg = FQuantConfig(stochastic=False)
    st = _store_with_tiers(v=v, d=d, seed=seed)
    if table is not None:
        st = st._replace(table=table)
    st = st._replace(table=qs.snap(
        st.table, qs.current_tiers(st, cfg), cfg))
    return pack(st, cfg)


@pytest.mark.parametrize("b,k,d,h", [(5, 3, 16, 8), (8, 1, 32, 4),
                                     (7, 4, 24, 10)])
def test_bag_matmul_matches_ref(b, k, d, h):
    payload, scales, idx, w, w3 = _case(b, k, d, h)
    out = bag_matmul_pallas(payload, scales, idx, w, w3)
    ref = bag_matmul_ref(payload, scales, idx, w, w3)
    assert out.shape == (b, h) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)


def test_bag_matmul_k1_bit_identical_to_ref():
    """Single-slot bags (the per-field serving layout): no accumulation
    across slots, so fused == unfused bit for bit."""
    payload, scales, idx, w, w3 = _case(9, 1, 16, 8, seed=3)
    out = bag_matmul_pallas(payload, scales, idx, w, w3)
    ref = bag_matmul_ref(payload, scales, idx, w, w3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_bag_matmul_block_invariance():
    """(block_b, block_h) is a scheduling choice: every tiling —
    including non-dividing edge tiles — computes the same result, which
    is what makes the measured autotune cache safe to apply blindly.
    Tight-allclose, not bitwise: the per-tile dot's reduction order is
    backend-scheduled (CPU interpret lowers it to a gemm whose blocking
    varies with the tile shape)."""
    payload, scales, idx, w, w3 = _case(9, 3, 16, 12, seed=5)
    base = bag_matmul_pallas(payload, scales, idx, w, w3,
                             block_b=9, block_h=12)
    for bb, bh in ((2, 8), (4, 16), (7, 4), (1, 12)):
        out = bag_matmul_pallas(payload, scales, idx, w, w3,
                                block_b=bb, block_h=bh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=2e-5, atol=1e-6)


def test_bag_matmul_scale_after():
    """int8-in specialization: rows enter the matmul unscaled and the
    per-slot (scale*weight) factor applies to the (B, H) result —
    valid only for K=1 bags, where the factor is per-row."""
    payload, scales, idx, w, w3 = _case(6, 1, 16, 8, seed=7)
    out = bag_matmul_pallas(payload, scales, idx, w, w3,
                            scale_after=True)
    ref = bag_matmul_ref(payload, scales, idx, w, w3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)


def test_packed_bag_matmul_fused_vs_unfused():
    """The acceptance gate: fused serving == unfused
    (lookup + reshape + matmul) within fp32 tolerance on a mixed-tier
    packed store, for 2d and 3d weight layouts and the int8-direct
    fast path."""
    packed = _packed()
    rng = np.random.default_rng(11)
    b, f, h = 9, 5, 12
    idx = jnp.asarray(rng.integers(0, packed.vocab, (b, f))
                      .astype(np.int32))
    w2 = jnp.asarray(rng.standard_normal((f * packed.dim, h))
                     .astype(np.float32) * 0.1)
    unfused = packed_bag_matmul(packed, idx, w2, use_pallas=False)
    for kwargs in ({}, {"int8_direct": True},):
        fused = packed_bag_matmul(packed, idx, w2, use_pallas=True,
                                  **kwargs)
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(unfused),
                                   rtol=1e-6, atol=1e-6)
    w3 = w2.reshape(f, packed.dim, h)
    fused3 = packed_bag_matmul(packed, idx, w3, use_pallas=True)
    np.testing.assert_allclose(np.asarray(fused3), np.asarray(unfused),
                               rtol=1e-6, atol=1e-6)
    # core wrapper is the same computation
    wrapped = ps.bag_matmul(packed, idx, w2, use_pallas=True)
    np.testing.assert_array_equal(
        np.asarray(wrapped),
        np.asarray(packed_bag_matmul(packed, idx, w2, use_pallas=True)))


def test_bag_matmul_train_gradcheck_vs_dense():
    """bag_matmul_train's custom_vjp (serving kernels in both passes)
    vs jnp dense autodiff: dtable, dw3 and dweights all match."""
    from repro.kernels.bag_matmul.autodiff import bag_matmul_train

    rng = np.random.default_rng(13)
    v, d, b, k, h = 32, 8, 6, 3, 5
    table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, v, (b, k)).astype(np.int32))
    w3 = jnp.asarray(rng.standard_normal((k, d, h)).astype(np.float32))
    wts = jnp.asarray(rng.uniform(0.1, 1.0, (b, k)).astype(np.float32))
    cot = jnp.asarray(rng.standard_normal((b, h)).astype(np.float32))

    def fused_loss(t, w, ww):
        return jnp.sum(bag_matmul_train(t, idx, w, ww,
                                        use_pallas=True) * cot)

    def dense_loss(t, w, ww):
        rows = jnp.take(t, idx, axis=0) * ww[..., None]
        return jnp.sum(jnp.einsum("bkd,kdh->bh", rows, w) * cot)

    got = jax.grad(fused_loss, argnums=(0, 1, 2))(table, w3, wts)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(table, w3, wts)
    for g, ref in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_bag_matmul_train_forward_is_serving_kernel():
    from repro.kernels.bag_matmul.autodiff import bag_matmul_train

    rng = np.random.default_rng(17)
    v, d, b, k, h = 32, 8, 6, 3, 5
    table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, v, (b, k)).astype(np.int32))
    w2 = jnp.asarray(rng.standard_normal((k * d, h)).astype(np.float32))
    out = bag_matmul_train(table, idx, w2, use_pallas=True)
    rows = jnp.take(table, idx, axis=0)
    ref = jnp.einsum("bkd,kdh->bh", rows, w2.reshape(k, d, h))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_sharded_bag_matmul_mesh1_matches_host():
    from repro.dist.packed import shard_packed, sharded_bag_matmul

    packed = _packed(seed=4)
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sp = shard_packed(packed, mesh)
    rng = np.random.default_rng(19)
    b, f, h = 8, 4, 6
    idx = jnp.asarray(rng.integers(0, packed.vocab, (b, f))
                      .astype(np.int32))
    w = jnp.asarray(rng.standard_normal((f * packed.dim, h))
                    .astype(np.float32) * 0.1)
    wts = jnp.asarray(rng.uniform(0.1, 1.0, (b, f)).astype(np.float32))
    host = packed_bag_matmul(packed, idx, w, use_pallas=False)
    for use_pallas in (False, True):
        out = sharded_bag_matmul(sp, idx, w, mesh=mesh,
                                 use_pallas=use_pallas)
        np.testing.assert_allclose(np.asarray(out), np.asarray(host),
                                   rtol=2e-5, atol=2e-5)
    outw = sharded_bag_matmul(sp, idx, w, mesh=mesh, weights=wts,
                              use_pallas=True)
    hostw = packed_bag_matmul(packed, idx, w, weights=wts,
                              use_pallas=False)
    np.testing.assert_allclose(np.asarray(outw), np.asarray(hostw),
                               rtol=2e-5, atol=2e-5)


def test_sharded_bag_matmul_mesh4_matches_oracle():
    """4-way host mesh in a subprocess (device count must be set before
    jax init): psum'd (B, H) tiles vs the single-device oracle."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import FQuantConfig, pack
from repro.core import qat_store as qs
from repro.dist.packed import shard_packed, sharded_bag_matmul
from repro.kernels.bag_matmul.ops import packed_bag_matmul

v, d = 96, 32
st = qs.init(jax.random.PRNGKey(0), v, d, scale=0.05)
third = v // 3
pri = jnp.concatenate([jnp.zeros(third), jnp.full(third, 1e4),
                       jnp.full(v - 2 * third, 1e6)])
st = st._replace(priority=pri)
cfg = FQuantConfig(stochastic=False)
st = st._replace(table=qs.snap(st.table, qs.current_tiers(st, cfg), cfg))
packed = pack(st, cfg)

mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
sp = shard_packed(packed, mesh)
rng = np.random.default_rng(23)
b, f, h = 8, 4, 6
idx = jnp.asarray(rng.integers(0, v, (b, f)).astype(np.int32))
w = jnp.asarray(rng.standard_normal((f * d, h)).astype(np.float32) * 0.1)
wts = jnp.asarray(rng.uniform(0.1, 1.0, (b, f)).astype(np.float32))

for use_pallas in (False, True):
    for weights in (None, wts):
        out = sharded_bag_matmul(sp, idx, w, mesh=mesh, weights=weights,
                                 use_pallas=use_pallas)
        ref = packed_bag_matmul(packed, idx, w, weights=weights,
                                use_pallas=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
out8 = sharded_bag_matmul(sp, idx, w, mesh=mesh, use_pallas=True,
                          int8_direct=True)
ref = packed_bag_matmul(packed, idx, w, use_pallas=False)
np.testing.assert_allclose(np.asarray(out8), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("SHARDED_BAGMM_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "SHARDED_BAGMM_OK" in r.stdout, r.stderr[-2000:]


def test_mlp_tail_invariant():
    """mlp(params, x) == mlp_tail(params, x @ w0) — the identity the
    fused heads rely on, for 1-layer and deep nets."""
    from repro.models import layers as L

    rng = np.random.default_rng(29)
    x = jnp.asarray(rng.standard_normal((5, 12)).astype(np.float32))
    for dims in ((12, 7), (12, 16, 8, 1)):
        params = L.mlp_init(jax.random.PRNGKey(1), dims, jnp.float32)
        full = L.mlp(params, x)
        tail = L.mlp_tail(params, x @ params["l0"]["w"])
        np.testing.assert_allclose(np.asarray(tail), np.asarray(full),
                                   rtol=1e-6, atol=1e-6)


def _packed_for_model(model, params, seed=0):
    v = model.spec.total_rows
    d = model.spec.dim
    return _packed(v=v, d=d, seed=seed, table=params["embed_table"])


def test_wide_deep_fused_head_matches_head():
    from repro.models import recsys

    model = recsys.make_wide_deep(recsys.WideDeepConfig(
        cardinalities=(40, 30, 50), embed_dim=8, mlp=(16, 8)))
    params = model.init(jax.random.PRNGKey(2))
    packed = _packed_for_model(model, params)
    rng = np.random.default_rng(31)
    b = 6
    idx = jnp.asarray(np.stack([rng.integers(0, c, b) for c in
                                (40, 30, 50)], axis=1).astype(np.int32))
    batch = {"indices": idx}
    gidx = jnp.asarray(np.asarray(idx)
                       + model.spec.offsets()[None, :])
    emb = ps.lookup(packed, gidx)
    assert model.extras["fused_needs_emb"] is False
    fused = model.extras["fused_head"](
        params, batch, lambda w: ps.bag_matmul(packed, gidx, w,
                                               use_pallas=True))
    unfused = model.head(params, emb, batch)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-5, atol=1e-6)


def test_xdeepfm_fused_head_matches_head():
    from repro.models import recsys

    model = recsys.make_xdeepfm(recsys.XDeepFMConfig(
        cardinalities=(30, 20), embed_dim=8, cin_layers=(6,),
        mlp=(12,)))
    params = model.init(jax.random.PRNGKey(3))
    packed = _packed_for_model(model, params, seed=1)
    rng = np.random.default_rng(37)
    b = 5
    idx = jnp.asarray(np.stack([rng.integers(0, c, b) for c in
                                (30, 20)], axis=1).astype(np.int32))
    batch = {"indices": idx}
    gidx = jnp.asarray(np.asarray(idx)
                       + model.spec.offsets()[None, :])
    emb = ps.lookup(packed, gidx)
    assert model.extras["fused_needs_emb"] is True
    fused = model.extras["fused_head"](
        params, batch, lambda w: ps.bag_matmul(packed, gidx, w,
                                               use_pallas=True), emb)
    unfused = model.head(params, emb, batch)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-5, atol=1e-6)
