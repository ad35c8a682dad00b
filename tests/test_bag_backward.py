"""Fused scatter-add backward kernel + custom_vjp training lookup vs
the dense-embedding autodiff reference."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dequant_bag.autodiff import (
    bag_grad_tpu,
    bag_lookup_train,
    lookup_train,
)
from repro.kernels.dequant_bag.kernel import (
    bag_grad_pallas,
    bag_grad_pallas_rowgrid,
)
from repro.kernels.dequant_bag.ref import bag_grad_ref


def _case(v, d, b, k, seed=0, zero_frac=0.3, with_scales=True):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, v, (b, k)).astype(np.int32))
    w = rng.uniform(0, 1, (b, k)).astype(np.float32)
    w = jnp.asarray(w * (w > zero_frac))   # sprinkle zero-weight slots
    s = jnp.asarray(rng.uniform(0.5, 2.0, v).astype(np.float32)) \
        if with_scales else None
    return g, s, idx, w


@pytest.mark.parametrize("v,d,b,k", [(64, 32, 8, 5), (32, 16, 16, 1),
                                     (128, 48, 5, 9), (50, 24, 3, 4)])
def test_bag_grad_matches_segment_sum_oracle(v, d, b, k):
    g, s, idx, w = _case(v, d, b, k)
    out = bag_grad_pallas(g, s, idx, w, v)
    ref = bag_grad_ref(g, s, idx, w, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_bag_grad_tiled_bit_identical_to_rowgrid():
    """Both scatter layouts accumulate slots in (b, k) lexicographic
    order -> bit-equal, including duplicated rows within a batch."""
    for shape in [(40, 24, 7, 5), (16, 16, 9, 3), (8, 32, 11, 4)]:
        g, s, idx, w = _case(*shape, seed=shape[0])
        tiled = bag_grad_pallas(g, s, idx, w, shape[0])
        rowg = bag_grad_pallas_rowgrid(g, s, idx, w, shape[0])
        np.testing.assert_array_equal(np.asarray(tiled),
                                      np.asarray(rowg))


def test_bag_grad_block_invariance_bitwise():
    """Block geometry changes DMA batching, never accumulation order —
    any block_b, dividing the batch or not, is bit-identical."""
    v = 48
    g, s, idx, w = _case(v, 20, 30, 4, seed=3)
    base = bag_grad_pallas(g, s, idx, w, v, block_b=8)
    for bb in (16, 24, 32, 64):
        out = bag_grad_pallas(g, s, idx, w, v, block_b=bb)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_bag_grad_empty_bags_and_zero_slots():
    """All-zero-weight bags contribute nothing (every RMW skipped);
    rows only referenced by zero-weight slots stay exactly zero."""
    v = 32
    g, s, idx, _ = _case(v, 16, 6, 4, seed=5)
    out = bag_grad_pallas(g, s, idx, jnp.zeros((6, 4)), v)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.zeros((v, 16), np.float32))
    # one live slot: exactly one row gets exactly one contribution
    w = jnp.zeros((6, 4)).at[2, 1].set(0.5)
    out = bag_grad_pallas(g, s, idx, w, v)
    row = int(idx[2, 1])
    expect = np.zeros((v, 16), np.float32)
    expect[row] = 0.5 * float(s[row]) * np.asarray(g[2])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_bag_grad_tpu_dispatch():
    v = 40
    g, s, idx, w = _case(v, 12, 5, 3, seed=7)
    a = bag_grad_tpu(g, s, idx, w, v, use_pallas=True)
    b = bag_grad_tpu(g, s, idx, w, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ gradcheck

def _dense_bag(table, idx, w):
    rows = jnp.take(table, idx, axis=0)
    return (rows * w[..., None]).sum(axis=1)


@pytest.mark.parametrize("v,d,b,k", [(48, 16, 6, 4), (32, 24, 9, 1),
                                     (64, 20, 4, 7)])
def test_gradcheck_vs_dense_autodiff(v, d, b, k):
    """d loss / d table through the custom_vjp (Pallas scatter) matches
    jax.grad through jnp.take to fp32 tolerance — incl. K=1 and
    duplicated rows."""
    rng = np.random.default_rng(v + k)
    table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, v, (b, k)).astype(np.int32))
    w = jnp.asarray(rng.uniform(0, 1, (b, k)).astype(np.float32))
    tgt = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))

    def loss_fused(t, ww):
        out = bag_lookup_train(t, idx, ww, use_pallas=True)
        return ((out - tgt) ** 2).sum()

    def loss_dense(t, ww):
        return ((_dense_bag(t, idx, ww) - tgt) ** 2).sum()

    gt_f, gw_f = jax.grad(loss_fused, argnums=(0, 1))(table, w)
    gt_d, gw_d = jax.grad(loss_dense, argnums=(0, 1))(table, w)
    np.testing.assert_allclose(np.asarray(gt_f), np.asarray(gt_d),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_d),
                               rtol=1e-4, atol=1e-5)


def test_gradcheck_empty_bags_and_zero_weight_slots():
    """Fully padded (all-zero-weight) bags and scattered zero slots:
    gradients w.r.t. the table vanish exactly where nothing was read."""
    v, d, b, k = 40, 12, 6, 4
    rng = np.random.default_rng(11)
    table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, v, (b, k)).astype(np.int32))
    w = rng.uniform(0.2, 1.0, (b, k)).astype(np.float32)
    w[1] = 0.0                     # empty bag
    w[4, 2] = 0.0                  # zero-weight slot
    w = jnp.asarray(w)

    def loss(t):
        return (bag_lookup_train(t, idx, w, use_pallas=True) ** 2).sum()

    g_f = jax.grad(loss)(table)
    g_d = jax.grad(lambda t: ((_dense_bag(t, idx, w)) ** 2).sum())(table)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_d),
                               rtol=1e-4, atol=1e-5)
    live = np.zeros(v, bool)
    live[np.asarray(idx)[np.asarray(w) > 0]] = True
    np.testing.assert_array_equal(
        np.asarray(g_f)[~live], np.zeros(((~live).sum(), d), np.float32))


def test_gradcheck_non_dividing_block_d():
    """Row widths that do not divide the 128 lanes exercise the
    cotangent padding path (logical rows sharing a physical row): the
    gradient is bit-identical across bag blocks and matches dense
    autodiff."""
    v, b, k = 32, 21, 3
    rng = np.random.default_rng(13)
    for d in (7, 13, 20):
        table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, v, (b, k)).astype(np.int32))
        w = jnp.asarray(rng.uniform(0, 1, (b, k)).astype(np.float32))

        def loss(t, bb):
            out = bag_lookup_train(t, idx, w, use_pallas=True, block_b=bb)
            return (out ** 2).sum()

        base = jax.grad(lambda t: loss(t, 8))(table)
        for bb in (16, 24):
            g = jax.grad(lambda t: loss(t, bb))(table)
            np.testing.assert_array_equal(np.asarray(g), np.asarray(base))
        dense = jax.grad(
            lambda t: (_dense_bag(t, idx, w) ** 2).sum())(table)
        np.testing.assert_allclose(np.asarray(base), np.asarray(dense),
                                   rtol=1e-4, atol=1e-5)


def test_lookup_train_forward_bit_identical_to_take():
    """K = 1 has no accumulation: the training gather equals jnp.take
    bit for bit (what ties QAT training to the serving store)."""
    rng = np.random.default_rng(17)
    table = jnp.asarray(rng.standard_normal((30, 8)).astype(np.float32))
    for shape in [(7,), (4, 5), (2, 3, 2)]:
        idx = jnp.asarray(rng.integers(0, 30, shape).astype(np.int32))
        out = lookup_train(table, idx, use_pallas=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(jnp.take(table, idx, axis=0)))


def test_use_pallas_false_delegates_to_oracle():
    v, d, b, k = 24, 8, 5, 2
    rng = np.random.default_rng(19)
    table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, v, (b, k)).astype(np.int32))
    w = jnp.asarray(rng.uniform(0, 1, (b, k)).astype(np.float32))
    a = bag_lookup_train(table, idx, w, use_pallas=False)
    b_ = bag_lookup_train(table, idx, w, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               rtol=1e-6, atol=1e-7)
    ga = jax.grad(lambda t: (bag_lookup_train(t, idx, w,
                                              use_pallas=False)
                             ** 2).sum())(table)
    gb = jax.grad(lambda t: (bag_lookup_train(t, idx, w,
                                              use_pallas=True)
                             ** 2).sum())(table)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                               rtol=1e-5, atol=1e-6)


# -------------------------------------------------- sharded equivalence

def test_sharded_lookup_train_mesh1_matches_host():
    """Row-sharded training gather + gradient on a 1-way mesh vs the
    host custom_vjp path."""
    from repro.dist.packed import sharded_lookup_train

    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(23)
    v, d = 64, 12
    table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, v, (6, 4)).astype(np.int32))

    out = sharded_lookup_train(table, idx, mesh=mesh, use_pallas=True)
    ref = lookup_train(table, idx, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    g_sh = jax.grad(lambda t: (sharded_lookup_train(
        t, idx, mesh=mesh, use_pallas=True) ** 2).sum())(table)
    g_h = jax.grad(lambda t: (jnp.take(t, idx, axis=0) ** 2).sum())(
        table)
    np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_h),
                               rtol=1e-5, atol=1e-6)


def test_sharded_lookup_train_grads_match_4way():
    """mesh=4 in a subprocess (device count must be set before jax
    init): forward replicated-identical, table gradient matches the
    dense autodiff reference to fp32 tolerance."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.dist.packed import sharded_lookup_train

rng = np.random.default_rng(0)
v, d = 64, 12
table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
idx = jnp.asarray(rng.integers(0, v, (9, 5)).astype(np.int32))
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))

out = sharded_lookup_train(table, idx, mesh=mesh, use_pallas=True)
ref = jnp.take(table, idx, axis=0)
np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

tgt = jnp.asarray(rng.standard_normal((9, 5, d)).astype(np.float32))
def loss_sh(t):
    return ((sharded_lookup_train(t, idx, mesh=mesh, use_pallas=True)
             - tgt) ** 2).sum()
def loss_dense(t):
    return ((jnp.take(t, idx, axis=0) - tgt) ** 2).sum()
g_sh = jax.jit(jax.grad(loss_sh))(table)
g_d = jax.jit(jax.grad(loss_dense))(table)
np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_d),
                           rtol=1e-5, atol=1e-6)
print("SHARDED_BWD_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "SHARDED_BWD_OK" in r.stdout, r.stderr[-2000:]
