"""PackedStore bag lookup with weights + row-sharded serving path
(repro.dist.packed) vs the single-device oracle."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs


def _store_with_tiers(v=96, d=32, seed=0):
    st = qs.init(jax.random.PRNGKey(seed), v, d, scale=0.05)
    third = v // 3
    pri = jnp.concatenate([jnp.zeros(third), jnp.full(third, 1e4),
                           jnp.full(v - 2 * third, 1e6)])
    return st._replace(priority=pri)


def _packed(seed=0):
    cfg = FQuantConfig(stochastic=False)
    st = _store_with_tiers(seed=seed)
    st = st._replace(table=qs.snap(
        st.table, qs.current_tiers(st, cfg), cfg))
    return pack(st, cfg)


def test_bag_lookup_weighted_matches_manual():
    packed = _packed()
    rng = np.random.default_rng(3)
    n, bags = 40, 7
    idx = jnp.asarray(rng.integers(0, packed.vocab, n).astype(np.int32))
    seg = jnp.asarray(np.sort(rng.integers(0, bags, n)).astype(np.int32))
    w = jnp.asarray(rng.standard_normal(n).astype(np.float32))

    out = ps.bag_lookup(packed, idx, seg, bags, weights=w)
    assert out.shape == (bags, packed.dim)

    rows = np.asarray(ps.lookup(packed, idx)) * np.asarray(w)[:, None]
    expect = np.zeros((bags, packed.dim), np.float32)
    for i, b in enumerate(np.asarray(seg)):
        expect[b] += rows[i]
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5,
                               atol=1e-5)


def test_bag_lookup_unweighted_is_weight_one():
    packed = _packed(seed=1)
    rng = np.random.default_rng(5)
    idx = jnp.asarray(rng.integers(0, packed.vocab, 20).astype(np.int32))
    seg = jnp.asarray(np.sort(rng.integers(0, 4, 20)).astype(np.int32))
    a = ps.bag_lookup(packed, idx, seg, 4)
    b = ps.bag_lookup(packed, idx, seg, 4, weights=jnp.ones(20))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_bag_lookup_empty_bags_are_zero():
    """Bags no index maps to must come back exactly zero (segment_sum
    semantics), weighted or not — the serving path pads ragged request
    streams with empty bags."""
    packed = _packed(seed=2)
    rng = np.random.default_rng(9)
    n, bags = 12, 8
    idx = jnp.asarray(rng.integers(0, packed.vocab, n).astype(np.int32))
    seg = jnp.asarray(np.sort(rng.integers(0, 3, n)).astype(np.int32))
    occupied = np.unique(np.asarray(seg))
    empty = np.setdiff1d(np.arange(bags), occupied)
    assert empty.size > 0
    for w in (None, jnp.asarray(rng.standard_normal(n)
                                .astype(np.float32))):
        out = np.asarray(ps.bag_lookup(packed, idx, seg, bags, weights=w))
        assert out.shape == (bags, packed.dim)
        np.testing.assert_array_equal(
            out[empty], np.zeros((empty.size, packed.dim), np.float32))
        assert np.abs(out[occupied]).sum() > 0


def test_bag_lookup_all_bags_empty():
    """num_bags with a zero-length index stream: all-zero output."""
    packed = _packed(seed=3)
    out = ps.bag_lookup(packed, jnp.zeros((0,), jnp.int32),
                        jnp.zeros((0,), jnp.int32), 5)
    np.testing.assert_array_equal(
        np.asarray(out), np.zeros((5, packed.dim), np.float32))


def test_sharded_fused_lookup_mesh1_bit_identical():
    """Fused tiled-kernel sharded lookup on a 1-way mesh == the
    single-device oracle, bit for bit; the rect bag path matches the
    host fused bag exactly (no cross-shard partial sums at mesh=1)."""
    from repro.dist.packed import (shard_packed, sharded_bag_lookup_rect,
                                   sharded_lookup)
    from repro.kernels.dequant_bag.ops import packed_bag_lookup

    packed = _packed(seed=4)
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sp = shard_packed(packed, mesh)
    rng = np.random.default_rng(17)
    idx = jnp.asarray(rng.integers(0, packed.vocab, (9, 5))
                      .astype(np.int32))
    out = sharded_lookup(sp, idx, mesh=mesh, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ps.lookup(packed, idx)))
    w = jnp.asarray(rng.uniform(0, 1, (9, 5)).astype(np.float32))
    bags = sharded_bag_lookup_rect(sp, idx, mesh=mesh, weights=w,
                                   use_pallas=True)
    host = packed_bag_lookup(packed, idx, weights=w, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(bags), np.asarray(host))


def test_sharded_lookup_matches_oracle_4way():
    """shard_packed + sharded_{bag_,}lookup on a 4-device host mesh in a
    subprocess (device count must be set before jax init), vs the
    single-device packed_store oracle."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.dist.packed import (shard_packed, sharded_bag_lookup,
                               sharded_lookup)

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

rng = np.random.default_rng(11)
idx = jnp.asarray(rng.integers(0, v, 64).astype(np.int32))
seg = jnp.asarray(np.sort(rng.integers(0, 9, 64)).astype(np.int32))
w = jnp.asarray(rng.standard_normal(64).astype(np.float32))

out = sharded_lookup(sp, idx, mesh=mesh)
ref = ps.lookup(packed, idx)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)

for weights in (None, w):
    outb = sharded_bag_lookup(sp, idx, seg, 9, mesh=mesh, weights=weights)
    refb = ps.bag_lookup(packed, idx, seg, 9, weights=weights)
    np.testing.assert_allclose(np.asarray(outb), np.asarray(refb),
                               rtol=2e-5, atol=2e-5)

# fused tiled-kernel paths: lookup is bit-identical (each row owned by
# exactly one shard); rect bags match to psum partial-sum order
from repro.dist.packed import sharded_bag_lookup_rect
outf = sharded_lookup(sp, idx, mesh=mesh, use_pallas=True)
np.testing.assert_array_equal(np.asarray(outf), np.asarray(ref))
idx2 = idx.reshape(8, 8)
w2 = w.reshape(8, 8)
bagf = sharded_bag_lookup_rect(sp, idx2, mesh=mesh, weights=w2,
                               use_pallas=True)
bagj = sharded_bag_lookup_rect(sp, idx2, mesh=mesh, weights=w2,
                               use_pallas=False)
# k-sequential kernel accumulation vs XLA reduce order: allclose, and
# bit-equal is still demanded for the K=1 lookup above
np.testing.assert_allclose(np.asarray(bagf), np.asarray(bagj),
                           rtol=1e-6, atol=1e-7)
rows = np.asarray(ps.lookup(packed, idx2)) * np.asarray(w2)[..., None]
np.testing.assert_allclose(np.asarray(bagf), rows.sum(axis=1),
                           rtol=2e-5, atol=2e-5)
print("SHARDED_PACKED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "SHARDED_PACKED_OK" in r.stdout, r.stderr[-2000:]
