"""Online serving oracle tests: repack_delta == full pack (bit-exact,
single-device and row-sharded), hot-cache bit-identity, OnlineServer
end-to-end."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.core.priority import serve_update
from repro.core.tiers import TierConfig, tier_crossings
from repro.serve import (
    OnlineConfig,
    OnlineServer,
    build_cache,
    cached_lookup,
    drifting_zipf_batch,
    empty_cache,
)

V, D = 160, 24
CFG = FQuantConfig(tiers=TierConfig(t8=5.0, t16=50.0), stochastic=False)


def _store(seed=0, scale_pri=20.0):
    rng = np.random.default_rng(seed)
    st = qs.init(jax.random.PRNGKey(seed), V, D, scale=0.05)
    pri = jnp.asarray((rng.pareto(1.2, V) * scale_pri).astype(np.float32))
    st = st._replace(priority=pri)
    return st._replace(table=qs.snap(
        st.table, qs.current_tiers(st, CFG), CFG))


def _perturb(st, rng):
    f = rng.uniform(0.05, 20.0, V).astype(np.float32)
    return st._replace(priority=jnp.asarray(np.asarray(st.priority) * f))


def test_repack_delta_matches_full_pack_bitwise():
    """Iterated delta repacks after random priority perturbations stay
    bit-identical to a fresh full pack (unpack round-trip), with exact
    candidate sets from tier_crossings."""
    rng = np.random.default_rng(7)
    st = _store()
    packed = pack(st, CFG)
    for _ in range(6):
        st = _perturb(st, rng)
        changed, hist = tier_crossings(
            ps.packed_tiers(packed), qs.current_tiers(st, CFG))
        assert hist.sum() == changed.size
        packed = ps.repack_delta(packed, st, CFG, changed)
        full = pack(st, CFG)
        np.testing.assert_array_equal(np.asarray(ps.unpack(packed)),
                                      np.asarray(ps.unpack(full)))
        # tier populations (hence memory accounting) match too
        np.testing.assert_array_equal(
            np.bincount(ps.packed_tiers(packed), minlength=3),
            np.bincount(ps.packed_tiers(full), minlength=3))
        assert packed.nbytes() == full.nbytes()


def test_repack_delta_candidate_superset_and_noop():
    rng = np.random.default_rng(3)
    st = _store(seed=1)
    packed = pack(st, CFG)
    # no priority change -> no-op (same object)
    assert ps.repack_delta(packed, st, CFG, np.arange(V)) is packed
    # a full-vocab candidate set degrades to the exact mover set
    st2 = _perturb(st, rng)
    a = ps.repack_delta(packed, st2, CFG, np.arange(V))
    changed, _ = tier_crossings(ps.packed_tiers(packed),
                                qs.current_tiers(st2, CFG))
    b = ps.repack_delta(packed, st2, CFG, changed)
    np.testing.assert_array_equal(np.asarray(ps.unpack(a)),
                                  np.asarray(ps.unpack(b)))


def test_repack_delta_tier_emptied_and_refilled():
    """Forcing every row through one tier exercises the 1-row
    placeholder convention for emptied payload arrays."""
    st = _store(seed=2)
    packed = pack(st, CFG)
    for pri in (np.zeros(V), np.full(V, 1e3), np.zeros(V)):
        st = st._replace(priority=jnp.asarray(pri, jnp.float32))
        packed = ps.repack_delta(packed, st, CFG, np.arange(V))
        np.testing.assert_array_equal(
            np.asarray(ps.unpack(packed)),
            np.asarray(ps.unpack(pack(st, CFG))))


def test_pack_and_repack_scale_dtypes_stay_fp32():
    """Regression: scale columns must stay fp32 through pack AND the
    repack_delta host round-trip (numpy promotes to float64 on contact
    with python floats; a float64 scale column doubles serving scale
    bytes and breaks delta-vs-full-pack bit-identity)."""
    rng = np.random.default_rng(13)
    st = _store(seed=11)
    packed = pack(st, CFG)

    def check(p, where):
        assert p.scale8.dtype == jnp.float32, where
        assert p.scale16.dtype == jnp.float32, where
        assert p.payload8.dtype == jnp.int8, where
        assert p.payload16.dtype == jnp.bfloat16, where
        assert p.payload32.dtype == jnp.float32, where

    check(packed, "pack")
    for i in range(3):
        st = _perturb(st, rng)
        packed = ps.repack_delta(packed, st, CFG, np.arange(V))
        check(packed, f"repack_delta[{i}]")
    # _quantize_tier normalises even float64 host rows
    from repro.core.packed_store import _quantize_tier
    from repro.core.tiers import Tier
    rows64 = rng.standard_normal((4, D))            # float64
    for tier in (Tier.INT8, Tier.HALF):
        _, s = _quantize_tier(rows64, tier, CFG)
        assert s.dtype == np.float32, tier


def test_hot_cache_bit_identical_and_hit_accounting():
    st = _store(seed=3)
    packed = pack(st, CFG)
    cache = build_cache(packed, st.priority, 32)
    assert cache.capacity == 32
    rng = np.random.default_rng(11)
    idx = jnp.asarray(rng.integers(0, V, (16, 6)).astype(np.int32))
    out, hits = cached_lookup(packed, cache, idx)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ps.lookup(packed, idx)))
    in_cache = np.isin(np.asarray(idx), np.asarray(cache.ids))
    assert int(hits) == int(in_cache.sum())
    # all-resident batch: every lookup hits
    hot = jnp.asarray(np.asarray(cache.ids)[:8])
    _, hits = cached_lookup(packed, cache, hot)
    assert int(hits) == 8


def test_empty_and_oversized_cache():
    st = _store(seed=4)
    packed = pack(st, CFG)
    cache = empty_cache(V, D)
    idx = jnp.arange(10)
    out, hits = cached_lookup(packed, cache, idx)
    assert int(hits) == 0
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ps.lookup(packed, idx)))
    big = build_cache(packed, st.priority, V + 100)  # clamped to vocab
    assert big.capacity == V
    out, hits = cached_lookup(packed, big, idx)
    assert int(hits) == 10
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ps.lookup(packed, idx)))


def test_serve_update_counts_accesses():
    w = jnp.zeros((8,), jnp.float32)
    idx = jnp.asarray([[0, 1], [1, 2]])
    cfg = qs.FQuantConfig().priority
    w2 = serve_update(w, idx, cfg)
    # (1-beta)*0 + beta*(alpha*0 + count)
    expect = np.zeros(8, np.float32)
    expect[[0, 1, 2]] = cfg.beta * np.asarray([1, 2, 1], np.float32)
    np.testing.assert_allclose(np.asarray(w2), expect, rtol=1e-6)


def test_online_server_end_to_end():
    """Cache-first serving + priority fold + periodic re-tier: lookups
    stay bit-identical to the live host packed store, which itself stays
    bit-identical to a full pack of the live QAT store."""
    st = _store(seed=5)
    srv = OnlineServer(st, CFG,
                       OnlineConfig(cache_rows=24, retier_every=3))
    for r in range(9):
        idx = jnp.asarray(drifting_zipf_batch((V,), 32, r, 9, drift=2.0,
                                              seed=9))
        # oracle BEFORE the call: observe() may re-tier the store after
        # serving this batch
        ref = np.asarray(ps.lookup(srv.host_packed, idx))
        rows = srv.lookup(idx)
        np.testing.assert_array_equal(np.asarray(rows), ref)
    assert srv.stats.requests == 9
    assert srv.stats.retiers == 3
    assert srv.stats.lookups == 9 * 32
    assert 0.0 <= srv.stats.hit_rate <= 1.0
    srv.retier()
    np.testing.assert_array_equal(
        np.asarray(ps.unpack(srv.host_packed)),
        np.asarray(ps.unpack(pack(srv.store, CFG))))


def test_drifting_zipf_batch_ranges_and_drift():
    cards = (50, 7, 3000)
    for r in (0, 5, 11):
        b = drifting_zipf_batch(cards, 64, r, 12, drift=3.0, seed=1)
        assert b.shape == (64, 3) and b.dtype == np.int32
        assert (b >= 0).all()
        assert (b < np.asarray(cards)).all()
    # stationary stream keeps the same hot id; drifting moves it
    def head(drift, r):
        b = drifting_zipf_batch(cards, 512, r, 12, drift=drift, seed=2)
        return np.bincount(b[:, 2], minlength=3000).argmax()
    assert head(0.0, 0) == head(0.0, 8)
    assert head(4.0, 8) == (head(4.0, 0) + 32) % 3000


def test_repack_delta_sharded_4way():
    """Under a 4-way mesh: shard -> unshard -> delta repack -> reshard
    serves bit-identically to a fresh full pack's sharded lookup."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.core.tiers import TierConfig
from repro.dist.packed import shard_packed, sharded_lookup, unshard_packed
from repro.serve import OnlineConfig, OnlineServer

V, D = 160, 24
CFG = FQuantConfig(tiers=TierConfig(t8=5.0, t16=50.0), stochastic=False)
rng = np.random.default_rng(0)
st = qs.init(jax.random.PRNGKey(0), V, D, scale=0.05)
st = st._replace(priority=jnp.asarray((rng.pareto(1.2, V) * 20)
                                      .astype(np.float32)))
st = st._replace(table=qs.snap(st.table, qs.current_tiers(st, CFG), CFG))

mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
sp = shard_packed(pack(st, CFG), mesh)

# unshard trims padding back to the packed layout
back = unshard_packed(sp)
np.testing.assert_array_equal(np.asarray(ps.unpack(back)),
                              np.asarray(ps.unpack(pack(st, CFG))))

# perturb priorities, delta repack on host, reshard, serve
st2 = st._replace(priority=jnp.asarray(
    np.asarray(st.priority) * rng.uniform(0.05, 20, V).astype(np.float32)))
delta = ps.repack_delta(back, st2, CFG, np.arange(V))
full = pack(st2, CFG)
np.testing.assert_array_equal(np.asarray(ps.unpack(delta)),
                              np.asarray(ps.unpack(full)))
idx = jnp.asarray(rng.integers(0, V, 96).astype(np.int32))
out = sharded_lookup(shard_packed(delta, mesh), idx, mesh=mesh)
np.testing.assert_array_equal(np.asarray(out),
                              np.asarray(ps.lookup(full, idx)))

# OnlineServer drives the same machinery under the mesh
srv = OnlineServer(st, CFG, OnlineConfig(cache_rows=16, retier_every=2),
                   mesh=mesh)
for r in range(4):
    bidx = jnp.asarray(rng.integers(0, V, (8, 4)).astype(np.int32))
    ref = np.asarray(ps.lookup(srv.host_packed, bidx))
    rows = srv.lookup(bidx)
    np.testing.assert_array_equal(np.asarray(rows), ref)
assert srv.stats.retiers == 2
np.testing.assert_array_equal(
    np.asarray(ps.unpack(unshard_packed(srv.packed))),
    np.asarray(ps.unpack(pack(srv.store, CFG))))
print("ONLINE_SHARDED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "ONLINE_SHARDED_OK" in r.stdout, r.stderr[-2000:]
