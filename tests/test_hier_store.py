"""Hierarchical store: extraction/insertion primitives, budget planner,
cold-shard manifest, and bit-identity of the three-level lookup with a
fully device-resident PackedStore — including after priority-driven
promote/demote migration, at mesh=1 and mesh=4."""

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
from repro.core.tiers import TierConfig, memory_bytes, row_bytes
from repro.store import (
    HOT,
    ColdShards,
    HierConfig,
    build_hier,
    hier_bag_lookup,
    hier_lookup,
    hot_shard_bytes,
    np_lookup,
    plan_placement,
    write_cold_shards,
)

V, D = 160, 24
CFG = FQuantConfig(tiers=TierConfig(t8=5.0, t16=50.0), stochastic=False)


def _store(seed=0):
    rng = np.random.default_rng(seed)
    st = qs.init(jax.random.PRNGKey(seed), V, D, scale=0.05)
    pri = jnp.asarray((rng.pareto(1.2, V) * 20).astype(np.float32))
    st = st._replace(priority=pri)
    return st._replace(table=qs.snap(
        st.table, qs.current_tiers(st, CFG), CFG))


def _hier(st, tmp_path, frac=8, mesh=None, seed_dir="cold"):
    packed = pack(st, CFG)
    b = packed.nbytes() // frac
    cfg = HierConfig(hbm_budget_bytes=b, host_budget_bytes=b,
                     rows_per_shard=16,
                     store_dir=str(tmp_path / seed_dir))
    return build_hier(st, CFG, cfg, mesh=mesh), packed


# ------------------------------------------------------- primitives

def test_nbytes_by_tier_breakdown():
    st = _store(0)
    packed = pack(st, CFG)
    per = packed.nbytes(by_tier=True)
    assert set(per) == {"int8", "half", "fp32", "indirect"}
    assert sum(per.values()) == packed.nbytes()
    v8 = packed.payload8.shape[0]
    v16 = packed.payload16.shape[0]
    assert per["int8"] == v8 * D + v8 * 4
    assert per["half"] == v16 * 2 * D + v16 * 4
    assert per["fp32"] == packed.payload32.shape[0] * 4 * D
    assert per["indirect"] == V * 4


def test_row_bytes_sums_to_memory_bytes():
    tiers = np.array([0, 0, 1, 2, 1, 0], np.int8)
    assert int(row_bytes(tiers, D).sum()) == memory_bytes(
        jnp.asarray(tiers), D)


def test_extract_rows_bit_identical():
    st = _store(1)
    packed = pack(st, CFG)
    rng = np.random.default_rng(3)
    rows = rng.permutation(V)[:40]
    sub = ps.extract_rows(packed, rows)
    np.testing.assert_array_equal(
        np.asarray(ps.lookup(sub, jnp.arange(rows.size))),
        np.asarray(ps.lookup(packed, jnp.asarray(rows))))


def test_concat_stores_bit_identical_and_rebased():
    st = _store(2)
    packed = pack(st, CFG)
    a_rows = np.arange(0, 30)
    b_rows = np.arange(90, 150)          # disjoint, different tier mix
    merged = ps.concat_stores(ps.extract_rows(packed, a_rows),
                              ps.extract_rows(packed, b_rows))
    both = np.concatenate([a_rows, b_rows])
    assert merged.vocab == both.size
    np.testing.assert_array_equal(
        np.asarray(ps.lookup(merged, jnp.arange(both.size))),
        np.asarray(ps.lookup(packed, jnp.asarray(both))))
    # placeholders of empty tiers don't leak into the concat
    only32 = np.nonzero(ps.packed_tiers(packed) == 2)[0]
    m2 = ps.concat_stores(ps.extract_rows(packed, only32[:2]),
                          ps.extract_rows(packed, only32[2:4]))
    assert ps.live_counts(m2).tolist() == [0, 0, 4]
    np.testing.assert_array_equal(
        np.asarray(ps.lookup(m2, jnp.arange(4))),
        np.asarray(ps.lookup(packed, jnp.asarray(only32[:4]))))


# ---------------------------------------------------------- planner

def test_plan_placement_prefix_and_budgets():
    st = _store(3)
    pri = np.asarray(st.priority)
    tiers = ps.packed_tiers(pack(st, CFG))
    total = int(row_bytes(tiers, D).sum())
    small = plan_placement(pri, tiers, D, total // 10, total // 10)
    big = plan_placement(pri, tiers, D, total // 3, total // 10)
    # a bigger budget's hot set strictly contains the smaller one's
    assert set(small.hot_ids) <= set(big.hot_ids)
    assert small.hot_bytes <= total // 10
    # every row is placed exactly once
    for plan in (small, big):
        assert (np.sort(np.concatenate(
            [plan.hot_ids, plan.warm_ids, plan.cold_ids]))
            == np.arange(V)).all()
    # priority ordering: min hot priority >= max warm priority
    assert pri[small.hot_ids].min() >= pri[small.warm_ids].max() - 1e-6
    # unbounded host budget -> no cold
    nocold = plan_placement(pri, tiers, D, total // 10, None)
    assert nocold.cold_ids.size == 0


def test_hot_shard_bytes_matches_dist_accounting():
    """Planner byte math == measured per-shard bytes of the built
    store — including the placeholder rows of empty tiers, which are
    physically allocated and must be charged against the budget."""
    from repro.dist.packed import shard_nbytes

    st = _store(4)
    packed = pack(st, CFG)
    tiers = ps.packed_tiers(packed)
    all_three = np.concatenate([np.nonzero(tiers == t)[0][:6]
                                for t in range(3)])
    assert all_three.size == 18
    only_fp32 = np.nonzero(tiers == 2)[0][:5]   # int8/half tiers empty
    for ids in (all_three, only_fp32):
        hot = ps.extract_rows(packed, ids)
        for n in (1, 2, 4):
            planned = hot_shard_bytes(tiers[ids], D, ids.size, n)
            built = shard_nbytes(
                ps.PackedStore(*(jnp.asarray(leaf) for leaf in hot)), n)
            assert planned == built, (n, planned, built)


# --------------------------------------------------------- manifest

def test_cold_shards_roundtrip_and_mmap(tmp_path):
    st = _store(5)
    packed = pack(st, CFG)
    ids = np.arange(16, 120)
    sub = ps.extract_rows(packed, ids)
    man = write_cold_shards(str(tmp_path / "c"), sub, ids,
                            rows_per_shard=16)
    assert man["schema"] == "hier_store/v1"
    cold = ColdShards(str(tmp_path / "c"))
    assert cold.rows == ids.size and cold.num_shards == 7
    np.testing.assert_array_equal(cold.row_ids, ids)
    # mmap'd dequant == device dequant, bit for bit, any order
    probe = np.random.default_rng(0).permutation(ids.size)[:50]
    np.testing.assert_array_equal(
        cold.gather_fp32(probe),
        np.asarray(ps.lookup(packed, jnp.asarray(ids[probe]))))
    # quantized extraction preserves bytes across shard boundaries
    ext = cold.extract(probe)
    np.testing.assert_array_equal(
        np.asarray(ps.lookup(ext, jnp.arange(probe.size))),
        np.asarray(ps.lookup(packed, jnp.asarray(ids[probe]))))


def test_np_lookup_bit_identical_to_device():
    st = _store(6)
    packed = pack(st, CFG)
    host = ps.PackedStore(*(np.asarray(leaf) for leaf in
                            jax.device_get(packed)))
    idx = np.random.default_rng(1).integers(0, V, 64)
    np.testing.assert_array_equal(
        np_lookup(host, idx),
        np.asarray(ps.lookup(packed, jnp.asarray(idx))))


# ------------------------------------------------- hierarchy oracle

def test_hier_lookup_bit_identical(tmp_path):
    st = _store(7)
    hier, packed = _hier(st, tmp_path)
    assert hier.cold_ids.size > 0          # the spill path is real
    rng = np.random.default_rng(2)
    idx = jnp.asarray(rng.integers(0, V, (9, 7)).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(hier_lookup(hier, idx)),
        np.asarray(ps.lookup(packed, idx)))
    # whole vocab, including every cold row
    np.testing.assert_array_equal(
        np.asarray(hier_lookup(hier, jnp.arange(V))),
        np.asarray(ps.lookup(packed, jnp.arange(V))))
    # host-side gather agrees too (cache-build path)
    np.testing.assert_array_equal(
        hier.gather_fp32_host(np.arange(V)),
        np.asarray(ps.lookup(packed, jnp.arange(V))))


def test_hier_bag_lookup_bit_identical(tmp_path):
    st = _store(8)
    hier, packed = _hier(st, tmp_path)
    rng = np.random.default_rng(4)
    idx = jnp.asarray(rng.integers(0, V, 40).astype(np.int32))
    seg = jnp.asarray(np.sort(rng.integers(0, 7, 40)).astype(np.int32))
    w = jnp.asarray(rng.standard_normal(40).astype(np.float32))
    for weights in (None, w):
        np.testing.assert_array_equal(
            np.asarray(hier_bag_lookup(hier, idx, seg, 7,
                                       weights=weights)),
            np.asarray(ps.bag_lookup(packed, idx, seg, 7,
                                     weights=weights)))


def test_migrate_promotes_demotes_and_stays_bit_identical(tmp_path):
    st = _store(9)
    hier, _ = _hier(st, tmp_path)
    promoted_ids = hier.cold_ids[:5].copy()
    old_hot = hier.hot_ids.copy()

    pri2 = np.asarray(st.priority).copy()
    pri2[promoted_ids] = pri2.max() * 10    # hammer five cold rows
    st2 = st._replace(priority=jnp.asarray(pri2))
    moved = hier.migrate(st2, CFG)
    assert moved["promoted"] >= 5
    assert (hier.level[promoted_ids] == HOT).all()
    # something had to leave the budget-bound hot set
    assert moved["demoted"] > 0
    assert not set(old_hot) <= set(hier.hot_ids)
    # bit-identity vs a fresh full pack of the updated store (the
    # repack_delta contract, now across levels)
    packed2 = pack(st2, CFG)
    np.testing.assert_array_equal(
        np.asarray(hier_lookup(hier, jnp.arange(V))),
        np.asarray(ps.lookup(packed2, jnp.arange(V))))
    # a second migration with no priority change is a no-op placement
    before = (hier.hot_ids.copy(), hier.warm_ids.copy(),
              hier.cold_ids.copy())
    hier.migrate(st2, CFG)
    for a, b in zip(before, (hier.hot_ids, hier.warm_ids,
                             hier.cold_ids)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        np.asarray(hier_lookup(hier, jnp.arange(V))),
        np.asarray(ps.lookup(packed2, jnp.arange(V))))


def test_migrate_places_hot_as_shadow_stages_it(tmp_path):
    """A synchronous migrate places the hot level as a shadow migration
    stages it (``place_packed``): one pytree structure, so a forward
    warmed on the staged form serves ``hot_dev`` without a compile."""
    from repro import obs
    from repro.dist.packed import place_packed
    from repro.kernels.rows import LaneDense
    st = _store(9)
    hier, _ = _hier(st, tmp_path)
    pri2 = np.asarray(st.priority).copy()
    pri2[hier.cold_ids[:5]] = pri2.max() * 10
    hier.migrate(st._replace(priority=jnp.asarray(pri2)), CFG)
    staged = place_packed(hier.hot_host)
    assert jax.tree.structure(hier.hot_dev) == jax.tree.structure(staged)
    assert isinstance(hier.hot_dev.payload8, LaneDense)
    idx = jnp.arange(hier.hot_ids.size, dtype=jnp.int32)
    fwd = jax.jit(ps.lookup)
    jax.block_until_ready(fwd(staged, idx))
    before = obs.span_log().compiles["jax.compile"][0]
    out = fwd(hier.hot_dev, idx)
    jax.block_until_ready(out)
    assert obs.span_log().compiles["jax.compile"][0] == before
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ps.lookup(hier.hot_host, idx)))


def test_build_requires_store_dir_for_cold():
    st = _store(10)
    b = pack(st, CFG).nbytes() // 8
    with pytest.raises(ValueError, match="store_dir"):
        build_hier(st, CFG, HierConfig(hbm_budget_bytes=b,
                                       host_budget_bytes=b))


def test_hier_stage_counts_and_dedup(tmp_path):
    st = _store(11)
    hier, _ = _hier(st, tmp_path)
    warm_id = int(hier.warm_ids[0])
    cold_id = int(hier.cold_ids[0])
    hot_id = int(hier.hot_ids[0])
    g = np.array([[hot_id, warm_id], [cold_id, warm_id]], np.int64)
    sb = hier.stage(g)
    assert sb.warm_hits == 2 and sb.cold_hits == 1
    assert sb.staged == 2                  # warm_id deduplicated
    ss = np.asarray(sb.stage_slot)
    assert ss[0, 0] == -1                  # hot position not staged
    assert ss[0, 1] == ss[1, 1]            # same staging slot
    # valid mask drops padding from the accounting only
    sb2 = hier.stage(g, valid=np.array([[True], [False]]))
    assert sb2.warm_hits == 1 and sb2.cold_hits == 0
    # skip mask (cache hits) removes rows from staging entirely
    sb3 = hier.stage(g, skip=(g == warm_id))
    assert sb3.staged == 1 and sb3.warm_hits == 0 and sb3.cold_hits == 1


# ------------------------------------------------------- fault paths

def test_stage_empty_batch_and_all_hot_miss_free(tmp_path):
    """Staging-buffer corner cases: an EMPTY index batch and an all-hot
    batch both stage zero rows, leave every hit counter untouched, and
    the (placeholder) staging buffer never leaks into results."""
    st = _store(12)
    hier, packed = _hier(st, tmp_path)
    base = dict(hier.stats.as_dict())

    sb = hier.stage(np.zeros((0,), np.int64))
    assert sb.staged == 0 and sb.warm_hits == 0 and sb.cold_hits == 0
    assert sb.staging.shape[0] >= 1          # fixed non-empty buffer
    out = hier_lookup(hier, np.zeros((0,), np.int64))
    assert out.shape == (0, D)

    hot_batch = hier.hot_ids[:8]
    sb = hier.stage(hot_batch)
    assert sb.staged == 0 and sb.warm_hits == 0 and sb.cold_hits == 0
    assert (np.asarray(sb.stage_slot) == -1).all()
    np.testing.assert_array_equal(
        np.asarray(hier_lookup(hier, jnp.asarray(hot_batch))),
        np.asarray(ps.lookup(packed, jnp.asarray(hot_batch))))
    # an all-skip batch (every position a cache hit) stages nothing and
    # counts nothing, even though the rows are warm/cold misses
    mixed = np.array([int(hier.warm_ids[0]), int(hier.cold_ids[0])])
    sb = hier.stage(mixed, skip=np.ones(2, bool))
    assert sb.staged == 0 and sb.warm_hits == 0 and sb.cold_hits == 0
    after = hier.stats.as_dict()
    assert after["warm_hits"] == base["warm_hits"]
    assert after["cold_hits"] == base["cold_hits"]
    assert after["staged_rows"] == base["staged_rows"]


def test_bag_lookup_empty_bag_zero_not_stale(tmp_path):
    """A bag no index maps to must come back exactly zero — not a row
    from the shared staging buffer — and match the flat-store result."""
    st = _store(13)
    hier, packed = _hier(st, tmp_path)
    idx = np.concatenate([hier.cold_ids[:4], hier.warm_ids[:4]])
    seg = np.array([0, 0, 2, 2, 3, 3, 5, 5], np.int32)   # bags 1, 4 empty
    out = np.asarray(hier_bag_lookup(hier, jnp.asarray(idx),
                                     jnp.asarray(seg), 6))
    np.testing.assert_array_equal(
        out, np.asarray(ps.bag_lookup(packed, jnp.asarray(idx),
                                      jnp.asarray(seg), 6)))
    assert (out[1] == 0).all() and (out[4] == 0).all()


def test_promote_then_demote_same_row_counts_once_each(tmp_path):
    """One row rides a full promote+demote round trip inside one retier
    cadence (two migrations before any serving): each leg counts the
    row EXACTLY once in promoted/demoted, the staging/miss counters
    never move (migration is not a lookup), and the row's quantized
    bytes land back bit-identical."""
    st = _store(14)
    hier, packed = _hier(st, tmp_path)
    row = int(hier.cold_ids[0])
    before = np.asarray(ps.lookup(packed, jnp.asarray([row])))
    stage_base = {k: v for k, v in hier.stats.as_dict().items()
                  if k in ("staged_rows", "warm_hits", "cold_hits")}

    pri = np.asarray(st.priority).copy()
    pri2 = pri.copy()
    pri2[row] = pri.max() * 10              # cold -> hot AND tier cross
    moved_up = hier.migrate(st._replace(priority=jnp.asarray(pri2)), CFG)
    assert hier.level[row] == HOT
    assert moved_up["promoted"] >= 1
    p_after_up, d_after_up = hier.stats.promoted, hier.stats.demoted

    moved_dn = hier.migrate(st._replace(priority=jnp.asarray(pri)), CFG)
    assert hier.level[row] != HOT
    assert moved_dn["demoted"] >= 1
    # each migration's deltas equal its return — nothing double-counted
    assert hier.stats.promoted == p_after_up + moved_dn["promoted"]
    assert hier.stats.demoted == d_after_up + moved_dn["demoted"]
    for k, v in stage_base.items():
        assert hier.stats.as_dict()[k] == v, k
    # priorities restored -> same tiers -> byte-identical round trip
    np.testing.assert_array_equal(
        np.asarray(hier_lookup(hier, jnp.asarray([row]))), before)
    np.testing.assert_array_equal(
        np.asarray(hier_lookup(hier, jnp.arange(V))),
        np.asarray(ps.lookup(packed, jnp.arange(V))))


def test_manifest_reload_mid_migration(tmp_path):
    """Re-opening the cold manifest while a NEW generation is half
    written must see only the live generation: the unpublished shards
    live in a hidden tmp dir, abort removes them without a trace, and a
    reload after publish sees exactly the new row set while already
    open mmaps keep serving the old one."""
    import glob as _glob

    from repro.store.manifest import ShardWriter

    st = _store(15)
    hier, packed = _hier(st, tmp_path)
    store_dir = hier.cfg.store_dir
    live_ids = hier.cold_ids.copy()

    # plan a migration that reshuffles the cold set (priority reversal)
    st2 = st._replace(priority=jnp.asarray(
        np.asarray(st.priority)[::-1].copy()))
    rp = hier.plan_retier(st2, CFG)
    assert hier.cold_changed(rp)
    new_ids = rp.plan.cold_ids
    writer = ShardWriter(store_dir, hier.build_rows(new_ids, rp, CFG),
                         new_ids, rows_per_shard=16)
    writer.write_next()                      # mid-migration: 1+ shards
    assert _glob.glob(os.path.join(str(tmp_path), "**", ".tmp_hier_*"),
                      recursive=True)

    reload_mid = ColdShards(store_dir)       # manifest reload NOW
    np.testing.assert_array_equal(reload_mid.row_ids, live_ids)
    probe = np.arange(live_ids.size)
    np.testing.assert_array_equal(
        reload_mid.gather_fp32(probe),
        np.asarray(ps.lookup(packed, jnp.asarray(live_ids))))

    writer.abort()                           # crash-before-swap leg
    assert not _glob.glob(os.path.join(str(tmp_path), "**",
                                       ".tmp_hier_*"), recursive=True)
    np.testing.assert_array_equal(ColdShards(store_dir).row_ids,
                                  live_ids)

    # second writer runs to publish: reload sees the NEW generation...
    w2 = ShardWriter(store_dir, hier.build_rows(new_ids, rp, CFG),
                     new_ids, rows_per_shard=16)
    w2.publish()
    w2.abort()                               # idempotent after publish
    reload_new = ColdShards(store_dir)
    np.testing.assert_array_equal(reload_new.row_ids, new_ids)
    np.testing.assert_array_equal(
        reload_new.gather_fp32(np.arange(new_ids.size)),
        np.asarray(ps.lookup(pack(st2, CFG), jnp.asarray(new_ids))))
    # ...while the PREVIOUS generation's open mmaps stay valid
    np.testing.assert_array_equal(
        reload_mid.gather_fp32(probe),
        np.asarray(ps.lookup(packed, jnp.asarray(live_ids))))


def test_hier_mesh4_oracle_subprocess(tmp_path):
    """Three-level lookup on a 4-way mesh == single-device flat pack,
    bit for bit, before and after a promote/demote migration."""
    code = """
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.core.tiers import TierConfig
from repro.store import HierConfig, build_hier, hier_lookup

V, D = 160, 32
CFG = FQuantConfig(tiers=TierConfig(t8=5.0, t16=50.0), stochastic=False)
rng = np.random.default_rng(1)
st = qs.init(jax.random.PRNGKey(1), V, D, scale=0.05)
st = st._replace(priority=jnp.asarray((rng.pareto(1.2, V) * 20)
                                      .astype(np.float32)))
st = st._replace(table=qs.snap(st.table, qs.current_tiers(st, CFG), CFG))
packed = pack(st, CFG)
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
b = packed.nbytes() // 16
hier = build_hier(st, CFG, HierConfig(
    hbm_budget_bytes=b, host_budget_bytes=b, rows_per_shard=16,
    store_dir=os.path.join(tempfile.mkdtemp(), "cold")), mesh=mesh)
assert hier.cold_ids.size > 0
idx = jnp.asarray(rng.integers(0, V, (9, 5)).astype(np.int32))
np.testing.assert_array_equal(np.asarray(hier_lookup(hier, idx)),
                              np.asarray(ps.lookup(packed, idx)))
pri2 = np.asarray(st.priority).copy()
pri2[hier.cold_ids[:4]] = 1e6
st2 = st._replace(priority=jnp.asarray(pri2))
moved = hier.migrate(st2, CFG)
assert moved["promoted"] >= 4
np.testing.assert_array_equal(
    np.asarray(hier_lookup(hier, jnp.arange(V))),
    np.asarray(ps.lookup(pack(st2, CFG), jnp.arange(V))))
print("SHARDED_HIER_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "SHARDED_HIER_OK" in r.stdout, r.stderr[-2000:]
