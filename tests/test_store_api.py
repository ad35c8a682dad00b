"""EmbeddingStore protocol conformance over all three backends
(packed / hier / hashed): identity + lookup oracles, empty bags, K=1
bags, nbytes accounting, metrics-on/off serving bit-identity, ckpt
manifest round-trips — plus the hashed custom_vjp gradcheck against a
dense-materialized autodiff oracle at mesh=1 and mesh=4."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.core.tiers import TierConfig
from repro.ckpt.manager import CheckpointManager
from repro.serve import OnlineConfig, OnlineServer
from repro.store import (
    EmbeddingStore,
    HashedConfig,
    HierConfig,
    backend_names,
    build,
    fit_pool_from_table,
    from_manifest,
    register_backend,
)

V, D = 160, 24
CFG = FQuantConfig(tiers=TierConfig(t8=5.0, t16=50.0), stochastic=False)
HCFG = HashedConfig(vocab=V, dim=D, chunk_dim=8, num_slots=256,
                    num_hashes=2, seed=5)
BACKENDS = ("packed", "hier", "hashed")


def _qat(seed=0):
    rng = np.random.default_rng(seed)
    st = qs.init(jax.random.PRNGKey(seed), V, D, scale=0.05)
    pri = jnp.asarray((rng.pareto(1.2, V) * 20).astype(np.float32))
    st = st._replace(priority=pri)
    return st._replace(table=qs.snap(
        st.table, qs.current_tiers(st, CFG), CFG))


def _hier_cfg(tmp_path, st):
    b = pack(st, CFG).nbytes() // 4
    return HierConfig(hbm_budget_bytes=b, host_budget_bytes=b,
                      rows_per_shard=16,
                      store_dir=str(tmp_path / "cold"))


def _backend(kind, tmp_path, seed=0):
    st = _qat(seed)
    if kind == "packed":
        return build("packed", st, CFG)
    if kind == "hier":
        return build("hier", st, CFG, _hier_cfg(tmp_path, st))
    hs = fit_pool_from_table(st.table, HCFG, priority=st.priority)
    return build("hashed", hs, HCFG)


def _oracle_rows(be, idx):
    """Per-backend fp32 ground truth for ``lookup(idx)``."""
    flat = np.asarray(idx, np.int64).reshape(-1)
    return be.gather_fp32_host(flat).reshape(*np.shape(idx), D)


# ---------------------------------------------------------- protocol

@pytest.mark.parametrize("kind", BACKENDS)
def test_protocol_conformance(kind, tmp_path):
    be = _backend(kind, tmp_path)
    assert isinstance(be, EmbeddingStore)
    assert be.kind == kind
    assert be.vocab == V and be.dim == D
    assert be.nbytes() > 0
    counts = be.live_counts()
    assert counts and all(isinstance(n, int) for n in counts.values())
    assert np.asarray(be.priority).shape == (V,)


def test_registry_build_and_register():
    assert set(BACKENDS) <= set(backend_names())
    with pytest.raises(ValueError, match="unknown store backend"):
        build("no_such_backend")
    with pytest.raises(ValueError, match="no backend registered"):
        from_manifest({"kind": "mystery/v9"})
    register_backend("_test_dummy", lambda: "built")
    try:
        assert build("_test_dummy") == "built"
    finally:
        from repro.store import api as api_mod
        api_mod._BACKENDS.pop("_test_dummy")


# ------------------------------------------------------------ lookups

@pytest.mark.parametrize("kind", BACKENDS)
def test_lookup_matches_oracle(kind, tmp_path):
    be = _backend(kind, tmp_path)
    rng = np.random.default_rng(11)
    for shape in ((7,), (3, 5)):
        idx = jnp.asarray(rng.integers(0, V, shape), jnp.int32)
        got = np.asarray(be.lookup(idx))
        assert got.shape == shape + (D,)
        np.testing.assert_array_equal(got, _oracle_rows(be, idx))


@pytest.mark.parametrize("kind", BACKENDS)
def test_k1_bag_equals_lookup(kind, tmp_path):
    """A K=1 bag with unit weight IS the row lookup, bit for bit."""
    be = _backend(kind, tmp_path)
    idx = jnp.asarray(np.random.default_rng(2).integers(0, V, (9,)),
                      jnp.int32)
    bag = np.asarray(be.bag_lookup(idx[:, None]))
    np.testing.assert_array_equal(bag, np.asarray(be.lookup(idx)))


@pytest.mark.parametrize("kind", BACKENDS)
def test_empty_bags_are_exact_zero(kind, tmp_path):
    """Zero-weight bags contribute exactly 0.0 — the kernel-skip
    contract (no DMA issued, no accumulation, not even -0.0)."""
    be = _backend(kind, tmp_path)
    rng = np.random.default_rng(3)
    idx = jnp.asarray(rng.integers(0, V, (6, 4)), jnp.int32)
    w = np.ones((6, 4), np.float32)
    w[2] = 0.0          # one fully empty bag
    w[4, 1:] = 0.0      # one bag with a single live slot
    out = np.asarray(be.bag_lookup(idx, jnp.asarray(w)))
    assert np.all(out[2] == 0.0)
    np.testing.assert_array_equal(
        out[4], np.asarray(be.lookup(idx[4, 0])))


# ------------------------------------------------------------- nbytes

def test_nbytes_accounting(tmp_path):
    st = _qat(0)
    pk = build("packed", st, CFG)
    assert pk.nbytes() == pk.host_packed.nbytes()
    hr = build("hier", st, CFG, _hier_cfg(tmp_path, st))
    assert hr.nbytes() == sum(hr.hier.nbytes().values())
    hs = fit_pool_from_table(st.table, HCFG, priority=st.priority)
    hb = build("hashed", hs, HCFG)
    assert hb.nbytes() == HCFG.pool_nbytes() \
        == HCFG.num_slots * HCFG.chunk_dim * 4
    # the hashed bound is independent of cardinality: a 4x vocab pool
    # of the same slot count costs the same bytes
    big = HCFG._replace(vocab=4 * V)
    hs_big = fit_pool_from_table(
        jnp.zeros((4 * V, D), jnp.float32), big, cg_iters=0)
    assert build("hashed", hs_big, big).nbytes() == hb.nbytes()


# ------------------------------------- serving: metrics on/off parity

@pytest.mark.parametrize("kind", BACKENDS)
def test_serve_bit_identical_with_metrics_on(kind, tmp_path):
    """The obs plane must be observational: serving the same stream
    with the metrics registry enabled returns bit-identical rows and
    identical counters."""
    rng = np.random.default_rng(7)
    stream = [jnp.asarray(rng.integers(0, V, (5, 3)), jnp.int32)
              for _ in range(4)]

    def run():
        srv = OnlineServer(backend=_backend(kind, tmp_path),
                           online=OnlineConfig(cache_rows=16,
                                               retier_every=2))
        outs = [np.asarray(srv.lookup(ix)) for ix in stream]
        stats = {k: v for k, v in srv.stats.as_dict().items()
                 if "seconds" not in k}
        return outs, stats

    obs.disable()
    base_rows, base_stats = run()
    obs.enable()
    try:
        on_rows, on_stats = run()
    finally:
        obs.disable()
    for a, b in zip(base_rows, on_rows):
        np.testing.assert_array_equal(a, b)
    assert base_stats == on_stats


# ----------------------------------------------------- ckpt manifests

@pytest.mark.parametrize("kind", BACKENDS)
def test_ckpt_manifest_roundtrip(kind, tmp_path):
    """snapshot_manifest -> CheckpointManager -> from_manifest rebuilds
    a backend whose lookups are bit-identical — dispatched on the
    manifest's own kind tag, no caller-side branching."""
    be = _backend(kind, tmp_path)
    manifest = be.snapshot_manifest()
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=1)
    mgr.save(1, manifest)
    tree, step = mgr.restore(manifest)
    assert step == 1
    kwargs = {}
    if kind == "packed":
        kwargs = dict(cfg=CFG)
    elif kind == "hier":
        kwargs = dict(store=_qat(0), cfg=CFG,
                      hier_cfg=_hier_cfg(tmp_path, _qat(0)))
    rb = from_manifest(tree, **kwargs)
    assert rb.kind == kind
    idx = jnp.asarray(np.random.default_rng(5).integers(0, V, (11,)),
                      jnp.int32)
    np.testing.assert_array_equal(np.asarray(rb.lookup(idx)),
                                  np.asarray(be.lookup(idx)))
    np.testing.assert_array_equal(np.asarray(rb.priority),
                                  np.asarray(be.priority))
    assert rb.nbytes() == be.nbytes()


# --------------------------------------------- hashed gradcheck (vjp)

def _dense_materialize(pool, hcfg):
    """Autodiff oracle: materialize the whole virtual table from the
    pool with plain jnp ops (same hash family as the kernel)."""
    from repro.kernels.hashed_gather.ref import hash_slots
    ids = jnp.arange(hcfg.vocab, dtype=jnp.int32)
    slots, signs = hash_slots(ids, num_chunks=hcfg.num_chunks,
                              num_hashes=hcfg.num_hashes,
                              num_slots=hcfg.num_slots, seed=hcfg.seed)
    chunks = jnp.take(pool, slots, axis=0)        # (V, C, NH, Z)
    return (chunks * signs[..., None]).sum(-2).reshape(
        hcfg.vocab, hcfg.dim)


def test_hashed_gradcheck_vs_dense_oracle_mesh1():
    from repro.kernels.hashed_gather.autodiff import hashed_lookup_train
    rng = np.random.default_rng(9)
    pool = jnp.asarray(rng.standard_normal(
        (HCFG.num_slots, HCFG.chunk_dim)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, V, (6, 4)), jnp.int32)
    cot = jnp.asarray(rng.standard_normal((6, 4, D)).astype(np.float32))

    def f_kernel(p):
        return (hashed_lookup_train(
            p, idx, num_chunks=HCFG.num_chunks,
            num_hashes=HCFG.num_hashes, seed=HCFG.seed,
            use_pallas=False) * cot).sum()

    def f_oracle(p):
        return (jnp.take(_dense_materialize(p, HCFG), idx, axis=0)
                * cot).sum()

    g_k = jax.grad(f_kernel)(pool)
    g_o = jax.grad(f_oracle)(pool)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_o),
                               rtol=1e-6, atol=1e-6)


def test_hashed_gradcheck_mesh4_subprocess():
    """Row-sharded hashed training gather on a 4-way mesh: forward
    replicated psum == dense oracle, backward scatter == dense oracle
    grad (each shard owns its pool rows; no gradient collective)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.dist.hashed import sharded_hashed_lookup_train
from repro.kernels.hashed_gather.ref import hash_slots

V, D, Z, S, NH, SEED = 160, 24, 8, 256, 2, 5
C = D // Z
rng = np.random.default_rng(9)
pool = jnp.asarray(rng.standard_normal((S, Z)).astype(np.float32))
idx = jnp.asarray(rng.integers(0, V, (6, 4)), jnp.int32)
cot = jnp.asarray(rng.standard_normal((6, 4, D)).astype(np.float32))
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))

def dense(p):
    ids = jnp.arange(V, dtype=jnp.int32)
    slots, signs = hash_slots(ids, num_chunks=C, num_hashes=NH,
                              num_slots=S, seed=SEED)
    chunks = jnp.take(p, slots, axis=0)
    return (chunks * signs[..., None]).sum(-2).reshape(V, D)

def f_sharded(p):
    out = sharded_hashed_lookup_train(
        p, idx, num_chunks=C, num_hashes=NH, num_slots=S, seed=SEED,
        mesh=mesh, axis="model", use_pallas=False)
    return (out * cot).sum()

def f_oracle(p):
    return (jnp.take(dense(p), idx, axis=0) * cot).sum()

v_s, g_s = jax.value_and_grad(f_sharded)(pool)
v_o, g_o = jax.value_and_grad(f_oracle)(pool)
np.testing.assert_allclose(float(v_s), float(v_o), rtol=1e-5)
np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_o),
                           rtol=1e-5, atol=1e-5)
print("MESH4_GRADCHECK_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src") + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH4_GRADCHECK_OK" in out.stdout


# --------------------------------------- hashed x rowwise (combined)

def test_hashed_int8_combined_mode_roundtrip(tmp_path):
    """quantize_pool composes: the int8 pool serves through the same
    kernel (per-slot dequant) and the backend surface is unchanged."""
    from repro.store import quantize_pool
    st = _qat(0)
    hs = fit_pool_from_table(st.table, HCFG, priority=st.priority)
    q = quantize_pool(hs)
    assert q.pool.dtype == jnp.int8
    be = build("hashed", q, HCFG)
    assert be.nbytes() == HCFG.num_slots * (HCFG.chunk_dim + 4)
    idx = jnp.asarray(np.arange(V, dtype=np.int32))
    got = np.asarray(be.lookup(idx))
    np.testing.assert_array_equal(got, _oracle_rows(be, idx))
    # int8 pool costs ~2.7x less than the fp32 pool at Z=8
    assert be.nbytes() < HCFG.pool_nbytes() / 2
