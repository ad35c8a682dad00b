"""Interleaving stress harness for shadow-store re-tiering.

The shadow swap's contract (src/repro/serve/shadow.py): however serve
steps, priority folds, chunked shadow builds, staging and swaps
interleave, every lookup is bit-identical to a **lockstep synchronous
oracle** — a full ``pack`` at the fold state of the LAST swap's
snapshot.  A deterministic scheduler executes hypothesis-generated op
schedules against an ``OnlineServer`` and checks that oracle after
every single op, plus the per-chunk-boundary invariant
(``ShadowRepack.materialize() == repack_delta(live, snapshot, cfg,
movers[:pos])``) at every chunk.

Named schedules cover the corners: swap-during-drift (the swap lands
the SNAPSHOT fold state, not the drifted live one), double-swap,
crash-before-swap (shadow discarded, live store untouched — including
the hier cold generation's unpublished tmp dir).  The same harness
runs at mesh=1 in-process and mesh=4 in a subprocess (the XLA host
device count must be fixed before jax initialises).
"""

import glob
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.core.tiers import TierConfig, tier_crossings
from repro.serve import OnlineConfig, OnlineServer
from repro.store.hier import hier_lookup

V, D = 160, 24
CFG = FQuantConfig(tiers=TierConfig(t8=5.0, t16=50.0), stochastic=False)

# op weights for generated schedules: mostly traffic, with enough
# begin/chunk/tick to keep a build in flight and the rare drain/discard
OPS = ("serve", "serve", "serve", "fold", "fold", "begin", "chunk",
       "chunk", "tick", "drain", "discard")


def _store(seed=0, scale_pri=20.0):
    rng = np.random.default_rng(seed)
    st_ = qs.init(jax.random.PRNGKey(seed), V, D, scale=0.05)
    pri = jnp.asarray((rng.pareto(1.2, V) * scale_pri)
                      .astype(np.float32))
    st_ = st_._replace(priority=pri)
    return st_._replace(table=qs.snap(
        st_.table, qs.current_tiers(st_, CFG), CFG))


def _flat_server(seed=0, mesh=None, retier_every=0):
    return OnlineServer(
        _store(seed), CFG,
        OnlineConfig(cache_rows=24, retier_every=retier_every,
                     retier_async=True, shadow_rows_per_step=16,
                     verify_swap=True),
        mesh=mesh)


def _mirror(server):
    """The synchronous oracle the live store must match right now."""
    return np.asarray(ps.unpack(server.host_packed))


def _record_swaps(server) -> list:
    """Snapshots of the shadows ``server`` swaps in, in order.  A
    re-tier queued while a shadow is in flight opens a second shadow
    that can be built and swapped inside one later op (``drain``), so
    the oracle reads the snapshot each swap committed rather than the
    last one the scheduler saw between ops."""
    snaps = []
    swap = server._swap

    def _swap():
        snap = server.shadow.snapshot
        out = swap()
        snaps.append(snap)
        return out
    server._swap = _swap
    return snaps


def run_flat_schedule(server, ops, rng):
    """Execute one op schedule, asserting the lockstep oracle after
    every op.  ``mirror`` is the unpacked synchronous pack at the last
    swap's snapshot fold state; a swap may land inside ANY op (the
    staging thread finishing is scheduler-invisible), so the swap
    counter is re-checked after each one."""
    mirror = _mirror(server)
    np.testing.assert_array_equal(
        mirror, np.asarray(ps.unpack(pack(server.store, CFG))))
    snaps = _record_swaps(server)
    swaps = 0
    for op in ops:
        pre_swaps = server.stats.swaps
        if op == "serve":
            idx = rng.integers(0, V, (8,)).astype(np.int32)
            rows = np.asarray(server.lookup(jnp.asarray(idx)))
            np.testing.assert_array_equal(rows, mirror[idx])
        elif op == "fold":
            idx = rng.integers(0, V, (16,)).astype(np.int32)
            server.observe(jnp.asarray(idx), count=4)
        elif op == "begin":
            server.begin_retier()
        elif op == "chunk":
            sh = server.shadow
            if sh is not None and not sh.staged:
                sh.step(int(rng.integers(1, 48)))
                got = np.asarray(ps.unpack(sh.materialize()))
                ref = np.asarray(ps.unpack(ps.repack_delta(
                    server.host_packed, sh.snapshot, CFG,
                    sh.movers[:sh.pos])))
                np.testing.assert_array_equal(got, ref)
        elif op == "tick":
            server._shadow_tick(1)
        elif op == "drain":
            server.drain_shadow()
        elif op == "discard":
            server.discard_shadow()
            # crash-before-swap: live store untouched
            np.testing.assert_array_equal(_mirror(server), mirror)
        if server.stats.swaps > pre_swaps:
            swaps += server.stats.swaps - pre_swaps
            mirror = np.asarray(ps.unpack(pack(snaps[-1], CFG)))
        np.testing.assert_array_equal(_mirror(server), mirror)
    pre_swaps = server.stats.swaps
    server.drain_shadow()           # joins the staging thread too
    if server.stats.swaps > pre_swaps:
        swaps += server.stats.swaps - pre_swaps
        mirror = np.asarray(ps.unpack(pack(snaps[-1], CFG)))
    np.testing.assert_array_equal(_mirror(server), mirror)
    return swaps


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None, derandomize=True)
def test_flat_schedules_bit_identical(seed):
    rng = np.random.default_rng(seed)
    server = _flat_server(seed=seed % 5)
    ops = [OPS[i] for i in rng.integers(0, len(OPS), 40)]
    run_flat_schedule(server, ops, rng)


def test_auto_mode_swaps_under_traffic():
    """retier_every-triggered builds: the server opens, chunks and
    swaps shadows on its own while every lookup stays on the oracle."""
    rng = np.random.default_rng(3)
    server = _flat_server(seed=3, retier_every=2)
    swaps = run_flat_schedule(server, ["serve"] * 60, rng)
    assert server.stats.shadow_builds >= 1
    assert swaps >= 1
    assert server.stats.rows_moved > 0


def test_swap_during_drift_lands_snapshot_state():
    """Priorities folded AFTER the snapshot must NOT leak into the
    swapped store: the swap equals pack() at the snapshot, and only the
    NEXT build picks the drift up."""
    rng = np.random.default_rng(11)
    server = _flat_server(seed=1)
    for _ in range(6):      # drift until some rows cross tiers
        server.observe(jnp.asarray(rng.integers(0, V, (64,))
                                   .astype(np.int32)), count=16)
    assert server.begin_retier()
    snap = server.shadow.snapshot
    # keep folding while the build is chunked — swap-during-drift
    while not server.shadow.staged:
        server.observe(jnp.asarray(rng.integers(0, V, (64,))
                                   .astype(np.int32)), count=16)
        if server.shadow is None:   # staged + swapped under traffic
            break
        server.shadow.step(16)
    drifted = server.store
    server.drain_shadow()
    assert server.stats.swaps == 1
    np.testing.assert_array_equal(
        np.asarray(ps.unpack(server.host_packed)),
        np.asarray(ps.unpack(pack(snap, CFG))))
    crossed, _ = tier_crossings(ps.packed_tiers(server.host_packed),
                                qs.current_tiers(drifted, CFG))
    if crossed.size:    # drift did cross tiers: live != pack(drifted)
        assert not np.array_equal(
            np.asarray(ps.unpack(server.host_packed)),
            np.asarray(ps.unpack(pack(drifted, CFG))))
    # the next build converges onto the drifted state
    server.begin_retier()
    final = server.shadow.snapshot if server.shadow is not None \
        else server.store
    server.drain_shadow()
    np.testing.assert_array_equal(
        np.asarray(ps.unpack(server.host_packed)),
        np.asarray(ps.unpack(pack(final, CFG))))


def _placed_like_fresh(server) -> bool:
    """The live device store has the pytree structure a fresh placement
    of the live host pack has, payloads lane-dense."""
    from repro.dist.packed import place_packed
    from repro.kernels.rows import LaneDense
    pk = server.packed
    return (jax.tree.structure(pk)
            == jax.tree.structure(place_packed(server.host_packed))
            and all(isinstance(p, LaneDense) for p in
                    (pk.payload8, pk.payload16, pk.payload32)))


def test_swap_keeps_placed_layout_and_compiles_nothing():
    """The shadow is staged as the live store is placed, so the forward
    warmed on the staged store while staging serves the swapped-in
    store without a compile (the compile counter of ``obs.span_log``)."""
    from repro import obs
    from repro.serve import cached_lookup
    rng = np.random.default_rng(11)
    server = _flat_server(seed=1)
    lfn = server.lookup_fn()
    idx = jnp.asarray(rng.integers(0, V, (32,)).astype(np.int32))
    fwd = jax.jit(lambda pk, cache, i: cached_lookup(pk, cache, i,
                                                     lfn)[0])
    server.warmup_fn = lambda staged: jax.block_until_ready(
        fwd(staged, server.cache, idx))
    assert _placed_like_fresh(server)
    jax.block_until_ready(fwd(server.packed, server.cache, idx))
    for _ in range(6):      # drift until some rows cross tiers
        server.observe(jnp.asarray(rng.integers(0, V, (64,))
                                   .astype(np.int32)), count=16)
    shapes = [p.shape for p in server.packed]
    assert server.begin_retier()
    server.drain_shadow()
    assert server.stats.swaps == 1
    assert [p.shape for p in server.packed] != shapes
    assert _placed_like_fresh(server)
    before = obs.span_log().compiles["jax.compile"][0]
    out = fwd(server.packed, server.cache, idx)
    jax.block_until_ready(out)
    assert obs.span_log().compiles["jax.compile"][0] == before
    np.testing.assert_array_equal(np.asarray(out),
                                  _mirror(server)[np.asarray(idx)])


def test_double_swap_and_crash_before_swap():
    rng = np.random.default_rng(23)
    server = _flat_server(seed=2)

    def drift():
        for _ in range(4):
            server.observe(jnp.asarray(rng.integers(0, V, (64,))
                                       .astype(np.int32)), count=16)

    # crash-before-swap: partial build discarded, live untouched
    before = _mirror(server)
    drift()
    if server.begin_retier():
        server.shadow.step(8)
        server.discard_shadow()
    np.testing.assert_array_equal(_mirror(server), before)
    assert server.stats.swaps == 0

    # double-swap: two full cycles, each bit-identical at its snapshot
    for _ in range(2):
        drift()
        if server.begin_retier():
            snap = server.shadow.snapshot
            server.drain_shadow()
            np.testing.assert_array_equal(
                np.asarray(ps.unpack(server.host_packed)),
                np.asarray(ps.unpack(pack(snap, CFG))))
    # a begin with zero movers is the synchronous no-move path
    n_retier = server.stats.retiers
    assert not server.begin_retier() or server.shadow is not None
    server.drain_shadow()
    assert server.stats.retiers >= n_retier


def test_chunk_boundary_invariant_every_row():
    """Budget=1 stepping: the materialized shadow equals the partial
    synchronous repack at EVERY mover-row boundary."""
    rng = np.random.default_rng(5)
    server = _flat_server(seed=4)
    for _ in range(6):
        server.observe(jnp.asarray(rng.integers(0, V, (64,))
                                   .astype(np.int32)), count=16)
    assert server.begin_retier()
    sh = server.shadow
    assert sh.moved > 1
    while not sh.step(1):
        got = np.asarray(ps.unpack(sh.materialize()))
        ref = np.asarray(ps.unpack(ps.repack_delta(
            server.host_packed, sh.snapshot, CFG, sh.movers[:sh.pos])))
        np.testing.assert_array_equal(got, ref)
    sh.verify()
    server.drain_shadow()


@given(st.integers(0, 2**31 - 1), st.integers(1, 6))
@settings(max_examples=12, deadline=None, derandomize=True)
def test_repack_delta_chunk_composition(seed, nchunks):
    """N chunked deltas over any partition, applied in any order,
    compose to exactly one full pack at the final fold state."""
    rng = np.random.default_rng(seed)
    st_ = _store(seed=seed % 5)
    packed = pack(st_, CFG)
    st2 = st_._replace(priority=jnp.asarray(
        np.asarray(st_.priority)
        * rng.uniform(0.05, 20.0, V).astype(np.float32)))
    changed, _ = tier_crossings(ps.packed_tiers(packed),
                                qs.current_tiers(st2, CFG))
    acc = packed
    for part in np.array_split(rng.permutation(changed),
                               min(nchunks, max(changed.size, 1))):
        acc = ps.repack_delta(acc, st2, CFG, part)
    full = pack(st2, CFG)
    np.testing.assert_array_equal(np.asarray(ps.unpack(acc)),
                                  np.asarray(ps.unpack(full)))
    np.testing.assert_array_equal(
        np.bincount(ps.packed_tiers(acc), minlength=3),
        np.bincount(ps.packed_tiers(full), minlength=3))
    assert acc.nbytes() == full.nbytes()


def _hier_server(store_dir, seed=0):
    from repro.store import HierConfig
    st_ = _store(seed)
    full = pack(st_, CFG).nbytes()
    budget = max(1, int(full * 0.3))
    return OnlineServer(
        st_, CFG,
        OnlineConfig(cache_rows=8, retier_every=0, retier_async=True,
                     shadow_rows_per_step=16, verify_swap=True),
        hier=HierConfig(hbm_budget_bytes=budget,
                        host_budget_bytes=budget,
                        rows_per_shard=16, store_dir=store_dir))


def _hier_mirror(server):
    return np.asarray(hier_lookup(server.hier, np.arange(V)))


def run_hier_schedule(server, ops, rng, store_dir):
    """Hier twin of the flat scheduler: the oracle is the level-resolved
    lookup of every row, which must equal pack() at the last swap's
    snapshot; discard must additionally leave no unpublished cold tmp
    generation behind."""
    mirror = _hier_mirror(server)
    np.testing.assert_array_equal(
        mirror, np.asarray(ps.unpack(pack(server.store, CFG))))
    snaps = _record_swaps(server)
    for op in ops:
        pre_swaps = server.stats.swaps
        if op == "serve":
            idx = rng.integers(0, V, (6, 4)).astype(np.int32)
            rows = np.asarray(server.lookup(jnp.asarray(idx)))
            np.testing.assert_array_equal(rows, mirror[idx])
        elif op == "fold":
            idx = rng.integers(0, V, (16,)).astype(np.int32)
            server.observe(jnp.asarray(idx), count=4)
        elif op == "begin":
            server.begin_retier()
        elif op == "chunk":
            sh = server.shadow
            if sh is not None and not sh.staged:
                before = sh.done_rows
                sh.step(int(rng.integers(1, 48)))
                assert sh.done_rows >= before
        elif op == "tick":
            server._shadow_tick(1)
        elif op == "drain":
            server.drain_shadow()
        elif op == "discard":
            server.discard_shadow()
            np.testing.assert_array_equal(_hier_mirror(server), mirror)
            assert not glob.glob(os.path.join(store_dir, "**",
                                              ".tmp_hier_*"),
                                 recursive=True)
        if server.stats.swaps > pre_swaps:
            mirror = np.asarray(ps.unpack(pack(snaps[-1], CFG)))
        np.testing.assert_array_equal(_hier_mirror(server), mirror)
    pre_swaps = server.stats.swaps
    server.drain_shadow()
    if server.stats.swaps > pre_swaps:
        mirror = np.asarray(ps.unpack(pack(snaps[-1], CFG)))
    np.testing.assert_array_equal(_hier_mirror(server), mirror)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None, derandomize=True)
def test_hier_schedules_bit_identical(seed):
    rng = np.random.default_rng(seed)
    store_dir = tempfile.mkdtemp(prefix="shadow_swap_hier_")
    server = _hier_server(store_dir, seed=seed % 5)
    ops = [OPS[i] for i in rng.integers(0, len(OPS), 30)]
    run_hier_schedule(server, ops, rng, store_dir)


def test_hier_cold_rewrite_and_crash_before_swap():
    """An outright priority reversal forces the cold set to change: the
    shadow stages a NEW cold generation shard-by-shard in a hidden tmp
    dir; discard before the swap removes it and the live generation
    (open mmaps included) keeps serving bit-identically."""
    store_dir = tempfile.mkdtemp(prefix="shadow_swap_cold_")
    server = _hier_server(store_dir, seed=6)
    before = _hier_mirror(server)
    pri = np.asarray(server.store.priority)
    server.store = server.store._replace(
        priority=jnp.asarray(pri[::-1].copy()))
    assert server.begin_retier()
    sh = server.shadow
    assert sh._cold_needed
    snap = sh.snapshot
    while not sh.step(32):      # builds + one cold shard per call
        np.testing.assert_array_equal(_hier_mirror(server), before)
    # crash-before-swap: tmp generation discarded, live untouched
    server.discard_shadow()
    assert not glob.glob(os.path.join(store_dir, "**", ".tmp_hier_*"),
                         recursive=True)
    np.testing.assert_array_equal(_hier_mirror(server), before)
    # the rebuilt shadow swaps onto the snapshot fold state
    server.store = snap
    assert server.begin_retier()
    server.drain_shadow()
    assert server.stats.swaps == 1
    np.testing.assert_array_equal(
        _hier_mirror(server),
        np.asarray(ps.unpack(pack(snap, CFG))))


def test_flat_schedule_sharded_4way():
    """The generated-schedule harness under a 4-way row-sharded mesh:
    same oracle, device placement through shard_packed/place_packed."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "tests")
try:
    import hypothesis  # noqa: F401
except ImportError:
    from _hypothesis_stub import install
    install()
import numpy as np, jax
from test_shadow_swap import OPS, _flat_server, run_flat_schedule

mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(7)
server = _flat_server(seed=1, mesh=mesh)
ops = [OPS[i] for i in rng.integers(0, len(OPS), 30)]
run_flat_schedule(server, ops, rng)

# and an auto-mode pass that must actually swap under the mesh
rng = np.random.default_rng(8)
server = _flat_server(seed=2, mesh=mesh, retier_every=2)
swaps = run_flat_schedule(server, ["serve"] * 50, rng)
assert swaps >= 1, "no swap landed under the 4-way mesh"
print("SHADOW_SWAP_MESH4_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "SHADOW_SWAP_MESH4_OK" in r.stdout, r.stderr[-2000:]
