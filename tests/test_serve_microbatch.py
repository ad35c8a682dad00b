"""Micro-batched serving pipeline: fixed-shape pad+mask fusion,
vectorised masked observe, request-counter re-tier cadence, and
bit-identity of the served rows with the packed-store oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FQuantConfig, pack
from repro.core import packed_store as ps
from repro.core import qat_store as qs
from repro.core.tiers import TierConfig
from repro.serve import (
    MicroBatcher,
    OnlineConfig,
    OnlineServer,
    build_cache,
    cached_lookup,
    drifting_zipf_batch,
    run_microbatched_loop,
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


def test_microbatcher_fill_and_flush():
    mb = MicroBatcher(4, 3)
    assert mb.add([1, 2, 3]) is None
    assert mb.add([4, 5, 6]) is None
    assert len(mb) == 2
    tail = mb.flush()
    assert tail.count == 2
    assert tail.indices.shape == (4, 3)
    assert tail.indices.dtype == np.int32
    np.testing.assert_array_equal(tail.valid, [True, True, False, False])
    np.testing.assert_array_equal(tail.indices[2:], 0)  # row-0 padding
    assert len(mb) == 0 and mb.flush() is None

    full = None
    for i in range(4):
        got = mb.add([i, i, i])
        full = got or full
    assert full is not None and full.count == 4 and full.valid.all()
    np.testing.assert_array_equal(full.indices[:, 0], [0, 1, 2, 3])


def test_microbatcher_rejects_bad_shapes():
    with pytest.raises(ValueError):
        MicroBatcher(0, 3)
    mb = MicroBatcher(2, 3)
    with pytest.raises(ValueError):
        mb.add([1, 2])


def test_cached_lookup_valid_masks_hit_count():
    st = _store(1)
    packed = pack(st, CFG)
    cache = build_cache(packed, st.priority, 32)
    hot = np.asarray(cache.ids)[:4]
    idx = jnp.asarray(np.stack([hot, hot]).T)          # (4, 2) all hits
    valid = jnp.asarray([True, True, False, False])
    out, hits = cached_lookup(packed, cache, idx, valid=valid[:, None])
    assert int(hits) == 4                               # 2 rows x 2 cols
    # masking changes accounting only, never the gathered rows
    out_all, hits_all = cached_lookup(packed, cache, idx)
    assert int(hits_all) == 8
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_all))


def test_observe_masked_equals_unpadded_fold():
    """A padded micro-batch folds exactly like its live prefix."""
    st = _store(2)
    a = OnlineServer(st, CFG, OnlineConfig(retier_every=0))
    b = OnlineServer(st, CFG, OnlineConfig(retier_every=0))
    idx = np.array([[3, 4], [7, 8], [0, 0], [0, 0]], np.int32)
    valid = np.array([True, True, False, False])
    a.observe(jnp.asarray(idx), 1, valid=valid[:, None], count=2)
    b.observe(jnp.asarray(idx[:2]), 1, count=2)
    np.testing.assert_array_equal(np.asarray(a.store.priority),
                                  np.asarray(b.store.priority))
    assert a.stats.requests == b.stats.requests == 2
    assert a.stats.lookups == b.stats.lookups == 4
    assert a.stats.hits == b.stats.hits == 1


def test_observe_count_crossing_triggers_retier():
    """count > 1 fires the re-tier whenever the request counter crosses
    a retier_every boundary — same boundaries as count=1 serving."""
    st = _store(3)
    srv = OnlineServer(st, CFG, OnlineConfig(retier_every=4))
    idx = jnp.asarray(np.zeros((3, 2), np.int32))
    fired = []
    for _ in range(4):
        srv.observe(idx, count=3)      # requests: 3, 6, 9, 12
        fired.append(srv.stats.retiers)
    assert fired == [0, 1, 2, 3]       # crossings at 4, 8, 12

    srv1 = OnlineServer(st, CFG, OnlineConfig(retier_every=4))
    for _ in range(12):
        srv1.observe(idx[:1], count=1)
    assert srv1.stats.retiers == 3     # identical cadence per-request


def test_run_microbatched_loop_serves_bit_identical_rows():
    """End-to-end: every micro-batch's gathered rows equal the oracle
    lookup on the live host store; stats line up with the stream."""
    st = _store(4)
    srv = OnlineServer(st, CFG,
                       OnlineConfig(cache_rows=24, retier_every=8))
    served = []

    def serve_fn(mb):
        idx = jnp.asarray(mb.indices)
        ref = np.asarray(ps.lookup(srv.host_packed, idx))
        rows, hits = cached_lookup(srv.packed, srv.cache, idx,
                                   srv.lookup_fn(),
                                   valid=jnp.asarray(mb.valid)[:, None])
        np.testing.assert_array_equal(np.asarray(rows), ref)
        srv.observe(idx, int(hits), valid=mb.valid[:, None],
                    count=mb.count)
        served.append(mb.count)
        return rows

    result = run_microbatched_loop(
        srv, serve_fn,
        lambda r: drifting_zipf_batch((V, V), 1, r, 22, drift=2.0,
                                      seed=3)[0],
        requests=22, serve_batch=4)
    assert sum(served) == 22
    assert served[-1] == 2                  # padded tail batch
    assert srv.stats.requests == 22
    assert srv.stats.lookups == 44          # 22 requests x 2 fields
    assert srv.stats.retiers == 2           # crossings at 8, 16
    assert result.qps > 0 and result.steady_qps > 0
    assert len(result.lat_s) == 6           # ceil(22 / 4) batches
    # post-stream: the delta-repacked store still equals a full pack
    np.testing.assert_array_equal(
        np.asarray(ps.unpack(srv.host_packed)),
        np.asarray(ps.unpack(pack(srv.store, CFG))))


def test_microbatch_stream_independent_of_fusion_factor():
    """The same seed yields the same request sequence whatever the
    micro-batch capacity — QPS sweeps compare like-for-like."""
    reqs = [drifting_zipf_batch((V, 31), 1, r, 16, drift=3.0, seed=7)[0]
            for r in range(16)]
    for sb in (1, 4, 8):
        batcher = MicroBatcher(sb, 2)
        got = []
        for r in reqs:
            out = batcher.add(r)
            if out is not None:
                got.append(out.indices[:out.count])
        tail = batcher.flush()
        if tail is not None:
            got.append(tail.indices[:tail.count])
        np.testing.assert_array_equal(np.concatenate(got),
                                      np.stack(reqs))


def test_relayout_traced_zero_for_placed_forward_not_for_train_step(
        monkeypatch):
    """``kernels.relayout_traced`` on the kernel path: the micro-batched
    forward over the placed store traces no whole-table relayout, the
    same forward over (V, D) payloads one per tier, and the compressed
    train step (a logical table, rewritten every step) keeps its own."""
    from repro import obs
    from repro.configs import dlrm_rm2
    from repro.kernels.rows import LaneDense
    from repro.train.setup import build_recsys_training
    from _smoke_serve import kernel_path, logical, smoke_forward

    server, fwd, (packed, *rest) = smoke_forward()
    assert isinstance(packed.payload8, LaneDense)
    train = build_recsys_training(dlrm_rm2.arch(), batch=16,
                                  use_pallas=True)
    batch = train.batch_fn(0)

    def relayouts(fn, *args):
        with obs.bind(obs.Registry()) as reg:
            jaxpr = str(fn.trace(*args).jaxpr)
        assert "pallas_call" in jaxpr
        return {k: v for k, v in reg.counters.items()
                if k.startswith("kernels.relayout_traced.")}

    with kernel_path(monkeypatch):
        assert relayouts(fwd, packed, *rest) == {}
        assert len(relayouts(fwd, logical(packed), *rest)) == 3
        assert relayouts(jax.jit(train.step), train.state, batch)
