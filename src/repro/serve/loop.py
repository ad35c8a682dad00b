"""Request loop + micro-batching + synthetic drifting-zipf workload.

``drifting_zipf_batch`` draws per-field zipf-ranked indices whose hot
set rotates linearly through each field's id space over the request
stream — the adversarial case for any *static* tier assignment: rows
that were cold at pack time become the head of the distribution
mid-stream.  The online path (priority fold + delta re-tier + cache
rebuild) is exactly what keeps hit rate and per-row bytes tracking such
drift; the offline path degrades.

``run_loop`` times a request stream and reports overall QPS (first,
compile-bearing request dropped — the same convention as the offline
driver) and steady-state QPS: the second half of the stream minus the
requests that ran a re-tier or immediately followed one (those pay the
host repack and the jit recompile respectively; a production deployment
runs them off the serving thread).

``serve_forward_loop`` is the shared online driver behind
``repro.launch.serve --online`` and ``benchmarks/qps.py --online``:
jitted cache-first forward + priority fold over a drifting-zipf stream.

Micro-batching (``MicroBatcher`` / ``run_microbatched_loop`` /
``serve_forward_microbatched``) replaces request-at-a-time execution:
incoming single-user requests accumulate into **fixed-shape** (N, F)
batches — padded with row 0 and a validity mask when the stream ends
mid-batch, so the jitted forward never re-specialises — and each batch
runs ONE forward, ONE vectorised priority fold, and ONE cache pass.
The per-request Python + dispatch overhead that dominates small-request
serving is amortised N ways; ``--serve-batch`` in the drivers selects N
and ``benchmarks/qps.py --online`` sweeps it.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import embedding as E
from repro.obs.registry import Histogram
from repro.serve.cache import cache_select, cached_lookup
from repro.serve.online import OnlineServer

# the serving span taxonomy (docs/observability.md): pre-registered by
# the drivers when metrics are on, so every snapshot carries the full
# per-phase histogram catalog even for phases that never fired (e.g.
# stage/migrate when serving a fully resident store)
SERVE_PHASES = ("serve.request", "serve.stage", "serve.lookup",
                "serve.fold", "serve.fold.mask", "serve.fold.priority",
                "serve.fold.retier", "serve.retier",
                "serve.shadow.plan", "serve.shadow.chunk",
                "serve.shadow.build", "serve.shadow.stage",
                "serve.shadow.verify", "serve.shadow.warmup",
                "serve.shadow.swap", "store.stage", "store.migrate")


class LoopResult(NamedTuple):
    lat_s: tuple          # per-request wall seconds
    qps: float            # whole stream minus the first request
    steady_qps: float     # second half, re-tier-affected requests excluded
    p50_us: float         # histogram-derived (obs.registry.Histogram)
    p95_us: float
    p99_us: float
    p99_retier_attributed: float  # fraction of the p99 tail's wall time
                                  # spent inside retier/migrate
    p99_while_retiering: float    # p99 over ONLY the requests that
                                  # overlapped a re-tier: sync repack,
                                  # shadow build/chunk/stage or swap
                                  # (0.0 when the stream had none) —
                                  # the number the tail budget gates
    stats: dict           # ServeStats.as_dict() snapshot
    last_out: object = None  # what serve_fn returned for the last batch
    forward: object = None   # (jitted forward, its last call's args):
                             # lower it to inspect the served program

    def as_dict(self) -> dict:
        d = {"qps": round(self.qps, 1),
             "steady_qps": round(self.steady_qps, 1),
             "p50_us": round(self.p50_us, 1),
             "p95_us": round(self.p95_us, 1),
             "p99_us": round(self.p99_us, 1),
             # bench_qps/v1 percentile columns (same values, the
             # stable names the tail-latency items diff against)
             "latency_p50": round(self.p50_us, 1),
             "latency_p95": round(self.p95_us, 1),
             "latency_p99": round(self.p99_us, 1),
             "p99_retier_attributed": round(
                 self.p99_retier_attributed, 4),
             "p99_while_retiering": round(self.p99_while_retiering, 1)}
        d.update(self.stats)
        return d


def _latency_summary(lat_us: np.ndarray, retier_us: np.ndarray,
                     warm: slice, window=None
                     ) -> tuple[float, float, float, float, float]:
    """(p50, p95, p99, p99_retier_attributed, p99_while_retiering) over
    the warm window.

    Percentiles come from an ``obs`` streaming histogram — the same
    estimator replicas merge across shards — not from the raw latency
    list.  Attribution: of the batches at/above the p99 estimate, the
    fraction of their summed wall time that was spent inside
    ``OnlineServer.retier`` (delta re-tier or hier migration) — the
    quantity the async-retier work must drive to ~0.

    ``window`` (bool per batch, or None) marks batches that overlapped
    re-tier activity — a synchronous repack, or any shadow
    build/chunk/stage/swap; ``p99_while_retiering`` is the p99 over
    ONLY those batches (0.0 when there are none), i.e. the tail a
    client sees *while* the store is re-tiering.
    """
    lw, rw = lat_us[warm], retier_us[warm]
    hist = Histogram()
    hist.record_many(lw)
    p50, p95, p99 = (hist.percentile(q) for q in (50, 95, 99))
    tail = lw >= p99
    denom = float(lw[tail].sum())
    attributed = float(rw[tail].sum()) / denom if denom > 0 else 0.0
    p99_while = 0.0
    if window is not None:
        ww = np.asarray(window, bool)[warm]
        if ww.any():
            wh = Histogram()
            wh.record_many(lw[ww])
            p99_while = float(wh.percentile(99))
    return (p50, p95, p99, float(min(max(attributed, 0.0), 1.0)),
            p99_while)


def drifting_zipf_batch(cardinalities, batch: int, request: int,
                        num_requests: int, *, a: float = 1.2,
                        drift: float = 4.0, seed: int = 0) -> np.ndarray:
    """Field-local int32 (batch, F) indices, zipf-ranked with a moving
    hot set.

    Rank r of field f maps to id ``(r + shift_f) % card_f`` where
    ``shift_f = floor(drift * request)``: the hot set advances ``drift``
    ids per request, wrapping around each field's id space.  The rate is
    absolute (ids/request, not a fraction of the cardinality) so it is
    *trackable*: the zipf head is a few dozen ids wide, and a re-tier +
    cache rebuild every few requests can keep up with a few-ids/request
    drift, while a static pack decays.  ``drift=0`` is a stationary
    zipf workload.  ``num_requests`` is unused but kept so callers can
    switch drift laws without re-plumbing.
    """
    del num_requests
    cards = np.asarray(cardinalities, np.int64)
    rng = np.random.default_rng(seed * 1_000_003 + request)
    ranks = rng.zipf(a, size=(batch, cards.size)).astype(np.int64) - 1
    shift = np.int64(np.floor(drift * request))
    return ((ranks + shift) % cards[None, :]).astype(np.int32)


class MicroBatch(NamedTuple):
    indices: np.ndarray   # (N, F) int32; padded slots hold row 0
    valid: np.ndarray     # (N,) bool; False marks padding
    count: int            # live requests in this batch


class MicroBatcher:
    """Accumulates single-request index vectors into fixed-shape batches.

    ``add`` returns a full ``MicroBatch`` every ``capacity`` requests
    and ``None`` otherwise; ``flush`` pads a partial tail batch (row 0
    indices, ``valid=False``) so every emitted batch has the SAME
    (capacity, F) shape — the jitted forward compiles once per
    capacity, never per fill level.
    """

    def __init__(self, capacity: int, num_fields: int):
        if capacity < 1:
            raise ValueError("micro-batch capacity must be >= 1")
        self.capacity = int(capacity)
        self.num_fields = int(num_fields)
        self._buf = np.zeros((self.capacity, self.num_fields), np.int32)
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def add(self, request) -> MicroBatch | None:
        req = np.asarray(request, np.int32).reshape(-1)
        if req.shape[0] != self.num_fields:
            raise ValueError(
                f"request has {req.shape[0]} fields, expected "
                f"{self.num_fields}")
        self._buf[self._n] = req
        self._n += 1
        return self.flush() if self._n == self.capacity else None

    def flush(self) -> MicroBatch | None:
        if self._n == 0:
            return None
        n = self._n
        valid = np.zeros((self.capacity,), bool)
        valid[:n] = True
        batch = MicroBatch(indices=self._buf.copy(), valid=valid, count=n)
        self._buf[:] = 0
        self._n = 0
        return batch


def run_microbatched_loop(server: OnlineServer,
                          serve_fn: Callable[[MicroBatch], object],
                          make_request: Callable[[int], np.ndarray],
                          requests: int, serve_batch: int) -> LoopResult:
    """Drive ``requests`` single-user requests through ``serve_fn`` in
    fixed-shape micro-batches of ``serve_batch`` and time the batches.

    ``make_request(r)`` yields one (F,) index vector; ``serve_fn``
    receives a ``MicroBatch`` and is responsible for the forward AND for
    ``server.observe(..., valid=..., count=...)``; its result is blocked
    on for honest wall-clock.  QPS counts *requests* (not batches), so
    numbers are comparable across ``serve_batch`` values.  Steady-state
    follows the ``run_loop`` convention at micro-batch granularity:
    second half of the batch stream, re-tier-affected batches excluded.
    """
    first = np.asarray(make_request(0), np.int32).reshape(-1)
    batcher = MicroBatcher(serve_batch, first.shape[0])
    lat, counts, retiered, retier_s, window = [], [], [], [], []
    last: list = [None]

    def run_batch(mb: MicroBatch) -> None:
        n_retiers = server.stats.retiers
        r0 = server.stats.retier_seconds
        c0 = server.stats.shadow_chunks
        s0 = server.stats.swaps
        active0 = server.shadow is not None
        with obs.timeblock("serve.request") as tb:
            last[0] = tb.sync(serve_fn(mb))
        lat.append(tb.seconds)
        counts.append(mb.count)
        retiered.append(server.stats.retiers > n_retiers)
        retier_s.append(server.stats.retier_seconds - r0)
        window.append(active0 or retiered[-1]
                      or server.stats.shadow_chunks > c0
                      or server.stats.swaps > s0)
        obs.tick()

    pending = batcher.add(first)
    if pending is not None:
        run_batch(pending)
    for r in range(1, requests):
        pending = batcher.add(make_request(r))
        if pending is not None:
            run_batch(pending)
    tail = batcher.flush()
    if tail is not None:
        run_batch(tail)

    lat_arr = np.asarray(lat)
    cnt_arr = np.asarray(counts, np.float64)
    warm = slice(1, None) if len(lat) > 1 else slice(None)
    half = len(lat) // 2
    steady = [i for i in range(half, len(lat))
              if not (i == 0 or retiered[i] or retiered[i - 1])]
    if not steady:
        steady = list(range(half, len(lat)))
    p50, p95, p99, attributed, p99_while = _latency_summary(
        lat_arr * 1e6, np.asarray(retier_s) * 1e6, warm, window)
    return LoopResult(
        lat_s=tuple(lat),
        qps=float(cnt_arr[warm].sum() / lat_arr[warm].sum()),
        steady_qps=float(cnt_arr[steady].sum() / lat_arr[steady].sum()),
        p50_us=p50, p95_us=p95, p99_us=p99,
        p99_retier_attributed=attributed,
        p99_while_retiering=p99_while,
        stats=server.stats.as_dict(), last_out=last[0])


def run_loop(server: OnlineServer,
             serve_fn: Callable[[np.ndarray], object],
             make_batch: Callable[[int], np.ndarray],
             requests: int, batch: int) -> LoopResult:
    """Drive ``requests`` batches through ``serve_fn`` and time them.

    ``serve_fn`` receives the (batch, F) field-local index array and is
    responsible for the forward *and* for ``server.observe`` (so jit
    boundaries stay under the driver's control); its result is blocked
    on for honest wall-clock.  Requests during which the server
    re-tiered are detected from ``server.stats`` and excluded — together
    with their successor, which pays the recompile — from the
    steady-state window.
    """
    lat, retiered, retier_s, window = [], [], [], []
    for r in range(requests):
        idx = make_batch(r)
        n_retiers = server.stats.retiers
        r0 = server.stats.retier_seconds
        c0 = server.stats.shadow_chunks
        s0 = server.stats.swaps
        active0 = server.shadow is not None
        with obs.timeblock("serve.request") as tb:
            tb.sync(serve_fn(idx))
        lat.append(tb.seconds)
        retiered.append(server.stats.retiers > n_retiers)
        retier_s.append(server.stats.retier_seconds - r0)
        window.append(active0 or retiered[-1]
                      or server.stats.shadow_chunks > c0
                      or server.stats.swaps > s0)
        obs.tick()
    lat_arr = np.asarray(lat)

    warm_sl = slice(1, None) if len(lat) > 1 else slice(None)
    warm = lat_arr[warm_sl]
    steady = [lat_arr[i] for i in range(len(lat) // 2, len(lat))
              if not (i == 0 or retiered[i] or retiered[i - 1])]
    steady = np.asarray(steady) if steady else lat_arr[len(lat) // 2:]
    p50, p95, p99, attributed, p99_while = _latency_summary(
        lat_arr * 1e6, np.asarray(retier_s) * 1e6, warm_sl, window)
    return LoopResult(
        lat_s=tuple(lat),
        qps=batch / float(warm.mean()),
        steady_qps=batch / float(steady.mean()),
        p50_us=p50, p95_us=p95, p99_us=p99,
        p99_retier_attributed=attributed,
        p99_while_retiering=p99_while,
        stats=server.stats.as_dict())


def _fused_entry(server: OnlineServer, model, fuse_matmul: bool):
    """Resolve the ``fuse_matmul`` serving mode: (fused_head | None,
    needs_emb, bag_matmul_fn | None).

    Fusion needs the model to expose ``extras["fused_head"]`` (wide&deep
    and xDeepFM do; DLRM's first consumer of emb is the Gram
    interaction, so its ceiling is the fused lookup).  When the fused
    head does not consume raw embeddings the fp32 hot-row cache is
    bypassed for that branch — the trade the fused kernel makes for
    eliminating the (B, F*D) HBM round-trip (docs/kernels.md).
    """
    if not fuse_matmul:
        return None, False, None
    fused = model.extras.get("fused_head")
    if fused is None:
        raise ValueError(
            f"model {model.name!r} has no fused head "
            "(extras['fused_head']); serve without fuse_matmul")
    return (fused, bool(model.extras.get("fused_needs_emb")),
            server.bag_matmul_fn())


def serve_forward_loop(server: OnlineServer, model, spec, params, *,
                       batch: int, requests: int, drift: float = 4.0,
                       num_dense: int = 0, a: float = 1.2,
                       seed: int = 0,
                       fuse_matmul: bool = False) -> LoopResult:
    """Shared online driver: jitted cache-first forward + observe fold.

    Serves ``requests`` drifting-zipf batches through
    ``model.head(params, cached_lookup(...), batch)``.  The jitted
    forward takes the packed store and cache as arguments, so a re-tier
    (which changes payload shapes) recompiles exactly at re-tier
    boundaries and nowhere else.  ``num_dense > 0`` synthesises that
    many dense features per request (DLRM-style heads).

    ``fuse_matmul=True`` serves through ``extras["fused_head"]``: the
    deep branch's first matmul runs fused with the embedding gather
    (``kernels.bag_matmul`` via ``server.bag_matmul_fn()``) so the
    (B, F*D) activations never materialise; heads that don't consume
    raw embeddings skip the cache-first lookup entirely (hits = 0).
    """
    lfn = server.lookup_fn()
    fused, needs_emb, bmfn = _fused_entry(server, model, fuse_matmul)

    @jax.jit
    def fwd(packed, cache, net, b):
        gidx = E.globalize(b["indices"], spec)
        if fused is not None:
            bm = lambda w: bmfn(packed, gidx, w)  # noqa: E731
            if needs_emb:
                emb, hits = cached_lookup(packed, cache, gidx, lfn)
                return fused(net, b, bm, emb), hits, gidx
            return fused(net, b, bm), jnp.zeros((), jnp.int32), gidx
        emb, hits = cached_lookup(packed, cache, gidx, lfn)
        return model.head(net, emb, b), hits, gidx

    counter = {"r": 0}
    last: dict = {}

    # shadow staging pre-compiles the forward for the new payload
    # shapes off-thread, so the post-swap request hits the jit cache
    def _warm(staged) -> None:
        if "b" in last:
            jax.block_until_ready(
                fwd(staged, server.cache, params, last["b"]))
    server.warmup_fn = _warm

    def serve_fn(idx: np.ndarray):
        r = counter["r"]
        counter["r"] += 1
        b = {"indices": jnp.asarray(idx),
             "labels": jnp.zeros((idx.shape[0],))}
        if num_dense:
            rr = np.random.default_rng(10_000 + r)
            b["dense"] = jnp.asarray(rr.standard_normal(
                (idx.shape[0], num_dense)).astype(np.float32))
        last["b"] = b
        with obs.span("serve.lookup"):
            out, hits, gidx = fwd(server.packed, server.cache, params, b)
            jax.block_until_ready(out)
        server.observe(gidx, int(hits))
        return out

    cards = np.asarray(spec.cardinalities, np.int64)
    return run_loop(
        server, serve_fn,
        lambda r: drifting_zipf_batch(cards, batch, r, requests, a=a,
                                      drift=drift, seed=seed),
        requests, batch)


def serve_forward_microbatched(server: OnlineServer, model, spec,
                               params, *, serve_batch: int,
                               requests: int, drift: float = 4.0,
                               num_dense: int = 0, a: float = 1.2,
                               seed: int = 0,
                               fuse_matmul: bool = False) -> LoopResult:
    """Micro-batched online driver: one jitted forward per N requests.

    Single-user drifting-zipf requests accumulate into fixed-shape
    (serve_batch, F) batches (pad + mask); each batch runs one
    cache-first forward through ``model.head`` and ONE vectorised
    ``server.observe`` fold, with padded slots masked out of both the
    hit count and the priority EMA.  The Eq. 7 EMA becomes one
    count-weighted fold per micro-batch (N requests' access counts
    enter a single decay step instead of N sequential steps); re-tiers
    fire on the same request-counter boundaries as per-request serving
    while ``serve_batch <= retier_every``, and boundaries spanned by
    one batch coalesce into a single re-tier otherwise (see
    ``OnlineServer.observe``).  The request stream depends only on the
    seed, not on ``serve_batch``, so QPS across batch sizes compares
    like-for-like.  ``fuse_matmul`` as in ``serve_forward_loop``
    (padded slots' fused outputs are garbage-in/ignored-out, exactly
    like the unfused head's).
    """
    lfn = server.lookup_fn()
    fused, needs_emb, bmfn = _fused_entry(server, model, fuse_matmul)

    @jax.jit
    def fwd(packed, cache, net, b, valid):
        gidx = E.globalize(b["indices"], spec)
        if fused is not None:
            bm = lambda w: bmfn(packed, gidx, w)  # noqa: E731
            if needs_emb:
                emb, hits = cached_lookup(packed, cache, gidx, lfn,
                                          valid=valid[:, None])
                return fused(net, b, bm, emb), hits, gidx
            return fused(net, b, bm), jnp.zeros((), jnp.int32), gidx
        emb, hits = cached_lookup(packed, cache, gidx, lfn,
                                  valid=valid[:, None])
        return model.head(net, emb, b), hits, gidx

    counter = {"b": 0}
    last: dict = {}

    def _warm(staged) -> None:
        if "a" in last:
            b, valid = last["a"]
            jax.block_until_ready(
                fwd(staged, server.cache, params, b, valid))
    server.warmup_fn = _warm

    def serve_fn(mb: MicroBatch):
        r = counter["b"]
        counter["b"] += 1
        b = {"indices": jnp.asarray(mb.indices),
             "labels": jnp.zeros((mb.indices.shape[0],))}
        if num_dense:
            rr = np.random.default_rng(20_000 + r)
            b["dense"] = jnp.asarray(rr.standard_normal(
                (mb.indices.shape[0], num_dense)).astype(np.float32))
        valid = jnp.asarray(mb.valid)
        last["a"] = (b, valid)
        with obs.span("serve.lookup"):
            args = (server.packed, server.cache, params, b, valid)
            last["call"] = args
            out, hits, gidx = fwd(*args)
            jax.block_until_ready(out)
        server.observe(gidx, int(hits), valid=mb.valid[:, None],
                       count=mb.count)
        return out

    cards = np.asarray(spec.cardinalities, np.int64)
    res = run_microbatched_loop(
        server, serve_fn,
        lambda r: drifting_zipf_batch(cards, 1, r, requests, a=a,
                                      drift=drift, seed=seed)[0],
        requests, serve_batch)
    return res._replace(forward=(fwd, last.get("call")))


def serve_forward(server: OnlineServer, model, spec, params, *,
                  serve_batch: int, requests: int, drift: float = 4.0,
                  num_dense: int = 0, a: float = 1.2, seed: int = 0,
                  fuse_matmul: bool = False) -> LoopResult:
    """ONE micro-batched entry point for every store backend.

    Dispatches on the backend's ``needs_staging`` capability (protocol,
    not ``isinstance``): backends whose misses stage through a host
    buffer (hier) run the staged pipeline, fully device-addressable
    backends (packed, hashed) run the plain cache-first forward.  This
    is what ``launch.serve --online --store-backend B`` drives.
    """
    if server.backend.needs_staging:
        if fuse_matmul:
            raise ValueError("fuse_matmul needs a fully resident "
                             "packed store (backend stages misses)")
        return _serve_forward_staged(
            server, model, spec, params, serve_batch=serve_batch,
            requests=requests, drift=drift, num_dense=num_dense, a=a,
            seed=seed)
    return serve_forward_microbatched(
        server, model, spec, params, serve_batch=serve_batch,
        requests=requests, drift=drift, num_dense=num_dense, a=a,
        seed=seed, fuse_matmul=fuse_matmul)


def serve_forward_hier(server: OnlineServer, model, spec, params,
                       **kw) -> LoopResult:
    """Deprecated shim: ``serve_forward`` dispatches on the backend's
    staging capability — staged serving no longer needs a hier-specific
    entry point."""
    if not server.backend.needs_staging:
        raise ValueError("serve_forward_hier needs an OnlineServer "
                         "built with hier=HierConfig(...)")
    return serve_forward(server, model, spec, params, **kw)


def _serve_forward_staged(server: OnlineServer, model, spec, params, *,
                          serve_batch: int, requests: int,
                          drift: float = 4.0, num_dense: int = 0,
                          a: float = 1.2, seed: int = 0) -> LoopResult:
    """Micro-batched online driver over a staging store backend.

    Same stream and cadence contract as ``serve_forward_microbatched``,
    with the forward split into the staged pipeline per batch:

      1. host: resolve residency per index, dequantize warm/cold
         misses into ONE fixed-shape staging buffer and ship it with a
         single async ``jax.device_put`` (``HierStore.stage``);
         positions the fp32 cache will serve are skipped entirely;
      2. device (jit): cache-first select over [cache rows | staged
         rows | fused hot-store gather] — bit-identical to a fully
         resident ``cached_lookup``;
      3. fold: one vectorised ``observe`` per batch.  Warm/cold misses
         enter the same Eq. 7 EMA as every access, so pressured rows
         climb the ranking and the next re-tier *migrates* them into
         device HBM (``OnlineServer.retier`` -> ``HierStore.migrate``).

    The returned ``LoopResult.stats`` carries the hier counters
    (``warm_hits`` / ``cold_hits`` / ``staged_rows`` / ``migrations`` /
    ``promoted`` / ``demoted`` and ``hier_miss_rate``) alongside the
    cache stats.
    """
    from repro.store.hier import combine_rows

    backend = server.backend
    lfn = server.lookup_fn()
    offsets = np.asarray(spec.offsets(), np.int64)

    @jax.jit
    def fwd(hot, cache, net, b, valid, hot_local, stage_slot, staging):
        gidx = E.globalize(b["indices"], spec)
        rows = combine_rows(hot, hot_local, stage_slot, staging, lfn)
        emb, hits = cache_select(cache, gidx, rows, valid=valid[:, None])
        return model.head(net, emb, b), hits, gidx

    counter = {"b": 0}
    last: dict = {}

    def _warm(staged) -> None:
        if "a" in last:
            b, valid, hot_local, stage_slot, staging = last["a"]
            jax.block_until_ready(
                fwd(staged, server.cache, params, b, valid, hot_local,
                    stage_slot, staging))
    server.warmup_fn = _warm

    def serve_fn(mb: MicroBatch):
        r = counter["b"]
        counter["b"] += 1
        with obs.span("serve.stage"):
            g = mb.indices.astype(np.int64) + offsets[None, :]
            skip = (server.cache_mask[g]
                    if server.cache_mask is not None else None)
            sb = backend.stage_host(g, skip=skip,
                                    valid=mb.valid[:, None])
        b = {"indices": jnp.asarray(mb.indices),
             "labels": jnp.zeros((mb.indices.shape[0],))}
        if num_dense:
            rr = np.random.default_rng(20_000 + r)
            b["dense"] = jnp.asarray(rr.standard_normal(
                (mb.indices.shape[0], num_dense)).astype(np.float32))
        with obs.span("serve.lookup"):
            valid = jnp.asarray(mb.valid)
            last["a"] = (b, valid, sb.hot_local, sb.stage_slot,
                         sb.staging)
            out, hits, gidx = fwd(server.packed, server.cache, params,
                                  b, valid, sb.hot_local, sb.stage_slot,
                                  sb.staging)
            jax.block_until_ready(out)
        server.observe(gidx, int(hits), valid=mb.valid[:, None],
                       count=mb.count)
        return out

    cards = np.asarray(spec.cardinalities, np.int64)
    result = run_microbatched_loop(
        server, serve_fn,
        lambda r: drifting_zipf_batch(cards, 1, r, requests, a=a,
                                      drift=drift, seed=seed)[0],
        requests, serve_batch)
    hier = backend.hier
    if hier is None:
        return result
    lookups = max(server.stats.lookups, 1)
    hstats = hier.stats.as_dict()
    hstats["hier_miss_rate"] = round(
        (hier.stats.warm_hits + hier.stats.cold_hits) / lookups, 4)
    hstats.update(hier.counts())
    return result._replace(stats={**result.stats, **hstats})


def stream_bytes_per_request(tiers, spec, requests: int,
                             drift: float = 4.0, a: float = 1.2,
                             seed: int = 0) -> dict:
    """Mean HBM bytes per single-user request over the drifting-zipf
    benchmark stream, against a fixed per-row tier assignment.

    ``tiers`` is the (V,) Eq. 8 tier vector of the pack being measured
    (``packed_store.packed_tiers`` or ``HierStore.tiers``).  Shared by
    ``benchmarks/qps.py``, ``benchmarks/qps_sharded.py`` and the serve
    driver so every ``bench_qps/v1`` producer computes the contract
    identically: pack-time bytes are the stable cross-sweep quantity
    (the online EMA may drift the *final* assignment).
    """
    from repro.core.tiers import row_bytes

    cards = np.asarray(spec.cardinalities, np.int64)
    idx = np.stack([drifting_zipf_batch(cards, 1, r, requests, a=a,
                                        drift=drift, seed=seed)[0]
                    for r in range(requests)])              # (R, F)
    gidx = np.asarray(idx, np.int64) + np.asarray(
        spec.offsets(), np.int64)[None, :]
    packed_bytes = int(row_bytes(
        np.asarray(tiers)[gidx.reshape(-1)], spec.dim).sum())
    return {
        "bytes_per_request_fp32": int(gidx.size * spec.dim * 4
                                      // requests),
        "bytes_per_request_packed": packed_bytes // requests,
    }
