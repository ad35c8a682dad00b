"""Online serving state: live priority EMA + hot cache + delta re-tier.

``OnlineServer`` owns the live, traffic-adaptive state around ONE
``store.api.EmbeddingStore`` backend (packed / hier / hashed — built
via ``store.build`` or passed as ``backend=``):

  * the backend: payload arrays, placement, lookup kernels, priority
    vector and re-tier machinery, all behind the protocol — the
    request path below contains NO backend branches,
  * the hot-row cache (``serve.cache``), rebuilt after every re-tier,
  * ``ServeStats`` counters (requests / lookups / hits / retiers /
    rows_moved).

Per request the driver either calls ``server.lookup(indices)`` (eager
convenience: cache-first gather + priority fold + periodic re-tier) or
runs its own jitted forward over ``server.packed`` / ``server.cache``
and then calls ``server.observe(indices, hits)``.  The second form is
what ``repro.launch.serve --online`` does — a re-tier swaps in payload
arrays with *new shapes*, so jit recompiles exactly at re-tier
boundaries and nowhere else.

Re-tiering dispatches through the backend: ``repack_delta`` for the
flat store, ``HierStore.migrate`` across levels, a cache-only refresh
for the hashed pool (shared slots cannot re-tier).

With ``OnlineConfig.retier_async`` the re-tier instead runs as a
**shadow build** (``serve.shadow``): the boundary request only opens the
shadow, every subsequent request advances it by a bounded row budget,
and the finished generation is device-staged (with the driver's jitted
forward pre-compiled on a warm-up thread) before one atomic pointer
swap — the state machine is build -> chunk -> [verify ->] swap, with
``discard_shadow`` as the crash-before-swap exit.  The swapped result is
bit-identical to a synchronous re-tier at the snapshot fold state.

Back-compat: the ``hier=HierConfig(...)`` keyword and the
``store``/``cfg`` positional pair are thin shims over
``store.build("hier"|"packed", ...)``; ``server.store`` /
``server.host_packed`` / ``server.packed`` / ``server.hier`` proxy the
backend's state so existing callers (and the shadow commit protocol)
keep working unchanged.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import NamedTuple

import jax
import numpy as np

from repro import obs
from repro.core.priority import PriorityConfig

Array = jax.Array


class OnlineConfig(NamedTuple):
    cache_rows: int = 0      # top-K fp32 hot rows (0 = cache disabled)
    retier_every: int = 0    # requests between delta re-tiers (0 = never)
    priority: PriorityConfig | None = None  # None -> FQuantConfig's
    retier_async: bool = False    # shadow-build re-tiers off the request
                                  # path instead of synchronous repacks
    shadow_rows_per_step: int = 512  # shadow build budget per live
                                     # request (rows; scaled by batch)
    verify_swap: bool = False     # O(V) bit-identity check vs pack() at
                                  # the snapshot fold state, every swap


@dataclasses.dataclass
class ServeStats:
    requests: int = 0
    lookups: int = 0       # individual VALID row lookups served
                           # (micro-batch padding excluded)
    hits: int = 0          # of which from the hot cache
    retiers: int = 0
    rows_moved: int = 0    # tier-crossing rows migrated by repack_delta
    retier_seconds: float = 0.0  # wall time inside retier()/migrate —
                                 # the loops diff this per request to
                                 # attribute tail latency (always on:
                                 # one perf_counter pair per re-tier)
    shadow_builds: int = 0   # shadow generations opened
    shadow_chunks: int = 0   # bounded build steps taken on request path
    swaps: int = 0           # shadow generations atomically swapped in

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def as_dict(self) -> dict:
        return {"requests": self.requests, "lookups": self.lookups,
                "hits": self.hits, "cache_hit_rate": round(self.hit_rate, 4),
                "retiers": self.retiers, "rows_moved": self.rows_moved,
                "shadow_builds": self.shadow_builds, "swaps": self.swaps}


class OnlineServer:
    """Mutable serving-side owner of an EmbeddingStore backend, the hot
    cache and the serve-side priority fold."""

    def __init__(self, store=None, cfg=None,
                 online: OnlineConfig = OnlineConfig(), *, mesh=None,
                 axis: str = "model", hier=None, backend=None):
        """``backend`` (a ``store.api.EmbeddingStore``) is the new
        construction path: ``OnlineServer(backend=store.build("hashed",
        hs, hcfg), online=...)``.  The legacy forms build one: the
        ``(store, cfg)`` QATStore pair builds ``"packed"``, and
        ``hier=HierConfig(...)`` builds ``"hier"`` (deprecated shims —
        both dispatch through ``store.build``)."""
        if backend is None:
            from repro.store import build
            if store is None or cfg is None:
                raise ValueError("OnlineServer needs either backend= "
                                 "or the (store, cfg) QATStore pair")
            if hier is not None:
                backend = build("hier", store, cfg, hier, mesh=mesh,
                                axis=axis)
            else:
                backend = build("packed", store, cfg, mesh=mesh,
                                axis=axis)
        self.backend = backend
        self.online = online
        self.mesh = backend.mesh
        self.axis = backend.axis
        self.stats = ServeStats()
        # shadow re-tier state (OnlineConfig.retier_async)
        self.shadow = None            # active ShadowRepack/ShadowMigrate
        self._retier_pending = False  # boundary crossed while building
        self._staged = None           # device-placed shadow, pre-swap
        self.warmup_fn = None         # registered by the loop drivers:
                                      # fn(staged_packed) pre-compiles
                                      # the jitted forward for the new
                                      # payload shapes
        self._warmup = None           # in-flight staging thread
        self._stage_err = None        # staging/verify failure, raised at swap
        self._shadow_t0 = 0.0         # perf_counter at begin_retier —
                                      # serve.shadow.build_us measures
                                      # the whole plan->swap lifecycle
        self._rebuild_cache()
        if online.retier_async:
            self.backend.prewarm_retier(online.shadow_rows_per_step)

    # -- backend state proxies (back-compat + shadow commit protocol) --

    @property
    def store(self):
        """The backend's QATStore (None for hashed)."""
        return self.backend.store

    @store.setter
    def store(self, value) -> None:
        self.backend.store = value

    @property
    def cfg(self):
        """The backend's FQuantConfig (None for hashed)."""
        return self.backend.cfg

    @property
    def host_packed(self):
        return self.backend.host_packed

    @host_packed.setter
    def host_packed(self, value) -> None:
        self.backend.host_packed = value

    @property
    def packed(self):
        """The placed device store the jitted forward closes over."""
        return self.backend.device_store

    @packed.setter
    def packed(self, value) -> None:
        self.backend.device_store = value

    @property
    def hier(self):
        return self.backend.hier

    def _place(self) -> None:
        self.backend.place()

    def lookup_fn(self):
        """Miss-path gather matching the placement of ``self.packed``
        (protocol dispatch: fused dequant-bag / sharded / hashed)."""
        return self.backend.lookup_fn()

    def bag_matmul_fn(self):
        """Fused bag->first-matmul matching the placement of
        ``self.packed`` (packed backends only — hier/hashed raise)."""
        return self.backend.bag_matmul_fn()

    def _rebuild_cache(self) -> None:
        self.cache, self.cache_mask = self.backend.build_cache(
            self.online.cache_rows)
        if obs.enabled():
            self._export_gauges()

    def _export_gauges(self) -> None:
        """Occupancy gauges for the current placement (docs/
        observability.md) — the backend names its own gauge set.
        Refreshed after every (re)placement — build, retier, migrate."""
        obs.gauge("serve.cache.rows", float(self.cache.capacity))
        for name, value in self.backend.occupancy().items():
            obs.gauge(name, value)

    # -- request path --------------------------------------------------

    def lookup(self, indices: Array, *, valid: Array | None = None,
               count: int | None = None) -> Array:
        """Eager cache-first gather + traffic fold.  int (...,) -> fp32
        (..., D) through the backend's cached request path (for exact
        backends, bit-identical to a fresh full pack of the current
        store).

        ``valid`` (bool, broadcastable to ``indices``) masks padded
        micro-batch slots out of the hit/lookup accounting AND the
        priority fold — without it a padded batch served through this
        eager path would dilute the cache hit-rate denominator and
        feed phantom row-0 traffic into the Eq. 7 EMA.  ``count`` is
        the number of live requests in the batch (defaults to 1, the
        single-request contract).
        """
        count = 1 if count is None else count
        rows, hits = self.backend.cached_lookup(
            self.cache, self.cache_mask, indices, valid=valid)
        self.observe(indices, int(hits), valid=valid, count=count)
        return rows

    def observe(self, indices: Array, hits: int | None = None, *,
                valid: Array | None = None, count: int = 1) -> bool:
        """Fold one served batch into the online state — vectorised.

        Updates the priority EMA with the served indices (Eq. 7, c- only
        — labels don't exist at lookup time), bumps counters, and when
        the ``retier_every`` request boundary is crossed runs an
        incremental re-tier.  Returns True when the packed store was
        repacked (payload shapes may have changed — re-fetch
        ``server.packed`` / ``server.cache``).

        Micro-batched serving passes one *fused* batch per call:
        ``count`` live requests folded in one vectorised update, with
        ``valid`` (bool, broadcastable to ``indices``) masking the
        padded slots out of both the priority fold and the lookup
        counters.  The re-tier fires when the request counter crosses a
        multiple of ``retier_every`` — exactly the per-request cadence
        when ``count <= retier_every``.  A single call whose ``count``
        spans SEVERAL boundaries coalesces them into ONE re-tier (the
        store cannot re-tier mid-forward), so with
        ``serve_batch > retier_every`` the adaptation rate is once per
        micro-batch, not once per boundary.

        Traced as span ``serve.fold``, keyed by the request count
        before the batch, over ``serve.fold.mask`` (valid-mask build
        and upload), ``serve.fold.priority`` (the eager Eq. 7 chain)
        and ``serve.fold.retier`` (boundary check, re-tier, shadow
        tick).
        """
        import jax.numpy as jnp
        with obs.span("serve.fold", key=self.stats.requests):
            before = self.stats.requests
            self.stats.requests += count
            with obs.span("serve.fold.mask"):
                if valid is None:
                    n_lookups = int(np.prod(np.shape(indices)))
                    vmask = None
                else:
                    # count host-side (valid is the batcher's numpy
                    # mask) — no device round-trip inside the timed
                    # serving path
                    vnp = np.broadcast_to(np.asarray(valid, bool),
                                          np.shape(indices))
                    n_lookups = int(vnp.sum())
                    vmask = jnp.asarray(vnp)
            self.stats.lookups += n_lookups
            if hits is not None:
                self.stats.hits += int(hits)
            if obs.enabled():
                obs.inc("serve.requests", count)
                obs.inc("serve.lookups", n_lookups)
                if hits is not None:
                    obs.inc("serve.cache.hits", int(hits))
                obs.gauge("serve.cache.hit_rate", self.stats.hit_rate)
            pcfg = self.online.priority or self._default_priority_cfg()
            with obs.span("serve.fold.priority"):
                self.backend.fold_priority(indices, pcfg, valid=vmask)
            with obs.span("serve.fold.retier"):
                if self.online.retier_every:
                    re = self.online.retier_every
                    if self.stats.requests // re > before // re:
                        if not self.online.retier_async:
                            return self.retier()
                        self._retier_pending = True
                if self.online.retier_async:
                    return self._shadow_tick(count)
                return False

    def _default_priority_cfg(self) -> PriorityConfig:
        cfg = self.backend.cfg
        if cfg is not None and cfg.priority is not None:
            return cfg.priority
        return PriorityConfig()

    # -- shadow re-tier (async) ----------------------------------------

    def begin_retier(self) -> bool:
        """Open a shadow build against the current fold state.

        The backend snapshots its own fold state (the ``QATStore`` is
        an immutable NamedTuple — priority folds ``_replace`` into a
        NEW store, so capturing the reference IS the snapshot): the
        shadow's re-tier decision is frozen while live folds keep
        drifting the backend forward (the next build picks them up,
        same as a re-tier that ran at the boundary).  Returns True when
        a shadow was opened; a backend with nothing to move matches the
        synchronous no-move path (count the re-tier, refresh the cache,
        no swap).
        """
        if self.shadow is not None:     # one generation at a time
            self._retier_pending = True
            return False
        rows = self.online.shadow_rows_per_step
        self._shadow_t0 = time.perf_counter()
        with obs.span("serve.shadow.plan"):
            sh = self.backend.begin_retier(rows)
        if sh is None:
            self.stats.retiers += 1
            self._rebuild_cache()
            return False
        self.shadow = sh
        self.stats.shadow_builds += 1
        obs.inc("serve.shadow.builds", 1)
        obs.gauge("serve.shadow.in_flight", 1.0)
        return True

    def _shadow_tick(self, count: int = 1) -> bool:
        """One request's worth of shadow progress: open a pending
        build, advance it by the per-step row budget, stage / swap when
        ready.  Returns True when the live store was swapped (payload
        shapes may have changed — re-fetch ``server.packed``)."""
        if self.shadow is None and self._retier_pending:
            self._retier_pending = False
            self.begin_retier()
        if self.shadow is None:
            return False
        with obs.timeblock("serve.retier") as tb:
            swapped = self._shadow_advance(count)
        self.stats.retier_seconds += tb.seconds
        return swapped

    def _shadow_advance(self, count: int) -> bool:
        sh = self.shadow
        if not sh.staged:
            with obs.span("serve.shadow.chunk"):
                sh.step(self.online.shadow_rows_per_step
                        * max(int(count), 1))
            self.stats.shadow_chunks += 1
            if obs.enabled():
                obs.gauge("serve.shadow.lag_rows",
                          float(sh.remaining_rows))
            if sh.staged:
                # built on this very tick: stage the device transfer
                # (and the jit warm-up) now, swap on a later tick so
                # neither lands on a serving request
                self._begin_staging()
            return False
        if self._warmup is None:
            self._begin_staging()
            return False
        if self._warmup.is_alive():
            return False
        return self._swap()

    def _begin_staging(self) -> None:
        """Kick off the staging thread: device placement, the optional
        bit-identity verify, and the forward-recompile warm-up all run
        off the serving thread (XLA compilation and execution release
        the GIL, and the jit cache is shared) — the swap tick that
        follows is a pointer flip, not a ~100x-p50 stall."""
        sh, fn = self.shadow, self.warmup_fn
        verify = self.online.verify_swap
        # the staging thread inherits the serving thread's registry
        # binding (replica namespaces are thread-local), so its spans
        # land next to the rest of this server's metrics
        reg = obs.get_registry()

        def _stage() -> None:
            with obs.bind(reg):
                try:
                    with obs.span("serve.shadow.stage"):
                        staged = sh.place(self.mesh, self.axis)
                        if verify:
                            with obs.span("serve.shadow.verify"):
                                sh.verify()
                    self._staged = staged
                except Exception as e:          # surfaced by _swap
                    self._stage_err = e
                    return
                if fn is not None:
                    try:
                        with obs.span("serve.shadow.warmup"):
                            fn(staged)
                    except Exception:
                        pass    # a failed warm-up only costs a recompile
        self._warmup = threading.Thread(target=_stage, daemon=True)
        self._warmup.start()

    def _swap(self) -> bool:
        """Atomic generation flip: commit the staged shadow and rebuild
        the hot cache.  The only point where live serving state
        changes.  A verify failure on the staging thread surfaces here
        — the shadow is discarded and the live store stays as-is."""
        if self._stage_err is not None:
            err = self._stage_err
            self.discard_shadow()
            raise err
        with obs.span("serve.shadow.swap"):
            moved = self.shadow.commit(self, self._staged)
        self.shadow = None
        self._staged = None
        self._warmup = None
        self.stats.retiers += 1
        self.stats.swaps += 1
        self.stats.rows_moved += int(moved)
        obs.inc("serve.retier.rows_moved", int(moved))
        obs.inc("serve.shadow.swaps", 1)
        # whole-lifecycle build latency (plan -> chunks -> stage ->
        # swap) and the in-flight marker the fleet plane reads to
        # detect co-scheduled swaps across replicas
        obs.observe("serve.shadow.build_us",
                    (time.perf_counter() - self._shadow_t0) * 1e6)
        obs.gauge("serve.shadow.in_flight", 0.0)
        self._rebuild_cache()
        return True

    def drain_shadow(self) -> bool:
        """Synchronously finish any in-flight (or pending) shadow and
        swap it in — loop teardown and verification paths.  Returns
        True when a swap happened."""
        if self.shadow is None and self._retier_pending:
            self._retier_pending = False
            self.begin_retier()
        if self.shadow is None:
            return False
        with obs.timeblock("serve.retier") as tb:
            while not self.shadow.staged:
                self.shadow.step(1 << 30)
                self.stats.shadow_chunks += 1
            if self._warmup is None:
                self._begin_staging()
            self._warmup.join()
            out = self._swap()
        self.stats.retier_seconds += tb.seconds
        return out

    def discard_shadow(self) -> None:
        """Crash-before-swap: drop the shadow generation entirely.  The
        live store (and any cold-shard mmaps) is untouched — serving
        continues on the old generation as if the build never started.
        """
        if self._warmup is not None and self._warmup.is_alive():
            # let the staging thread finish its XLA work before the
            # shadow objects it references go away (an interpreter
            # exiting under a live compile aborts the process)
            self._warmup.join()
        if self.shadow is not None:
            self.shadow.discard()
            obs.gauge("serve.shadow.in_flight", 0.0)
        self.shadow = None
        self._staged = None
        self._warmup = None
        self._stage_err = None
        self._retier_pending = False

    # -- incremental re-tier -------------------------------------------

    def retier(self) -> bool:
        """Backend re-tier + hot cache rebuild.

        Flat store: delta-repack tier-crossing rows — equivalent to
        (but much cheaper than) ``pack(self.store, self.cfg)`` followed
        by re-placement.  Hier: ``HierStore.migrate`` re-tiers crossed
        rows AND moves rows between HBM / host RAM / disk by their live
        priority rank.  Hashed: cache refresh only (pool slots are
        shared, nothing migrates).  Returns True if anything changed.

        Wall time accumulates into ``stats.retier_seconds`` (always —
        the serve loops attribute tail latency from it) and into the
        ``serve.retier_us`` histogram when metrics are on.

        A synchronous re-tier supersedes any in-flight shadow build:
        the shadow is discarded (its snapshot is stale next to the
        store this call re-tiers from) and the live store repacked in
        one step.
        """
        if self.shadow is not None or self._retier_pending:
            self.discard_shadow()
        with obs.timeblock("serve.retier") as tb:
            res = self.backend.retier()
            self.stats.retiers += 1
            if res["rows_moved"]:
                self.stats.rows_moved += int(res["rows_moved"])
                obs.inc("serve.retier.rows_moved",
                        int(res["rows_moved"]))
            self._rebuild_cache()
        self.stats.retier_seconds += tb.seconds
        return bool(res["changed"])
