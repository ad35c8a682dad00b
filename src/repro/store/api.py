"""`EmbeddingStore` protocol + backend registry: ONE serving surface.

Every embedding backend — the flat tier-partitioned ``PackedStore``
(``"packed"``), the three-level HBM/host/disk ``HierStore``
(``"hier"``) and the ROBE-style compositional ``HashedStore``
(``"hashed"``) — answers the same protocol, so ``serve.online`` /
``serve.loop`` / the launch drivers dispatch through one object with
NO backend ``isinstance`` branches on the request path:

  identity      kind, vocab, dim, nbytes(), live_counts()
  lookups       lookup(idx), bag_lookup(idx, w) — eager, uncached
  serving       place() / device_store (the pytree the jitted forward
                closes over), lookup_fn() / bag_matmul_fn() (pure,
                jit-traceable), stage_host(...) for backends whose
                misses stage through a host buffer, cached_lookup(...)
                (the eager cache-first request path),
                gather_fp32_host(ids) + build_cache(k) (hot-row cache
                rebuilds), occupancy() (gauges)
  adaptation    priority / fold_priority(idx, pcfg) (Eq. 7 serve-side
                fold), retier() (synchronous), begin_retier(rows)
                (shadow generation or None when there is nothing to
                move), prewarm_retier(rows)
  persistence   snapshot_manifest() -> kind-tagged pytree;
                ``from_manifest`` rebuilds the backend from it (the
                ``ckpt.CheckpointManager`` store round-trip)

Registry: ``register_backend(name, factory)`` + ``build(name, **cfg)``
— third-party backends plug in without touching the serving stack.

Capability matrix (docs/storage.md#backend-protocol):

  backend   exact?                  memory bound        retier
  packed    bit-exact per tier      O(V) payload bytes  repack_delta
  hier      bit-exact per tier      per-level budgets   migrate levels
  hashed    approximate (hashing)   O(S*Z) pool bytes   cache-only
"""

from __future__ import annotations

from typing import Any, Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.priority import PriorityConfig, serve_update

Array = jax.Array


@runtime_checkable
class EmbeddingStore(Protocol):
    """Structural protocol every backend satisfies (see module doc).

    Only the members the serving stack actually dispatches on are
    listed; backends are free to carry extra state (``host_packed``,
    ``hier``, ...) that backend-aware tools reach for explicitly.
    """
    kind: str

    @property
    def vocab(self) -> int: ...
    @property
    def dim(self) -> int: ...
    @property
    def priority(self) -> Array: ...
    def nbytes(self) -> int: ...
    def live_counts(self) -> dict: ...
    def lookup(self, indices) -> Array: ...
    def bag_lookup(self, indices, weights=None) -> Array: ...
    def fold_priority(self, indices, pcfg, valid=None) -> None: ...
    def begin_retier(self, chunk_rows: int): ...
    def retier(self) -> dict: ...
    def snapshot_manifest(self) -> dict: ...


# --------------------------------------------------------------------- packed


class PackedBackend:
    """Flat tier-partitioned store: the QATStore is authoritative, the
    host PackedStore is its serving pack, ``device_store`` the placed
    copy (row-sharded under a mesh)."""

    kind = "packed"

    def __init__(self, store, cfg, *, mesh=None, axis: str = "model",
                 host_packed=None):
        from repro.core.packed_store import pack
        self.store = store          # QATStore (table + Eq. 7 priority)
        self.cfg = cfg              # FQuantConfig
        self.mesh = mesh
        self.axis = axis
        self.hier = None
        if host_packed is None:
            with obs.timeblock("store.pack"):
                host_packed = pack(store, cfg)
        self.host_packed = host_packed
        self.device_store = None
        self.place()

    # -- identity ------------------------------------------------------

    @property
    def vocab(self) -> int:
        return int(self.host_packed.vocab)

    @property
    def dim(self) -> int:
        return int(self.host_packed.dim)

    @property
    def priority(self) -> Array:
        return self.store.priority

    def nbytes(self) -> int:
        return int(self.host_packed.nbytes())

    def live_counts(self) -> dict:
        from repro.core.packed_store import packed_tiers
        counts = np.bincount(
            np.asarray(packed_tiers(self.host_packed)).reshape(-1),
            minlength=3)
        return {"int8": int(counts[0]), "half": int(counts[1]),
                "fp32": int(counts[2])}

    # -- serving surface -----------------------------------------------

    def place(self) -> None:
        from repro.dist.packed import place_packed
        with obs.timeblock("store.place"):
            self.device_store = place_packed(self.host_packed, self.mesh,
                                             self.axis)

    def lookup_fn(self) -> Callable:
        if self.mesh is None:
            from repro.core.packed_store import lookup_fused
            return lookup_fused
        from repro.dist.packed import sharded_lookup
        mesh, axis = self.mesh, self.axis
        return lambda pk, idx: sharded_lookup(pk, idx, mesh=mesh,
                                              axis=axis)

    def bag_matmul_fn(self) -> Callable:
        if self.mesh is None:
            from repro.core.packed_store import bag_matmul
            return bag_matmul
        from repro.dist.packed import sharded_bag_matmul
        mesh, axis = self.mesh, self.axis
        return lambda pk, idx, w: sharded_bag_matmul(
            pk, idx, w, mesh=mesh, axis=axis)

    needs_staging = False

    def stage_host(self, gidx, *, skip=None, valid=None):
        return None

    def cached_lookup(self, cache, cache_mask, indices,
                      valid=None) -> tuple[Array, Array]:
        from repro.serve.cache import cached_lookup
        return cached_lookup(
            self.device_store, cache, indices, self.lookup_fn(),
            valid=None if valid is None else jnp.asarray(valid))

    def gather_fp32_host(self, ids) -> np.ndarray:
        from repro.core import packed_store as ps
        rows = ps.lookup(self.host_packed,
                         jnp.asarray(np.asarray(ids), jnp.int32))
        return np.asarray(jax.device_get(rows), np.float32)

    def build_cache(self, cache_rows: int):
        from repro.serve.cache import build_cache
        cache = build_cache(self.host_packed, self.store.priority,
                            cache_rows)
        return cache, None

    def occupancy(self) -> dict:
        out = {"store.packed_bytes": float(self.host_packed.nbytes())}
        for name, n in self.live_counts().items():
            out[f"store.tier_rows_{name}"] = float(n)
        return out

    # -- lookups (eager) -----------------------------------------------

    def lookup(self, indices) -> Array:
        return self.lookup_fn()(self.device_store,
                                jnp.asarray(indices))

    def bag_lookup(self, indices, weights=None) -> Array:
        from repro.kernels.dequant_bag.ops import packed_bag_lookup
        return packed_bag_lookup(self.device_store,
                                 jnp.asarray(indices), weights)

    # -- adaptation ----------------------------------------------------

    def fold_priority(self, indices, pcfg: PriorityConfig,
                      valid=None) -> None:
        self.store = self.store._replace(
            priority=serve_update(self.store.priority, indices, pcfg,
                                  valid=valid))

    def prewarm_retier(self, chunk_rows: int) -> None:
        from repro.core.packed_store import quantize_rows
        dim = self.host_packed.payload32.shape[-1]
        quantize_rows(np.zeros((3, dim), np.float32), np.arange(3),
                      np.arange(3), self.cfg, pad_to=chunk_rows)

    def begin_retier(self, chunk_rows: int):
        from repro.serve.shadow import ShadowRepack
        sh = ShadowRepack(self.host_packed, self.store, self.cfg,
                          chunk_rows=chunk_rows)
        return sh if sh.moved else None

    def retier(self) -> dict:
        from repro.core.packed_store import packed_tiers, repack_delta
        from repro.core.qat_store import current_tiers
        from repro.core.tiers import tier_crossings
        old = packed_tiers(self.host_packed)
        new = np.asarray(current_tiers(self.store, self.cfg))
        changed, _ = tier_crossings(old, new)
        if changed.size:
            self.host_packed = repack_delta(self.host_packed,
                                            self.store, self.cfg,
                                            changed)
            self.place()
        return {"rows_moved": int(changed.size),
                "changed": bool(changed.size)}

    # -- persistence ---------------------------------------------------

    def snapshot_manifest(self) -> dict:
        return {"kind": "packed_store/v1",
                "packed": self.host_packed,
                "priority": self.store.priority}

    @classmethod
    def from_manifest(cls, tree: dict, *, store=None, cfg=None,
                      mesh=None, axis: str = "model"):
        """Rebuild from ``snapshot_manifest`` output.  ``store``/``cfg``
        re-attach the training-side state the pack was made from (the
        pack itself is the restored artifact of record)."""
        from repro.core.packed_store import PackedStore
        from repro.core.qat_store import QATStore
        packed = tree["packed"]
        if not isinstance(packed, PackedStore):
            packed = PackedStore(*packed)
        if store is None:
            from repro.core.packed_store import unpack
            store = QATStore(table=jnp.asarray(unpack(packed)),
                             priority=jnp.asarray(tree["priority"]))
        else:
            store = store._replace(
                priority=jnp.asarray(tree["priority"]))
        return cls(store, cfg, mesh=mesh, axis=axis,
                   host_packed=packed)


# ----------------------------------------------------------------------- hier


class HierBackend(PackedBackend):
    """Three-level store: device HBM holds the priority-hot rows, host
    RAM the warm spill, mmap'd cold shards the rest.  Misses stage
    through a fixed-shape host buffer (``needs_staging``)."""

    kind = "hier"

    def __init__(self, store, cfg, hier_cfg=None, *, mesh=None,
                 axis: str = "model", hier=None):
        from repro.store.hier import build_hier
        self.store = store
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.host_packed = None
        self.hier = (hier if hier is not None
                     else build_hier(store, cfg, hier_cfg, mesh=mesh,
                                     axis=axis))
        self.device_store = None
        self.place()

    @property
    def vocab(self) -> int:
        return int(self.hier.vocab)

    @property
    def dim(self) -> int:
        return int(self.hier.dim)

    def nbytes(self) -> int:
        return int(sum(self.hier.nbytes().values()))

    def live_counts(self) -> dict:
        return dict(self.hier.counts())

    def place(self) -> None:
        self.device_store = self.hier.hot_dev

    def bag_matmul_fn(self) -> Callable:
        raise ValueError("fused bag->matmul serving requires a fully "
                         "resident packed store (no hier)")

    needs_staging = True

    def stage_host(self, gidx, *, skip=None, valid=None):
        return self.hier.stage(gidx, skip=skip, valid=valid)

    def cached_lookup(self, cache, cache_mask, indices,
                      valid=None) -> tuple[Array, Array]:
        from repro.serve.cache import cache_select
        from repro.store.hier import combine_rows
        g = np.asarray(indices, np.int64)
        skip = cache_mask[g] if cache_mask is not None else None
        sb = self.hier.stage(g, skip=skip, valid=valid)
        rows = combine_rows(self.hier.hot_dev, sb.hot_local,
                            sb.stage_slot, sb.staging,
                            self.lookup_fn())
        return cache_select(
            cache, jnp.asarray(indices), rows,
            valid=None if valid is None else jnp.asarray(valid))

    def gather_fp32_host(self, ids) -> np.ndarray:
        return np.asarray(self.hier.gather_fp32_host(np.asarray(ids)),
                          np.float32)

    def build_cache(self, cache_rows: int):
        from repro.serve.cache import cache_from_rows, empty_cache
        k = int(min(cache_rows, self.hier.vocab))
        if k <= 0:
            cache = empty_cache(self.hier.vocab, self.hier.dim)
        else:
            _, ids = jax.lax.top_k(self.store.priority, k)
            ids = np.asarray(ids)
            cache = cache_from_rows(
                jnp.asarray(ids, jnp.int32),
                jnp.asarray(self.hier.gather_fp32_host(ids)),
                self.hier.vocab)
        # host membership mask: staging skips rows the fp32 cache
        # serves anyway (no double traffic)
        mask = np.zeros(self.hier.vocab, bool)
        ids = np.asarray(cache.ids)
        if ids.size:
            mask[ids] = True
        return cache, mask

    def occupancy(self) -> dict:
        out = {}
        for lev, n in self.hier.counts().items():
            out[f"store.{lev}"] = float(n)        # hot/warm/cold rows
        for lev, nb in self.hier.nbytes().items():
            out[f"store.{lev}_bytes"] = float(nb)
        tiers = np.bincount(
            np.asarray(self.hier.tiers).reshape(-1), minlength=3)
        for name, n in zip(("int8", "half", "fp32"), tiers):
            out[f"store.tier_rows_{name}"] = float(n)
        return out

    def prewarm_retier(self, chunk_rows: int) -> None:
        from repro.core.packed_store import quantize_rows
        quantize_rows(np.zeros((3, self.hier.dim), np.float32),
                      np.arange(3), np.arange(3), self.cfg,
                      pad_to=chunk_rows)

    def begin_retier(self, chunk_rows: int):
        from repro.serve.shadow import ShadowMigrate
        return ShadowMigrate(self.hier, self.store, self.cfg,
                             chunk_rows=chunk_rows)

    def retier(self) -> dict:
        moved = self.hier.migrate(self.store, self.cfg)
        self.place()
        return {"rows_moved": int(moved["crossed"]),
                "changed": bool(moved["promoted"] or moved["demoted"]
                                or moved["crossed"])}

    def lookup(self, indices) -> Array:
        from repro.store.hier import hier_lookup
        return hier_lookup(self.hier, jnp.asarray(indices))

    def bag_lookup(self, indices, weights=None) -> Array:
        from repro.store.hier import hier_bag_lookup
        idx = jnp.asarray(indices)
        b, k = idx.shape
        seg = jnp.repeat(jnp.arange(b, dtype=jnp.int32), k)
        w = None if weights is None else jnp.asarray(weights).reshape(-1)
        return hier_bag_lookup(self.hier, np.asarray(idx).reshape(-1),
                               seg, b, w)

    def snapshot_manifest(self) -> dict:
        return self.hier.state_tree()

    @classmethod
    def from_manifest(cls, tree: dict, *, store=None, cfg=None,
                      hier_cfg=None, mesh=None, axis: str = "model"):
        """Rebuild the three-level store from ``state_tree`` output.
        Cold shards live on disk already (addressed by
        ``hier_cfg.store_dir``); ``store``/``cfg`` re-attach the
        training-side state for re-tiering."""
        from repro.core.packed_store import PackedStore
        from repro.store.hier import HierStore
        from repro.store.manifest import ColdShards

        def as_packed(x):
            return x if isinstance(x, PackedStore) else PackedStore(*x)

        cold_ids = np.asarray(tree["cold_ids"])
        cold = None
        if cold_ids.size:
            if hier_cfg is None or hier_cfg.store_dir is None:
                raise ValueError("cold shards need hier_cfg.store_dir")
            cold = ColdShards(hier_cfg.store_dir)
        hier = HierStore(
            cfg=hier_cfg, dim=int(tree["dim"]),
            level=np.asarray(tree["level"]),
            slot=np.asarray(tree["slot"]),
            tiers=np.asarray(tree["tiers"]),
            hot_ids=np.asarray(tree["hot_ids"]),
            warm_ids=np.asarray(tree["warm_ids"]),
            cold_ids=cold_ids,
            hot_host=as_packed(tree["hot"]),
            warm=as_packed(tree["warm"]),
            cold=cold, mesh=mesh, axis=axis)
        hier.place()
        return cls(store, cfg, mesh=mesh, axis=axis, hier=hier)


# --------------------------------------------------------------------- hashed


class HashedBackend:
    """ROBE-style compositional store: rows materialize on the fly from
    the shared chunk pool through the fused ``hashed_gather`` kernel.
    Memory is bounded by the pool (independent of vocab); re-tiering
    reduces to refreshing the priority-driven hot-row fp32 cache."""

    kind = "hashed"

    def __init__(self, hs, hcfg, *, mesh=None, axis: str = "model"):
        self.hs = hs                # store.hashed.HashedStore
        self.hcfg = hcfg            # store.hashed.HashedConfig
        self.mesh = mesh
        self.axis = axis
        self.cfg = None             # no FQuantConfig: pool is the pack
        self.hier = None
        self.host_packed = None
        self.store = None           # no QATStore behind this backend
        self.device_store = None
        self.place()

    @property
    def vocab(self) -> int:
        return int(self.hcfg.vocab)

    @property
    def dim(self) -> int:
        return int(self.hcfg.dim)

    @property
    def priority(self) -> Array:
        return self.hs.priority

    def nbytes(self) -> int:
        return int(self.hs.nbytes())

    def live_counts(self) -> dict:
        return {"pool_slots": int(self.hs.num_slots),
                "virtual_rows": int(self.hcfg.vocab)}

    # -- serving surface -----------------------------------------------

    def place(self) -> None:
        if self.mesh is None:
            self.device_store = self.hs._replace(
                pool=jax.device_put(self.hs.pool),
                pool_scale=jax.device_put(self.hs.pool_scale))
        else:
            from repro.dist.hashed import shard_hashed
            self.device_store = shard_hashed(self.hs, self.mesh,
                                             self.axis)

    def lookup_fn(self) -> Callable:
        from repro.store.hashed import hashed_lookup
        hcfg = self.hcfg
        if self.mesh is None:
            return lambda hsd, idx: hashed_lookup(hsd, hcfg, idx)
        from repro.dist.hashed import sharded_hashed_lookup
        mesh, axis = self.mesh, self.axis
        return lambda hsd, idx: sharded_hashed_lookup(
            hsd, hcfg, idx, mesh=mesh, axis=axis)

    def bag_matmul_fn(self) -> Callable:
        raise ValueError("fused bag->matmul serving requires a fully "
                         "resident packed store (hashed rows "
                         "materialize on the fly)")

    needs_staging = False

    def stage_host(self, gidx, *, skip=None, valid=None):
        return None

    def cached_lookup(self, cache, cache_mask, indices,
                      valid=None) -> tuple[Array, Array]:
        from repro.serve.cache import cached_lookup
        return cached_lookup(
            self.device_store, cache, indices, self.lookup_fn(),
            valid=None if valid is None else jnp.asarray(valid))

    def gather_fp32_host(self, ids) -> np.ndarray:
        from repro.store.hashed import gather_rows_host
        return gather_rows_host(self.hs, self.hcfg, ids)

    def build_cache(self, cache_rows: int):
        from repro.serve.cache import cache_from_rows, empty_cache
        k = int(min(cache_rows, self.vocab))
        if k <= 0:
            return empty_cache(self.vocab, self.dim), None
        _, ids = jax.lax.top_k(self.hs.priority, k)
        ids = np.asarray(ids)
        cache = cache_from_rows(
            jnp.asarray(ids, jnp.int32),
            jnp.asarray(self.gather_fp32_host(ids)), self.vocab)
        return cache, None

    def occupancy(self) -> dict:
        return {"store.pool_bytes": float(self.hs.nbytes()),
                "store.pool_slots": float(self.hs.num_slots)}

    # -- lookups (eager) -----------------------------------------------

    def lookup(self, indices) -> Array:
        from repro.store.hashed import hashed_lookup
        return hashed_lookup(self.hs, self.hcfg, jnp.asarray(indices))

    def bag_lookup(self, indices, weights=None) -> Array:
        from repro.store.hashed import hashed_bag_lookup
        return hashed_bag_lookup(self.hs, self.hcfg,
                                 jnp.asarray(indices), weights)

    # -- adaptation ----------------------------------------------------

    def fold_priority(self, indices, pcfg: PriorityConfig,
                      valid=None) -> None:
        self.hs = self.hs._replace(
            priority=serve_update(self.hs.priority, indices, pcfg,
                                  valid=valid))

    def prewarm_retier(self, chunk_rows: int) -> None:
        pass    # no payload to re-quantize: re-tier is a cache refresh

    def begin_retier(self, chunk_rows: int):
        return None    # nothing migrates; caller refreshes the cache

    def retier(self) -> dict:
        return {"rows_moved": 0, "changed": False}

    # -- persistence ---------------------------------------------------

    def snapshot_manifest(self) -> dict:
        from repro.store.hashed import hashed_state_tree
        return hashed_state_tree(self.hs, self.hcfg)

    @classmethod
    def from_manifest(cls, tree: dict, *, mesh=None,
                      axis: str = "model", **_):
        from repro.store.hashed import HashedConfig, HashedStore
        hcfg = HashedConfig(**{k: int(v) for k, v in
                               tree["config"].items()})
        hs = HashedStore(pool=jnp.asarray(tree["pool"]),
                         pool_scale=jnp.asarray(tree["pool_scale"]),
                         priority=jnp.asarray(tree["priority"]))
        return cls(hs, hcfg, mesh=mesh, axis=axis)


# ------------------------------------------------------------------- registry


_BACKENDS: dict[str, Callable[..., Any]] = {}
_MANIFEST_KINDS: dict[str, Callable[..., Any]] = {}


def register_backend(name: str, factory: Callable[..., Any],
                     manifest_kind: str | None = None) -> None:
    """Register ``factory`` under ``name`` for ``build``; optionally
    bind a ``snapshot_manifest`` kind tag for ``from_manifest``."""
    _BACKENDS[name] = factory
    if manifest_kind is not None:
        _MANIFEST_KINDS[manifest_kind] = factory


def backend_names() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def build(name: str, *args, **kwargs):
    """``build("packed"|"hier"|"hashed", ...)`` -> an EmbeddingStore.

    Positional/keyword arguments pass straight to the backend factory:
    ``build("packed", store, cfg, mesh=...)``,
    ``build("hier", store, cfg, hier_cfg, mesh=...)``,
    ``build("hashed", hashed_store, hashed_cfg)``.
    """
    try:
        factory = _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown store backend {name!r}; registered: "
            f"{', '.join(backend_names())}") from None
    return factory(*args, **kwargs)


def from_manifest(tree: dict, **kwargs):
    """Rebuild a backend from a ``snapshot_manifest`` pytree — the kind
    tag inside the manifest picks the backend (the
    ``ckpt.CheckpointManager`` round-trip entry point)."""
    kind = tree.get("kind") or tree.get("schema")
    if kind is None:
        raise ValueError("manifest carries no 'kind'/'schema' tag")
    factory = _MANIFEST_KINDS.get(str(kind))
    if factory is None:
        raise ValueError(
            f"no backend registered for manifest kind {kind!r}")
    return factory.from_manifest(tree, **kwargs)


register_backend("packed", PackedBackend,
                 manifest_kind="packed_store/v1")
register_backend("hier", HierBackend, manifest_kind="hier_store/v1")
register_backend("hashed", HashedBackend,
                 manifest_kind="hashed_store/v1")


__all__ = [
    "EmbeddingStore",
    "HashedBackend",
    "HierBackend",
    "PackedBackend",
    "backend_names",
    "build",
    "from_manifest",
    "register_backend",
]
