"""`HierStore`: three-level placement of the tier-partitioned store.

SHARK's industrial setting has embedding tables that "exceed terabytes"
— far past device HBM.  `HierStore` places the *same quantized rows* a
flat `PackedStore` would hold across three levels:

    HOT   a device-resident `PackedStore` over the priority-hot rows,
          chosen by `budget.plan_placement` under an HBM byte budget
          (row-sharded over a mesh via `dist.packed.shard_packed`)
    WARM  a host-RAM `PackedStore` (numpy leaves) over the next rows
    COLD  mmap'd disk shards (`manifest.ColdShards`)

One lookup API serves all three: `stage()` resolves residency per
index host-side, gathers + dequantizes the warm/cold misses into a
single fixed-shape fp32 staging buffer (ONE `jax.device_put` per
micro-batch — asynchronous, the transfer overlaps the host dispatch
that follows), and `combine_rows()` merges staged rows with the fused
device gather inside jit.  Because quantized bytes are preserved when
rows move levels (`extract_rows`/`concat_stores`) and host dequant is
bit-exact (`manifest.np_lookup`), a `HierStore` lookup is
**bit-identical** to `packed_store.lookup` on a fully device-resident
pack of the same rows — the oracle every test demands.

`migrate()` is the priority-driven re-tier+re-place step: rows whose
Eq. 8 precision crossed are re-quantized exactly as `pack()` would
(same contract as `repack_delta`), rows whose priority rank crossed a
budget boundary move levels with their bytes untouched (promote hot /
demote cold), and the cold shards are rewritten atomically when the
cold set changed.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import packed_store as ps
from repro.core.packed_store import (
    PackedStore,
    extract_rows,
    merge_stores,
)
from repro.core.qat_store import FQuantConfig, QATStore, current_tiers
from repro.store.budget import COLD, HOT, WARM, plan_placement
from repro.store.manifest import ColdShards, np_lookup, write_cold_shards

Array = jax.Array


class HierConfig(NamedTuple):
    hbm_budget_bytes: int                 # per-device HOT budget
    host_budget_bytes: int | None = None  # WARM budget; None = no cold
    rows_per_shard: int = 4096            # cold shard granularity
    store_dir: str | None = None          # required when cold non-empty


@dataclasses.dataclass
class HierStats:
    staged_rows: int = 0     # distinct rows staged (dedup'd DMA traffic)
    warm_hits: int = 0       # valid accesses resolved from host RAM
    cold_hits: int = 0       # valid accesses resolved from disk
    migrations: int = 0
    promoted: int = 0        # rows moved toward HOT across migrations
    demoted: int = 0

    def as_dict(self) -> dict:
        return {"staged_rows": self.staged_rows,
                "warm_hits": self.warm_hits,
                "cold_hits": self.cold_hits,
                "migrations": self.migrations,
                "promoted": self.promoted, "demoted": self.demoted}


class StagedBatch(NamedTuple):
    """Per-batch residency resolution, ready for the jitted combine."""
    hot_local: Array      # int32, shape of gidx; hot-local id (0 if not)
    stage_slot: Array     # int32, shape of gidx; staging row, -1 if hot
    staging: Array        # fp32 (capacity, D) dequantized miss rows
    warm_hits: int
    cold_hits: int
    staged: int           # distinct rows actually staged


# row-wise quantization shared with the flat store: any subset is
# byte-identical to quantizing inside a full pack() batch
_quantize_subset = ps.quantize_rows


class RetierPlan(NamedTuple):
    """Frozen migration decision: everything ``migrate`` derives from
    one (priority, tiers) snapshot.  Computing it once and building
    from it — whether in one shot (``_migrate``) or in bounded chunks
    (``serve.shadow.ShadowMigrate``) — is what makes the async path
    bit-identical to the synchronous one *by construction*."""
    table: np.ndarray        # fp32 (V, D) snapshot of the QAT table
    new_tiers: np.ndarray    # int8 (V,) Eq. 8 tiers at the fold state
    plan: object             # budget.BudgetPlan (hot/warm/cold ids)
    crossed: np.ndarray      # bool (V,) precision changed vs packed


@dataclasses.dataclass
class HierStore:
    """Mutable three-level owner.  All host state is numpy; ``hot_dev``
    is the placed (optionally row-sharded) device copy of ``hot_host``.
    """
    cfg: HierConfig
    dim: int
    level: np.ndarray        # int8 (V,) HOT/WARM/COLD
    slot: np.ndarray         # int64 (V,) level-local row id
    tiers: np.ndarray        # int8 (V,) Eq. 8 precision currently packed
    hot_ids: np.ndarray
    warm_ids: np.ndarray
    cold_ids: np.ndarray
    hot_host: PackedStore    # numpy mirror of the device store
    warm: PackedStore        # numpy
    cold: ColdShards | None
    mesh: object = None
    axis: str = "model"
    hot_dev: PackedStore = None
    stats: HierStats = dataclasses.field(default_factory=HierStats)

    @property
    def vocab(self) -> int:
        return self.level.shape[0]

    def counts(self) -> dict:
        return {"hot_rows": int(self.hot_ids.size),
                "warm_rows": int(self.warm_ids.size),
                "cold_rows": int(self.cold_ids.size)}

    def nbytes(self) -> dict:
        """Per-level bytes: what each level physically holds."""
        return {"hot": self.hot_host.nbytes(),
                "warm": self.warm.nbytes(),
                "cold": 0 if self.cold is None else self.cold.nbytes()}

    # -- placement -----------------------------------------------------

    def place(self) -> None:
        """``hot_dev`` through ``dist.packed.place_packed``, as the
        shadow migration stages it: one pytree structure before and
        after a swap."""
        from repro.dist.packed import place_packed
        self.hot_dev = place_packed(self.hot_host, self.mesh, self.axis)

    def lookup_fn(self):
        """Hot-store gather matching ``hot_dev``'s placement (the same
        contract as ``OnlineServer.lookup_fn``)."""
        if self.mesh is None:
            return ps.lookup_fused
        from repro.dist.packed import sharded_lookup
        mesh, axis = self.mesh, self.axis
        return lambda pk, idx: sharded_lookup(pk, idx, mesh=mesh,
                                              axis=axis)

    # -- lookup path ---------------------------------------------------

    def stage(self, gidx, *, skip=None, valid=None) -> StagedBatch:
        """Span-instrumented wrapper over ``_stage`` (histogram
        ``store.stage_us`` + staging counters when metrics are on)."""
        with obs.span("store.stage"):
            return self._stage(gidx, skip=skip, valid=valid)

    def _stage(self, gidx, *, skip=None, valid=None) -> StagedBatch:
        """Resolve residency per index and stage warm/cold misses.

        ``gidx``: int global row ids, any shape.  ``skip`` (bool, same
        shape) marks positions that need no rows at all (e.g. hot-cache
        hits) — they are neither staged nor counted.  ``valid`` masks
        micro-batch padding out of the *hit accounting* only (padding
        rows still stage so the jitted shapes stay stable, but they are
        deduplicated into the same slots as live accesses).

        Staged rows are deduplicated — each distinct missing row is
        dequantized once into a fixed ``gidx.size``-row fp32 buffer and
        shipped with ONE ``jax.device_put`` (async: the host returns
        before the copy completes and jit sequences the transfer before
        first use).
        """
        g = np.asarray(gidx, np.int64)
        flat = g.reshape(-1)
        lev = self.level[flat]
        hot_local = np.where(lev == HOT, self.slot[flat], 0).astype(
            np.int32)

        need = lev != HOT
        if skip is not None:
            need &= ~np.asarray(skip, bool).reshape(-1)
        miss_pos = np.nonzero(need)[0]
        uniq, inv = np.unique(flat[miss_pos], return_inverse=True)

        rows = np.zeros((max(flat.size, 1), self.dim), np.float32)
        ulev = self.level[uniq]
        uslot = self.slot[uniq]
        wm = ulev == WARM
        if wm.any():
            rows[np.nonzero(wm)[0]] = np_lookup(self.warm, uslot[wm])
        cm = ulev == COLD
        if cm.any():
            rows[np.nonzero(cm)[0]] = self.cold.gather_fp32(uslot[cm])

        stage_slot = np.full(flat.size, -1, np.int32)
        stage_slot[miss_pos] = inv.astype(np.int32)

        vm = np.ones(flat.size, bool) if valid is None else \
            np.broadcast_to(np.asarray(valid, bool), g.shape).reshape(-1)
        counted = lev[miss_pos[vm[miss_pos]]]
        warm_hits = int((counted == WARM).sum())
        cold_hits = int((counted == COLD).sum())
        self.stats.staged_rows += int(uniq.size)
        self.stats.warm_hits += warm_hits
        self.stats.cold_hits += cold_hits
        if obs.enabled():
            # staged_rows counts DISTINCT rows shipped (the dedup'd DMA
            # traffic); miss_dedup is what dedup saved vs naive staging
            obs.inc("store.staged_rows", int(uniq.size))
            obs.inc("store.miss_dedup", int(miss_pos.size - uniq.size))
            obs.inc("store.warm_hits", warm_hits)
            obs.inc("store.cold_hits", cold_hits)
            obs.gauge("store.staging_bytes", float(rows.nbytes))
        return StagedBatch(
            hot_local=jnp.asarray(hot_local.reshape(g.shape)),
            stage_slot=jnp.asarray(stage_slot.reshape(g.shape)),
            staging=jax.device_put(rows),
            warm_hits=warm_hits, cold_hits=cold_hits,
            staged=int(uniq.size))

    def gather_fp32_host(self, ids) -> np.ndarray:
        """Host-side dequantized rows for any global ids (cache builds,
        identity checks) — bit-identical to the device path."""
        g = np.asarray(ids, np.int64)
        flat = g.reshape(-1)
        out = np.empty((flat.size, self.dim), np.float32)
        for lev, src in ((HOT, self.hot_host), (WARM, self.warm)):
            m = self.level[flat] == lev
            if m.any():
                out[m] = np_lookup(src, self.slot[flat[m]])
        m = self.level[flat] == COLD
        if m.any():
            out[m] = self.cold.gather_fp32(self.slot[flat[m]])
        return out.reshape(*g.shape, self.dim)

    # -- migration -----------------------------------------------------

    def _gather_quantized(self, ids: np.ndarray) -> PackedStore:
        """Quantized sub-store over global ``ids`` pulled from whatever
        levels currently hold them (bytes untouched)."""
        parts, perm, base = [], np.empty(ids.size, np.int64), 0
        for lev in (HOT, WARM, COLD):
            m = np.nonzero(self.level[ids] == lev)[0]
            if not m.size:
                continue
            loc = self.slot[ids[m]]
            if lev == HOT:
                sub = extract_rows(self.hot_host, loc)
            elif lev == WARM:
                sub = extract_rows(self.warm, loc)
            else:
                sub = self.cold.extract(loc)
            parts.append(sub)
            perm[m] = base + np.arange(m.size)
            base += m.size
        return extract_rows(merge_stores(parts), perm)

    def migrate(self, store: QATStore, cfg: FQuantConfig) -> dict:
        """Span-instrumented wrapper over ``_migrate`` (histogram
        ``store.migrate_us``, moved-row counters and per-level
        occupancy gauges when metrics are on)."""
        with obs.span("store.migrate"):
            out = self._migrate(store, cfg)
        if obs.enabled():
            obs.inc("store.migrate.promoted", out["promoted"])
            obs.inc("store.migrate.demoted", out["demoted"])
            obs.inc("store.migrate.crossed", out["crossed"])
            for k, v in self.counts().items():
                obs.gauge(f"store.{k}", float(v))
            for k, v in self.nbytes().items():
                obs.gauge(f"store.{k}_bytes", float(v))
        return out

    def plan_retier(self, store: QATStore, cfg: FQuantConfig
                    ) -> RetierPlan:
        """Freeze one migration decision from the current fold state:
        Eq. 8 tiers, the budget placement and the crossed-row mask.
        Pure read — live state is untouched until ``commit_retier``."""
        new_tiers = np.asarray(current_tiers(store, cfg)).astype(np.int8)
        n_shards = 1 if self.mesh is None else self.mesh.shape[self.axis]
        plan = plan_placement(np.asarray(store.priority), new_tiers,
                              self.dim, self.cfg.hbm_budget_bytes,
                              self.cfg.host_budget_bytes, n_shards)
        return RetierPlan(table=np.asarray(store.table, np.float32),
                          new_tiers=new_tiers, plan=plan,
                          crossed=new_tiers != self.tiers)

    def build_rows(self, ids: np.ndarray, rp: RetierPlan,
                   cfg: FQuantConfig,
                   quant_pad: int | None = None) -> PackedStore:
        """One level's store (or any consecutive chunk of it) under the
        frozen plan: unchanged-precision rows carry their quantized
        bytes from whichever LIVE level holds them, crossed rows
        re-quantize from the snapshot table exactly as ``pack`` would.
        Position ``i`` = ``ids[i]``, so consecutive chunks of a level's
        id list ``merge_stores`` back into the one-shot build —
        lookup-bit-identically (chunking only permutes payload order
        *within* a tier, which ``indirect`` hides).  ``quant_pad`` is
        forwarded to ``quantize_rows`` so chunked callers keep one
        compiled shape set (``serve.shadow.ShadowMigrate``)."""
        if not ids.size:
            return extract_rows(self.hot_host, np.zeros((0,), np.int64))
        keep_pos = np.nonzero(~rp.crossed[ids])[0]
        req_pos = np.nonzero(rp.crossed[ids])[0]
        parts, perm = [], np.empty(ids.size, np.int64)
        base = 0
        if keep_pos.size:
            parts.append(self._gather_quantized(ids[keep_pos]))
            perm[keep_pos] = base + np.arange(keep_pos.size)
            base += keep_pos.size
        if req_pos.size:
            parts.append(_quantize_subset(rp.table, ids[req_pos],
                                          rp.new_tiers, cfg,
                                          pad_to=quant_pad))
            perm[req_pos] = base + np.arange(req_pos.size)
        return extract_rows(merge_stores(parts), perm)

    def cold_changed(self, rp: RetierPlan) -> bool:
        """Whether the plan moves/re-tiers any cold row (the live cold
        shards can be reused verbatim otherwise)."""
        plan = rp.plan
        return (plan.cold_ids.size != self.cold_ids.size
                or not np.array_equal(plan.cold_ids, self.cold_ids)
                or bool(rp.crossed[plan.cold_ids].any()))

    def commit_retier(self, rp: RetierPlan, new_hot: PackedStore,
                      new_warm: PackedStore,
                      new_cold: ColdShards | None,
                      hot_dev: PackedStore | None = None) -> dict:
        """Atomically flip the live state to the built generation.

        The ONE mutation point shared by the synchronous ``migrate``
        and the chunked shadow path (``serve.shadow.ShadowMigrate``):
        everything before this is built off to the side, so a crash or
        discard before the commit leaves the live store untouched.
        ``new_cold`` must already be published under ``cfg.store_dir``
        (or be the reused live object / None when the plan has no cold
        level).  ``hot_dev``, when given, is an already-placed device
        copy of ``new_hot`` (the shadow path stages the transfer ahead
        of the swap) and skips the blocking ``place()``.
        """
        plan = rp.plan
        promoted = int((plan.level < self.level).sum())
        demoted = int((plan.level > self.level).sum())
        self.cold = new_cold
        self.hot_host, self.warm = new_hot, new_warm
        self.hot_ids, self.warm_ids = plan.hot_ids, plan.warm_ids
        self.cold_ids = plan.cold_ids
        self.level = plan.level
        self.slot = np.zeros(self.vocab, np.int64)
        for ids in (plan.hot_ids, plan.warm_ids, plan.cold_ids):
            self.slot[ids] = np.arange(ids.size)
        self.tiers = rp.new_tiers
        if hot_dev is not None:
            self.hot_dev = hot_dev
        else:
            self.place()
        self.stats.migrations += 1
        self.stats.promoted += promoted
        self.stats.demoted += demoted
        return {"promoted": promoted, "demoted": demoted,
                "crossed": int(rp.crossed.sum())}

    def _migrate(self, store: QATStore, cfg: FQuantConfig) -> dict:
        """Priority-driven re-tier + re-place across levels.

        Recomputes Eq. 8 precision tiers and the budget placement from
        the live priority EMA, then rebuilds each level: rows whose
        precision is unchanged carry their quantized bytes from
        whichever level held them; crossed rows re-quantize from the
        fp32 table exactly as ``pack`` would.  The device copy is
        re-placed and the cold shards rewritten (atomically) when the
        cold set changed.  Bit-identity contract: afterwards, lookups
        equal ``pack(store, cfg)`` lookups — same contract as
        ``repack_delta``, now across levels.

        Implemented as plan -> build -> commit over the same pieces the
        chunked shadow migration drives (``plan_retier`` /
        ``build_rows`` / ``commit_retier``), so the synchronous and
        async paths are identical by construction.
        """
        rp = self.plan_retier(store, cfg)
        plan = rp.plan
        new_hot = self.build_rows(plan.hot_ids, rp, cfg)
        new_warm = self.build_rows(plan.warm_ids, rp, cfg)
        new_cold = self.cold
        if plan.cold_ids.size and self.cold_changed(rp):
            if self.cfg.store_dir is None:
                raise ValueError("cold spill requires store_dir")
            write_cold_shards(self.cfg.store_dir,
                              self.build_rows(plan.cold_ids, rp, cfg),
                              plan.cold_ids, self.cfg.rows_per_shard)
            new_cold = ColdShards(self.cfg.store_dir)
        elif not plan.cold_ids.size:
            new_cold = None
        return self.commit_retier(rp, new_hot, new_warm, new_cold)

    # -- checkpointing -------------------------------------------------

    def state_tree(self) -> dict:
        """Checkpointable manifest: mixed numpy/scalar/NamedTuple
        leaves (cold shards live on disk already and are addressed by
        ``cfg.store_dir``; see ``ckpt.CheckpointManager``)."""
        return {"schema": "hier_store/v1",
                "vocab": self.vocab, "dim": self.dim,
                "hbm_budget_bytes": int(self.cfg.hbm_budget_bytes),
                "level": self.level, "slot": self.slot,
                "tiers": self.tiers,
                "hot_ids": self.hot_ids, "warm_ids": self.warm_ids,
                "cold_ids": self.cold_ids,
                "hot": self.hot_host, "warm": self.warm}


def build_hier(store: QATStore, cfg: FQuantConfig, hcfg: HierConfig,
               mesh=None, axis: str = "model") -> HierStore:
    """Pack + partition: offline construction of the three levels.

    Packs the full store host-side (the transient host image a
    production build would stream shard-by-shard), plans placement from
    the priority vector, extracts the hot/warm sub-stores and writes
    the cold shards + manifest.
    """
    host = PackedStore(*(np.asarray(leaf) for leaf in
                         jax.device_get(ps.pack(store, cfg))))
    tiers = ps.packed_tiers(host)
    dim = host.payload32.shape[-1]
    n_shards = 1 if mesh is None else mesh.shape[axis]
    plan = plan_placement(np.asarray(store.priority), tiers, dim,
                          hcfg.hbm_budget_bytes, hcfg.host_budget_bytes,
                          n_shards)
    cold = None
    if plan.cold_ids.size:
        if hcfg.store_dir is None:
            raise ValueError("cold spill requires HierConfig.store_dir")
        write_cold_shards(hcfg.store_dir,
                          extract_rows(host, plan.cold_ids),
                          plan.cold_ids, hcfg.rows_per_shard)
        cold = ColdShards(hcfg.store_dir)

    slot = np.zeros(plan.level.shape[0], np.int64)
    for ids in (plan.hot_ids, plan.warm_ids, plan.cold_ids):
        slot[ids] = np.arange(ids.size)
    hier = HierStore(
        cfg=hcfg, dim=dim, level=plan.level, slot=slot,
        tiers=np.asarray(tiers).astype(np.int8),
        hot_ids=plan.hot_ids, warm_ids=plan.warm_ids,
        cold_ids=plan.cold_ids,
        hot_host=extract_rows(host, plan.hot_ids),
        warm=extract_rows(host, plan.warm_ids),
        cold=cold, mesh=mesh, axis=axis)
    hier.place()
    return hier


def combine_rows(hot_dev: PackedStore, hot_local: Array,
                 stage_slot: Array, staging: Array,
                 lookup_fn=None) -> Array:
    """Jit-friendly merge: fused device gather for hot positions, one
    ``take`` from the staging buffer for the rest.  Bit-identical to
    ``packed_store.lookup`` on a fully resident store."""
    rows = (lookup_fn or ps.lookup_fused)(hot_dev, hot_local)
    staged = jnp.take(staging,
                      jnp.clip(stage_slot, 0, staging.shape[0] - 1),
                      axis=0)
    return jnp.where((stage_slot >= 0)[..., None], staged, rows)


def hier_lookup(hier: HierStore, indices, lookup_fn=None) -> Array:
    """Three-level ``lookup``: int (...,) -> fp32 (..., D)."""
    sb = hier.stage(np.asarray(indices))
    return combine_rows(hier.hot_dev, sb.hot_local, sb.stage_slot,
                        sb.staging, lookup_fn or hier.lookup_fn())


def hier_bag_lookup(hier: HierStore, indices, segment_ids: Array,
                    num_bags: int, weights: Array | None = None) -> Array:
    """Three-level ``bag_lookup``: same reduction order as
    ``packed_store.bag_lookup``, so results are bit-identical."""
    rows = hier_lookup(hier, indices)
    if weights is not None:
        rows = rows * weights[:, None]
    return jax.ops.segment_sum(rows, segment_ids, num_segments=num_bags)
