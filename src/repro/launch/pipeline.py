"""One-command SHARK pipeline: train -> prune -> quantize -> pack -> serve.

    python -m repro.launch.pipeline [--fast] [--mesh N] [--emit PATH]

The full paper loop in one driver, built from the pieces the serving
PRs left disconnected from training:

  1. **train**    — ``train.steps.make_compressed_train_step`` under the
     fault-tolerant loop: the forward gather and the backward
     scatter-add both run the fused Pallas dequant-bag kernel family
     (``jax.custom_vjp``), the Eq. 7 priority EMA and Eq. 5-6 sparse
     snap fold into every step, and the in-training Taylor/access
     accumulator (``train.accum``) rides in the checkpointed state.
     ``--mesh N`` row-shards the table and runs the per-shard kernels
     under ``dist.packed.sharded_lookup_train``.
  2. **prune**    — fields ranked by the accumulated first-order Taylor
     scores (Eq. 2-4); the least important are masked until the
     remaining-memory fraction meets ``--prune-to``, then a short
     masked finetune (same step, ``field_mask``) repairs the head.
  3. **quantize** — Eq. 8 thresholds planned for ``--target-ratio``
     from the *trained* priority EMA; the table is snapped (Eq. 5-6,
     RTN) so every row is tier-exact.
  4. **pack**     — ``packed_store.pack`` + a ``CheckpointManager``
     round trip; the restored bytes must equal a fresh offline
     ``pack`` of the same trained rows bit-for-bit.
  5. **serve**    — the packed result is handed to ``OnlineServer`` and
     driven micro-batched under drifting zipf; after a final re-tier
     the live store must still be bit-identical to a fresh ``pack`` of
     the live priorities (the ``repack_delta`` lockstep contract).

A one-batch gradcheck (fused custom_vjp backward vs the dense
``jnp.take`` autodiff reference) runs in-driver and its max abs error
lands in the record.  The last stdout line is a ``bench_pipeline/v1``
JSON record (schema in docs/training.md, validated by
``tools/check_bench_schema.py``): compression ratio and storage bytes
(Fig. 2 / Table 2 quantities), train/eval quality (BCE loss + AUC
proxy), serve QPS, and the verification flags.  Any failed verify
exits non-zero — this is the CI pipeline smoke.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil

from repro import obs


@dataclasses.dataclass
class PipelineConfig:
    arch: str = "dlrm-rm2"
    steps: int = 120
    batch: int = 64
    lr: float | None = None      # None: the arch's driver rate
    mesh: int = 1
    ckpt_dir: str = "/tmp/repro_pipeline"
    ckpt_every: int = 40
    target_ratio: float = 0.5    # Eq. 8 byte budget (fraction of fp32)
    prune_to: float = 0.85       # keep-memory fraction after F-Perm
    finetune_steps: int = 16
    serve_requests: int = 96
    serve_batch: int = 8
    retier_every: int = 24
    cache_rows: int = 64
    drift: float = 2.0
    eval_batches: int = 8
    gradcheck_batch: int = 8
    seed: int = 0
    resume: bool = False         # keep ckpt_dir and resume training
    use_pallas: bool | None = None   # None = backend auto-detect
    store_backend: str = "packed"    # "packed" | "hashed" serving store
    hash_ratio: float = 100.0    # fp32/pool target (store_backend=hashed)


def fast_config(**overrides) -> PipelineConfig:
    """CI-sized pipeline (the ``--fast`` preset)."""
    base = dict(steps=24, batch=32, ckpt_every=10, finetune_steps=6,
                serve_requests=24, retier_every=12, eval_batches=4)
    base.update(overrides)
    return PipelineConfig(**base)


def _bits_equal(tree_a, tree_b) -> bool:
    import jax
    import numpy as np
    fa = jax.tree_util.tree_leaves(tree_a)
    fb = jax.tree_util.tree_leaves(tree_b)
    if len(fa) != len(fb):
        return False
    for la, lb in zip(fa, fb):
        a, b = np.asarray(la), np.asarray(lb)
        if a.dtype != b.dtype or a.shape != b.shape:
            return False
        if a.tobytes() != b.tobytes():
            return False
    return True


def run_pipeline(cfg: PipelineConfig) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.ckpt.manager import CheckpointManager
    from repro.core import metrics as metrics_lib
    from repro.core import packed_store as ps
    from repro.core import qat_store as qs
    from repro.core.pruning import memory_fraction
    from repro.core.qat_store import FQuantConfig, QATStore
    from repro.core.tiers import (
        assign_tiers,
        plan_thresholds_for_ratio,
        tier_counts,
    )
    from repro.train import accum as accum_lib
    from repro.train import loop as loop_lib
    from repro.train.setup import build_recsys_training
    from repro.train.steps import make_compressed_train_step

    arch = configs.get(cfg.arch)
    mesh = None
    if cfg.mesh > 1:
        from repro.launch.mesh import make_model_mesh
        mesh = make_model_mesh(cfg.mesh)
    fq_train = FQuantConfig()            # paper-default thresholds

    setup = build_recsys_training(
        arch, batch=cfg.batch, lr=cfg.lr, mesh=mesh, seed=cfg.seed,
        fq_cfg=fq_train, use_pallas=cfg.use_pallas)
    model, spec, batch_fn = setup.model, setup.spec, setup.batch_fn
    indices_fn = setup.indices_fn
    num_dense = setup.num_dense

    rec: dict = {"schema": "bench_pipeline/v1", "benchmark": "pipeline",
                 "arch": cfg.arch, "mesh": cfg.mesh,
                 "train_steps": cfg.steps, "batch": cfg.batch}
    stage_s: dict = {}

    # ------------------------------------------------------------ train
    train_dir = os.path.join(cfg.ckpt_dir, "train")
    if not cfg.resume and os.path.isdir(train_dir):
        shutil.rmtree(train_dir)
    loop_cfg = loop_lib.LoopConfig(
        total_steps=cfg.steps, ckpt_every=cfg.ckpt_every,
        ckpt_dir=train_dir, log_every=max(cfg.steps // 4, 1))
    with obs.timeblock("pipeline.train") as tb:
        result = loop_lib.run(setup.state, jax.jit(setup.step),
                              batch_fn, loop_cfg)
    state = result.state
    stage_s["train"] = round(tb.seconds, 3)

    if result.losses:
        loss_first, loss_last = result.losses[0], result.losses[-1]
    else:
        # --resume with training already complete: no steps ran this
        # session, so report the restored state's loss on one batch
        loss_first = loss_last = float(jax.jit(
            lambda p, b: model.loss_from_emb(
                p, model.embed(p, b), b).mean())(
            state.params, batch_fn(cfg.steps)))
    rec["train_loss_first"] = round(float(loss_first), 5)
    rec["train_loss_last"] = round(float(loss_last), 5)

    # the accumulator state checkpoints with the loop: the newest
    # checkpoint must carry it (restartable Taylor/access statistics)
    mgr = CheckpointManager(train_dir)
    restored, _ = mgr.restore(jax.device_get(state))
    accum_ckpt_ok = _bits_equal(jax.device_get(state.accum),
                                restored.accum)

    # in-driver gradcheck: fused custom_vjp backward vs dense autodiff
    table_h = jnp.asarray(jax.device_get(state.params["embed_table"]))
    gb = batch_fn(1_000_003)
    gb = {k: (v[:cfg.gradcheck_batch] if hasattr(v, "shape")
              and v.ndim else v) for k, v in gb.items()}
    gidx = indices_fn(gb)
    dense_h = {k: jax.device_get(v) for k, v in state.params.items()
               if k != "embed_table"}

    def _gc_loss(tbl, emb_of):
        e = emb_of(tbl)
        p = dict(dense_h)
        p["embed_table"] = tbl
        return model.loss_from_emb(p, e, gb).mean()

    from repro.kernels.dequant_bag.autodiff import lookup_train
    g_fused = jax.grad(lambda t: _gc_loss(
        t, lambda tt: lookup_train(tt, gidx, use_pallas=True)))(table_h)
    g_dense = jax.grad(lambda t: _gc_loss(
        t, lambda tt: jnp.take(tt, gidx, axis=0)))(table_h)
    grad_err = float(jnp.abs(g_fused - g_dense).max())
    grad_scale = float(jnp.abs(g_dense).max())
    rec["gradcheck_max_abs_err"] = grad_err
    grad_ok = grad_err <= 1e-5 + 1e-4 * grad_scale

    # ------------------------------------------------------------ prune
    tb = obs.timeblock("pipeline.prune").start()
    scores = np.asarray(accum_lib.field_scores(state.accum))
    table_bytes = spec.table_bytes()
    mask = np.ones(spec.num_fields, bool)
    for f in np.argsort(scores)[:spec.num_fields // 2]:
        if memory_fraction(mask, table_bytes) <= cfg.prune_to:
            break
        mask[int(f)] = False
    pruned = np.nonzero(~mask)[0]

    if pruned.size and cfg.finetune_steps:
        ft_step = make_compressed_train_step(
            model.loss_from_emb, indices_fn, lambda b: b["labels"],
            "embed_table", setup.lr, spec.num_fields, fq_cfg=fq_train,
            mesh=mesh, use_pallas=cfg.use_pallas, with_accum=True,
            field_mask=jnp.asarray(mask, jnp.float32))
        jft = jax.jit(ft_step)
        for i in range(cfg.finetune_steps):
            state, _ = jft(state, batch_fn(500_000 + i))

    # physically drop pruned fields: zero their rows and their priority
    # (zero priority -> coldest tier; zero rows quantize to zero bytes
    # of signal, so masked serving and zero-row serving agree exactly)
    table = np.array(jax.device_get(state.params["embed_table"]),
                     np.float32)
    priority = np.array(jax.device_get(state.priority), np.float32)
    offsets = spec.offsets()
    for f in pruned:
        lo = int(offsets[f])
        hi = lo + int(spec.cardinalities[f])
        table[lo:hi] = 0.0
        priority[lo:hi] = 0.0
    stage_s["prune"] = round(tb.stop(), 3)
    rec["fields_total"] = int(spec.num_fields)
    rec["fields_pruned"] = int(pruned.size)
    rec["kept_memory_fraction"] = round(
        memory_fraction(mask, table_bytes), 4)

    # -------------------------------------------------------- quantize
    tb = obs.timeblock("pipeline.quantize").start()
    pri = jnp.asarray(priority)
    tier_cfg = plan_thresholds_for_ratio(pri, spec.dim,
                                         cfg.target_ratio)
    final_cfg = FQuantConfig(tiers=tier_cfg, stochastic=False)
    tiers = assign_tiers(pri, tier_cfg)
    table = qs.snap(jnp.asarray(table), tiers, final_cfg)
    store = QATStore(table=table, priority=pri)
    stage_s["quantize"] = round(tb.stop(), 3)
    counts = tier_counts(tiers)
    rec["tier_rows_int8"] = int(counts[0])
    rec["tier_rows_half"] = int(counts[1])
    rec["tier_rows_fp32"] = int(counts[2])

    # ------------------------------------------------------------ pack
    tb = obs.timeblock("pipeline.pack").start()
    bytes_fp32 = spec.total_rows * spec.dim * 4
    pack_dir = os.path.join(cfg.ckpt_dir, "packed")
    if os.path.isdir(pack_dir):
        shutil.rmtree(pack_dir)
    pmgr = CheckpointManager(pack_dir, keep=1)
    # the store round-trips as a kind-tagged manifest: each backend
    # self-describes its payload (packed_store/v1 / hashed_store/v1)
    # and ``store.from_manifest`` dispatches the rebuild on the tag
    from repro.store import from_manifest as store_from_manifest
    hashed_backend = None
    restored_packed = None
    if cfg.store_backend == "hashed":
        from repro.store import (HashedConfig, build as store_build,
                                 fit_pool_from_table, plan_pool_slots)
        slots = plan_pool_slots(spec.total_rows, spec.dim, 8,
                                cfg.hash_ratio)
        hcfg = HashedConfig(vocab=spec.total_rows, dim=spec.dim,
                            chunk_dim=8, num_slots=slots)
        hs = fit_pool_from_table(jnp.asarray(table), hcfg,
                                 priority=pri)
        src_backend = store_build("hashed", hs, hcfg, mesh=mesh)
        bytes_packed = src_backend.nbytes()
        pmgr.save(cfg.steps, src_backend.snapshot_manifest())
        restored_tree, _ = pmgr.restore(src_backend.snapshot_manifest())
        hashed_backend = store_from_manifest(restored_tree, mesh=mesh)
        verify_pack = _bits_equal(hashed_backend.snapshot_manifest(),
                                  src_backend.snapshot_manifest())
    else:
        packed = ps.pack(store, final_cfg)
        bytes_packed = packed.nbytes()
        manifest = {"kind": "packed_store/v1", "packed": packed,
                    "priority": store.priority}
        pmgr.save(cfg.steps, manifest)
        restored_tree, _ = pmgr.restore(manifest)
        restored_packed = store_from_manifest(
            restored_tree, store=store, cfg=final_cfg).host_packed
        # the handoff artifact must equal a fresh offline pack of the
        # same trained rows, bit for bit, through the round trip
        verify_pack = (_bits_equal(restored_packed, packed)
                       and _bits_equal(restored_packed,
                                       ps.pack(store, final_cfg)))
    stage_s["pack"] = round(tb.stop(), 3)
    rec["bytes_fp32"] = int(bytes_fp32)
    rec["bytes_packed"] = int(bytes_packed)
    rec["compression_ratio"] = round(bytes_packed / bytes_fp32, 4)
    rec["verify_pack_bit_identical"] = bool(verify_pack)

    # quality: AUC proxy on held-out batches, fp32 table vs the served
    # (pruned + quantized) table
    def eval_quality(tbl) -> tuple[float, float]:
        p = {k: jax.device_get(v) for k, v in state.params.items()}
        p["embed_table"] = tbl
        losses, aucs = [], []
        fwd = jax.jit(lambda pp, b: model.forward(
            pp, b, jnp.asarray(mask, jnp.float32)))
        for i in range(cfg.eval_batches):
            b = batch_fn(2_000_000 + i)
            logits = fwd(p, b)
            losses.append(float(metrics_lib.bce_with_logits(
                logits, b["labels"]).mean()))
            aucs.append(float(metrics_lib.auc(logits, b["labels"])))
        return float(np.mean(losses)), float(np.mean(aucs))

    loss_fp32, auc_fp32 = eval_quality(
        jnp.asarray(jax.device_get(state.params["embed_table"])))
    if hashed_backend is not None:
        served_tbl = jnp.asarray(hashed_backend.gather_fp32_host(
            np.arange(spec.total_rows)))
    else:
        served_tbl = ps.unpack(restored_packed)
    loss_packed, auc_packed = eval_quality(served_tbl)
    rec["eval_loss_fp32"] = round(loss_fp32, 5)
    rec["eval_loss_packed"] = round(loss_packed, 5)
    rec["eval_auc_fp32"] = round(auc_fp32, 5)
    rec["eval_auc_packed"] = round(auc_packed, 5)

    # ----------------------------------------------------------- serve
    tb = obs.timeblock("pipeline.serve").start()
    from repro.serve import OnlineConfig, OnlineServer, serve_forward
    server = OnlineServer(
        store, final_cfg,
        OnlineConfig(cache_rows=cfg.cache_rows,
                     retier_every=cfg.retier_every),
        mesh=mesh, backend=hashed_backend)
    if hashed_backend is None:
        # direct handoff: the server's own pack of the trained store
        # must BE the pipeline's packed artifact
        handoff_ok = _bits_equal(server.host_packed, restored_packed)
    else:
        handoff_ok = True       # the restored backend IS the server's
    serve_params = {k: jax.device_get(v)
                    for k, v in state.params.items()}
    loop_res = serve_forward(
        server, model, spec, serve_params,
        serve_batch=cfg.serve_batch, requests=cfg.serve_requests,
        drift=cfg.drift, num_dense=num_dense, seed=cfg.seed)
    # lockstep bit-identity under live priorities: after a final
    # re-tier the served store equals a fresh pack of the live EMA
    # (hashed: the shared pool must come through serving untouched —
    # only the priority EMA and the cache may move)
    server.retier()
    if hashed_backend is None:
        verify_serve = _bits_equal(
            ps.unpack(server.host_packed),
            ps.unpack(ps.pack(server.store, final_cfg)))
    else:
        verify_serve = _bits_equal(server.backend.hs.pool, hs.pool)
    stage_s["serve"] = round(tb.stop(), 3)
    rec["serve_requests"] = int(cfg.serve_requests)
    rec["serve_batch"] = int(cfg.serve_batch)
    rec["steady_qps"] = round(loop_res.steady_qps, 1)
    rec["cache_hit_rate"] = float(loop_res.stats["cache_hit_rate"])
    rec["retiers"] = int(loop_res.stats["retiers"])
    rec["verify_serve_bit_identical"] = bool(verify_serve
                                             and handoff_ok)
    rec["verify_grad_fp32_tolerance"] = bool(grad_ok)
    rec["verify_accum_checkpointed"] = bool(accum_ckpt_ok)
    rec["store_backend"] = cfg.store_backend
    rec["stage_seconds"] = stage_s
    return rec


def verify_failures(rec: dict) -> list[str]:
    """Names of the record's end-to-end verifications that did NOT
    hold — non-empty means the run must exit non-zero (shared with
    ``benchmarks.run --emit-pipeline``)."""
    return [k for k in ("verify_pack_bit_identical",
                        "verify_serve_bit_identical",
                        "verify_grad_fp32_tolerance",
                        "verify_accum_checkpointed")
            if not rec.get(k)]


def main() -> None:
    """CLI wrapper: guarantee the terminal metrics flush on EVERY exit
    path (success, verify SystemExit, crash) — the periodic sink
    cadence otherwise drops the final partial window of ticks, i.e.
    exactly the snapshot a failed run needs most."""
    try:
        _main()
    finally:
        obs.close_sink()


def _main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-rm2")
    ap.add_argument("--fast", action="store_true",
                    help="CI-sized budgets (see fast_config)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--mesh", type=int, default=1,
                    help="row-shard training + serving over an N-way "
                         "'model' mesh (host devices)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_pipeline")
    ap.add_argument("--resume", action="store_true",
                    help="keep ckpt-dir and resume training from the "
                         "newest checkpoint")
    ap.add_argument("--target-ratio", type=float, default=0.5)
    ap.add_argument("--prune-to", type=float, default=0.85)
    ap.add_argument("--store-backend", default="packed",
                    choices=("packed", "hashed"),
                    help="serving store backend: 'packed' = the "
                         "tier-partitioned pack, 'hashed' = ROBE-style "
                         "pool fit to the trained table "
                         "(repro.store.build)")
    ap.add_argument("--hash-ratio", type=float, default=100.0,
                    help="target fp32-table / pool compression ratio "
                         "(--store-backend hashed)")
    ap.add_argument("--serve-requests", type=int, default=None)
    ap.add_argument("--emit", default=None, metavar="PATH",
                    help="also write the bench_pipeline/v1 record here")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="enable the repro.obs registry and write "
                         "metrics_snapshot/v1 JSONL here (periodic "
                         "flush every 16 train steps / served batches "
                         "+ a final snapshot); docs/observability.md")
    args = ap.parse_args()

    from repro.launch import (force_host_device_count,
                              use_compile_cache)
    force_host_device_count(args.mesh)
    use_compile_cache()

    if args.metrics_out:
        from repro.serve.loop import SERVE_PHASES
        obs.enable()
        obs.ensure_histograms(f"{p}_us" for p in SERVE_PHASES)
        obs.set_sink(obs.JsonlSink(args.metrics_out, every=16))

    overrides = dict(arch=args.arch, mesh=args.mesh,
                     ckpt_dir=args.ckpt_dir, resume=args.resume,
                     target_ratio=args.target_ratio,
                     prune_to=args.prune_to,
                     store_backend=args.store_backend,
                     hash_ratio=args.hash_ratio)
    for key, val in (("steps", args.steps), ("batch", args.batch),
                     ("serve_requests", args.serve_requests)):
        if val is not None:
            overrides[key] = val
    cfg = fast_config(**overrides) if args.fast \
        else PipelineConfig(**overrides)

    rec = run_pipeline(cfg)
    obs.flush()     # the happy-path snapshot; close_sink() in main()
                    # covers error exits and the final partial window
    if args.emit:
        with open(args.emit, "w") as f:
            json.dump(rec, f, indent=1, sort_keys=True)
        print(f"wrote {args.emit}")
    print(json.dumps(rec))
    failures = verify_failures(rec)
    if failures:
        raise SystemExit(f"pipeline verify FAILED: {failures}")
    print(f"pipeline OK: {rec['compression_ratio']:.2%} of fp32 bytes, "
          f"{rec['fields_pruned']}/{rec['fields_total']} fields pruned, "
          f"AUC {rec['eval_auc_fp32']:.3f} -> "
          f"{rec['eval_auc_packed']:.3f}, "
          f"steady {rec['steady_qps']:.0f} qps (mesh={cfg.mesh})")


if __name__ == "__main__":
    main()
