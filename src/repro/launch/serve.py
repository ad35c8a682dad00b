"""Serving driver: ``python -m repro.launch.serve --arch dlrm-rm2``.

Builds the packed tier-partitioned store for a recsys model and serves
a batched request stream, reporting latency percentiles and the
memory/bytes ratios behind the paper's QPS claim.  On a TPU the model
is the arch's chip config (published widths, the chip's share of the
vocabulary — ``RecsysArch.driver_model``); elsewhere its smoke config.

``--mesh N`` (N > 1) row-shards the PackedStore over an N-way "model"
mesh and serves through ``repro.dist.packed.sharded_lookup`` — the
distributed serving path.  On a CPU backend the mesh is faked with
``--xla_force_host_platform_device_count`` (set before jax initialises);
on a TPU it needs N real devices and fails otherwise.

``--online`` switches to the ``repro.serve`` subsystem: a drifting-zipf
request stream is served cache-first (``--cache-rows`` hot rows in
fp32), every served batch is folded into the Eq. 7 priority EMA, and
every ``--retier-every`` requests tier-crossing rows are migrated with
``packed_store.repack_delta`` (re-sharded under ``--mesh N``).  Payload
shapes change at re-tier boundaries, so jit recompiles exactly there.
``--retier-async`` moves the repack off the request path instead: a
shadow generation builds in bounded chunks across requests (with the
recompile pre-warmed on a side thread) and swaps in atomically —
``--verify-swap`` asserts bit-identity with a synchronous repack at
every swap (see ``repro.serve.shadow`` and docs/serving.md).

``--serve-batch N`` (with ``--online``) switches to the micro-batched
pipeline: single-user requests accumulate into fixed-shape (N, F)
batches (pad + mask) and each batch runs one jitted forward and one
vectorised priority fold — ``--requests`` then counts single-user
requests.  The serving gather is the fused tiled Pallas dequant-bag
kernel on TPU (``packed_store.lookup_fused``), its jnp oracle on CPU.

``--hbm-budget-mb B`` (with ``--online --serve-batch``) serves through
the hierarchical store (``repro.store``): the device holds only the
priority-hot rows under the per-device budget, the spill lives in host
RAM (``--host-budget-mb``, 0 = unbounded) and mmap'd cold shards under
``--store-dir``; warm/cold misses stage through one async fp32 buffer
per micro-batch and re-tiering migrates rows between levels.
``--verify-hier`` asserts bit-identity with a fully resident pack over
the whole vocab after serving (the CI spill smoke).  docs/storage.md.

The last stdout line is a machine-readable JSON record
(qps / p50_us / p99_us / packed_mib / ... plus, online:
cache_hit_rate / steady_qps / retiers / rows_moved) consumed by
``benchmarks/qps_sharded.py`` and the CI smoke — schema in
docs/serving.md.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro import obs


def build_serving_store(spec, table, seed: int = 0):
    """The QAT store the drivers serve: a zipf-like priority profile
    drawn from ``seed``, Eq. 8 thresholds planned for a 50% memory
    budget, and every row snapped to its tier.  Returns (store, cfg);
    ``OnlineServer`` / ``pack`` turn it into the packed store.  Timed
    as ``store.plan`` (profile and thresholds) and ``store.snap``."""
    import jax.numpy as jnp

    from repro.core import FQuantConfig
    from repro.core import qat_store as qs
    from repro.core.tiers import plan_thresholds_for_ratio

    with obs.timeblock("store.plan"):
        rng = np.random.default_rng(seed)
        pri = jnp.asarray((rng.pareto(1.2, spec.total_rows) * 10)
                          .astype(np.float32))
        cfg = FQuantConfig(
            tiers=plan_thresholds_for_ratio(pri, spec.dim, 0.5),
            stochastic=False)
    with obs.timeblock("store.snap") as tb:
        store = qs.QATStore(table, pri)
        tiers = qs.current_tiers(store, cfg)
        # snap in row chunks: each eager step of snap holds a full-size
        # buffer, too many at once for one chip at published widths
        chunk = 1 << 20
        store = store._replace(table=tb.sync(jnp.concatenate(
            [qs.snap(table[i:i + chunk], tiers[i:i + chunk], cfg)
             for i in range(0, table.shape[0], chunk)])))
    return store, cfg


def main() -> None:
    """CLI wrapper: guarantee the terminal metrics flush on EVERY exit
    path — the ``--verify-hier`` / ``--verify-swap`` failure exits
    (SystemExit) used to skip the final ``--metrics-out`` window, which
    is exactly the snapshot a failed verify needs for a post-mortem."""
    try:
        _main()
    finally:
        obs.close_sink()


def _main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-rm2")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--mesh", type=int, default=1,
                    help="row-shard the packed store over an N-way "
                         "'model' mesh (host devices)")
    ap.add_argument("--online", action="store_true",
                    help="serve through repro.serve: hot-row cache + "
                         "priority fold + incremental re-tiering under "
                         "a drifting-zipf workload")
    ap.add_argument("--cache-rows", type=int, default=256,
                    help="top-K fp32 hot rows (--online; 0 disables)")
    ap.add_argument("--retier-every", type=int, default=2,
                    help="requests between delta re-tiers (--online; "
                         "0 disables; smoke-sized default)")
    ap.add_argument("--drift", type=float, default=4.0,
                    help="zipf hot-set drift in ids/request "
                         "(--online; 0 = stationary)")
    ap.add_argument("--serve-batch", type=int, default=0,
                    help="micro-batch N single-user requests per jitted "
                         "forward (--online; 0 = legacy request-at-a-"
                         "time batches of --batch users).  --requests "
                         "then counts single-user requests")
    ap.add_argument("--store-backend", default="packed",
                    choices=("packed", "hier", "hashed"),
                    help="embedding store backend (repro.store.build): "
                         "'packed' = flat tier-partitioned store, "
                         "'hier' = three-level HBM/host/disk "
                         "(equivalent to --hbm-budget-mb), 'hashed' = "
                         "ROBE-style compositional rows materialized "
                         "from a shared chunk pool (--online)")
    ap.add_argument("--hash-ratio", type=float, default=100.0,
                    help="target fp32-table / pool compression ratio "
                         "for --store-backend hashed (pool rows are "
                         "planned from it; 1000x memory at ~1000x)")
    ap.add_argument("--hash-chunk-dim", type=int, default=8,
                    help="pool row width Z for --store-backend hashed "
                         "(must divide the embedding dim)")
    ap.add_argument("--hash-bits", type=int, default=32,
                    choices=(32, 8),
                    help="pool element width for --store-backend "
                         "hashed: 32 = fp32 pool, 8 = int8 pool + "
                         "per-slot scales (the SHARK-rowwise x hashing "
                         "combined mode)")
    ap.add_argument("--hbm-budget-mb", type=float, default=0.0,
                    help="serve through the hierarchical store "
                         "(repro.store): device HBM holds only the "
                         "priority-hot rows under this per-device "
                         "budget, spill goes to host RAM / disk "
                         "(--online --serve-batch; 0 = fully resident)")
    ap.add_argument("--host-budget-mb", type=float, default=0.0,
                    help="warm (host RAM) budget for the hierarchical "
                         "store; 0 = unbounded (no cold level), "
                         ">0 spills the remainder to mmap'd cold "
                         "shards under --store-dir")
    ap.add_argument("--store-dir", default=None,
                    help="directory for the cold shard files + "
                         "manifest (required when --host-budget-mb "
                         "forces a cold level)")
    ap.add_argument("--retier-async", action="store_true",
                    help="shadow-build re-tiers off the request path "
                         "(repro.serve.shadow): the boundary request "
                         "opens a shadow store, later requests advance "
                         "it in bounded chunks, and the finished "
                         "generation is swapped in atomically")
    ap.add_argument("--shadow-rows", type=int, default=512,
                    help="shadow build budget in rows per served "
                         "request (--retier-async)")
    ap.add_argument("--verify-swap", action="store_true",
                    help="at every shadow swap, assert the staged "
                         "generation is bit-identical to a full pack() "
                         "at the snapshot fold state (--retier-async; "
                         "O(vocab) per swap — CI stress smoke)")
    ap.add_argument("--verify-hier", action="store_true",
                    help="after serving, assert the hierarchical "
                         "lookup is bit-identical to a fully "
                         "device-resident pack of the live store over "
                         "the whole vocab (CI spill smoke)")
    ap.add_argument("--fuse-matmul", action="store_true",
                    help="serve through the model's fused head "
                         "(extras['fused_head']): the deep branch's "
                         "first matmul runs fused with the embedding "
                         "gather (kernels.bag_matmul) so the (B, F*D) "
                         "activations never round-trip through HBM "
                         "(--online; wide-deep / xdeepfm archs)")
    ap.add_argument("--autotune-cache", default=None, metavar="PATH",
                    help="measured kernel-tiling cache to serve with "
                         "(sets REPRO_AUTOTUNE_CACHE; seed it with "
                         "benchmarks/kernels.py --seed-cache).  "
                         "Default: results/autotune.json when present")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="enable the repro.obs registry and write "
                         "metrics_snapshot/v1 JSONL here (one line "
                         "every 16 served batches + a final snapshot); "
                         "docs/observability.md")
    ap.add_argument("--metrics-every", type=int, default=16,
                    help="snapshot cadence in served batches for "
                         "--metrics-out (0 = final snapshot only)")
    args = ap.parse_args()
    if args.serve_batch > 0 and not args.online:
        ap.error("--serve-batch requires --online")
    if args.hbm_budget_mb > 0 and args.serve_batch <= 0:
        ap.error("--hbm-budget-mb requires --online --serve-batch N")
    if args.verify_hier and args.hbm_budget_mb <= 0:
        ap.error("--verify-hier requires --hbm-budget-mb")
    if args.retier_async and not args.online:
        ap.error("--retier-async requires --online")
    if args.verify_swap and not args.retier_async:
        ap.error("--verify-swap requires --retier-async")
    if args.fuse_matmul and not args.online:
        ap.error("--fuse-matmul requires --online")
    if args.fuse_matmul and args.hbm_budget_mb > 0:
        ap.error("--fuse-matmul requires a fully resident store "
                 "(no --hbm-budget-mb)")
    if args.hbm_budget_mb > 0 and args.store_backend == "packed":
        args.store_backend = "hier"      # legacy spelling of the flag
    if args.store_backend == "hier" and args.hbm_budget_mb <= 0:
        ap.error("--store-backend hier needs --hbm-budget-mb")
    if args.store_backend == "hashed":
        if not args.online:
            ap.error("--store-backend hashed requires --online")
        if args.hbm_budget_mb > 0:
            ap.error("--store-backend hashed is incompatible with "
                     "--hbm-budget-mb")
        if args.fuse_matmul:
            ap.error("--store-backend hashed has no fused bag->matmul "
                     "path (rows materialize on the fly)")
        if args.verify_hier:
            ap.error("--verify-hier requires the hier backend")
    if args.autotune_cache:
        import os
        os.environ["REPRO_AUTOTUNE_CACHE"] = args.autotune_cache

    from repro.launch import (force_host_device_count,
                              use_compile_cache)
    force_host_device_count(args.mesh)
    use_compile_cache()

    import jax
    import jax.numpy as jnp

    if args.metrics_out:
        from repro.serve.loop import SERVE_PHASES
        obs.enable()
        # pre-register the full phase catalog so snapshots carry every
        # histogram even for phases this run never exercises (e.g.
        # store.stage/migrate when the store is fully device-resident)
        obs.ensure_histograms(f"{p}_us" for p in SERVE_PHASES)
        obs.set_sink(obs.JsonlSink(args.metrics_out,
                                   every=args.metrics_every))

    from repro import configs
    from repro.core import pack
    from repro.core.packed_store import lookup_fused as packed_lookup
    from repro.models import embedding as E

    arch = configs.get(args.arch)
    if arch.family != "recsys" or arch.seq_model:
        raise SystemExit("serve driver supports field-based recsys archs")
    model, num_dense, _ = arch.driver_model()
    spec = model.spec
    params = model.init(jax.random.PRNGKey(0))
    dev = jax.devices()[0]
    where = f"{dev.platform} {dev.device_kind}, mesh={args.mesh}"

    # fabricate a zipf priority profile and pack at a 50% budget
    store, cfg = build_serving_store(spec, params["embed_table"])
    pri = store.priority
    fp32 = spec.total_rows * spec.dim * 4

    mesh = None
    if args.mesh > 1:
        from repro.launch.mesh import make_model_mesh
        mesh = make_model_mesh(args.mesh)

    f = spec.num_fields
    cards = np.asarray(spec.cardinalities, np.int64)

    def uniform_batch(r: int) -> np.ndarray:
        # per-field uniform draws: every field samples its own id range
        # (a single min(cards) range would never exercise the rows of
        # high-cardinality fields)
        rr = np.random.default_rng(r)
        return (rr.random((args.batch, f)) * cards[None, :]).astype(
            np.int32)

    def full_batch(idx: np.ndarray, r: int) -> dict:
        batch = {"indices": jnp.asarray(idx),
                 "labels": jnp.zeros((args.batch,))}
        if arch.has_dense:
            rr = np.random.default_rng(10_000 + r)
            batch["dense"] = jnp.asarray(rr.standard_normal(
                (args.batch, num_dense)).astype(np.float32))
        return batch

    rec = {"arch": args.arch, "batch": args.batch,
           "requests": args.requests, "mesh": args.mesh,
           "online": args.online}

    if args.online:
        from repro.serve import (OnlineConfig, OnlineServer,
                                 serve_forward, serve_forward_loop,
                                 stream_bytes_per_request)

        hier_cfg = None
        backend = None
        if args.store_backend == "hier":
            from repro.store import HierConfig
            host_budget = (int(args.host_budget_mb * 2 ** 20)
                           if args.host_budget_mb > 0 else None)
            hier_cfg = HierConfig(
                hbm_budget_bytes=int(args.hbm_budget_mb * 2 ** 20),
                host_budget_bytes=host_budget,
                store_dir=args.store_dir)
        elif args.store_backend == "hashed":
            from repro.store import (HashedConfig, build,
                                     fit_pool_from_table,
                                     plan_pool_slots, quantize_pool)
            slots = plan_pool_slots(spec.total_rows, spec.dim,
                                    args.hash_chunk_dim,
                                    args.hash_ratio,
                                    pool_bits=args.hash_bits)
            hcfg = HashedConfig(vocab=spec.total_rows, dim=spec.dim,
                                chunk_dim=args.hash_chunk_dim,
                                num_slots=slots,
                                pool_bits=args.hash_bits)
            hs = fit_pool_from_table(store.table, hcfg, priority=pri)
            if args.hash_bits == 8:
                hs = quantize_pool(hs)
            backend = build("hashed", hs, hcfg, mesh=mesh)
        server = OnlineServer(
            store, cfg,
            OnlineConfig(cache_rows=args.cache_rows,
                         retier_every=args.retier_every,
                         retier_async=args.retier_async,
                         shadow_rows_per_step=args.shadow_rows,
                         verify_swap=args.verify_swap),
            mesh=mesh, hier=hier_cfg, backend=backend)
        packed_bytes = server.backend.nbytes()
        tiers_at_pack = None
        if server.hier is not None:
            tiers_at_pack = server.hier.tiers.copy()
            print(f"hier {packed_bytes / 2 ** 20:.2f} MiB total, "
                  f"levels {server.hier.nbytes()} "
                  f"rows {server.hier.counts()}")
        elif args.store_backend == "hashed":
            print(f"hashed pool {hcfg.num_slots} x {hcfg.chunk_dim} "
                  f"@ {args.hash_bits}b = "
                  f"{packed_bytes / 2 ** 20:.3f} MiB "
                  f"({fp32 / packed_bytes:.0f}x vs fp32 table)")
        else:
            from repro.core.packed_store import packed_tiers
            tiers_at_pack = packed_tiers(server.host_packed)
        print(f"packed {packed_bytes / 2 ** 20:.2f} MiB "
              f"({packed_bytes / fp32:.1%} of fp32), "
              f"cache {args.cache_rows} rows, "
              f"retier every {args.retier_every} requests")
        if args.serve_batch > 0:
            if tiers_at_pack is not None:
                rec.update(stream_bytes_per_request(
                    tiers_at_pack, spec, args.requests,
                    drift=args.drift))
            result = serve_forward(
                server, model, spec, params,
                serve_batch=args.serve_batch,
                requests=args.requests, drift=args.drift,
                num_dense=num_dense, fuse_matmul=args.fuse_matmul)
            shape_note = (f"{args.requests} requests micro-batched "
                          f"x{args.serve_batch}")
        else:
            result = serve_forward_loop(
                server, model, spec, params, batch=args.batch,
                requests=args.requests, drift=args.drift,
                num_dense=num_dense, fuse_matmul=args.fuse_matmul)
            shape_note = f"{args.requests} requests x{args.batch}"
        if args.retier_async:
            # finish any in-flight shadow build synchronously so the
            # process exits on a committed generation (verify_swap
            # covers this final swap too)
            server.drain_shadow()
            print(f"shadow: {server.stats.shadow_builds} builds, "
                  f"{server.stats.shadow_chunks} chunks, "
                  f"{server.stats.swaps} swaps"
                  + (" (bit-identity verified at every swap)"
                     if args.verify_swap else ""))
        print(f"{shape_note}: "
              f"p50 {result.p50_us:.0f}us p99 {result.p99_us:.0f}us "
              f"steady {result.steady_qps:.0f} qps "
              f"hit-rate {server.stats.hit_rate:.1%} "
              f"retiers {server.stats.retiers} "
              f"rows moved {server.stats.rows_moved} ({where})")
        rec.update(result.as_dict())
        rec.update({"cache_rows": args.cache_rows,
                    "retier_every": args.retier_every,
                    "retier_async": args.retier_async,
                    "drift": args.drift,
                    "serve_batch": args.serve_batch,
                    "fuse_matmul": args.fuse_matmul,
                    "store_backend": args.store_backend,
                    "packed_mib": round(packed_bytes / 2 ** 20, 3),
                    "packed_fp32_ratio": round(packed_bytes / fp32, 4)})
        if server.hier is not None:
            rec["hbm_budget_mb"] = args.hbm_budget_mb
        if args.store_backend == "hashed":
            rec.update({"pool_slots": int(hcfg.num_slots),
                        "hash_bits": args.hash_bits,
                        "hash_ratio": round(fp32 / packed_bytes, 2)})
        if args.verify_hier:
            from repro.core import packed_store as ps
            from repro.store import hier_lookup

            # bit-identity holds *at re-tier boundaries* (the
            # repack_delta contract): the hier tiers date from the last
            # migrate, while a fresh pack would use the live EMA.  Fold
            # any post-migration priority movement in first, so the
            # check is meaningful for any --requests/--retier-every
            # combination.
            server.retier()
            probe = jnp.arange(server.hier.vocab)
            ref = np.asarray(ps.lookup(pack(server.store, cfg), probe))
            got = np.asarray(hier_lookup(server.hier, probe))
            if not np.array_equal(ref, got):
                raise SystemExit(
                    "hier verify FAILED: hierarchical lookup is not "
                    "bit-identical to the fully resident pack")
            print(f"hier verify OK: {server.hier.vocab} rows "
                  "bit-identical across "
                  f"{server.hier.counts()} after "
                  f"{server.hier.stats.migrations} migrations")
        obs.flush()
        print(json.dumps(rec))
        return

    packed = pack(store, cfg)
    packed_bytes = packed.nbytes()
    packed_mib = packed_bytes / 2 ** 20
    print(f"packed {packed_mib:.2f} MiB ({packed_bytes/fp32:.1%} of fp32)")

    from repro.dist.packed import place_packed, sharded_lookup
    packed = place_packed(packed, mesh)

    @jax.jit
    def serve(packed, params, batch):
        gidx = E.globalize(batch["indices"], spec)
        if mesh is not None:
            emb = sharded_lookup(packed, gidx, mesh=mesh)
        else:
            emb = packed_lookup(packed, gidx)
        return model.head(params, emb, batch)

    lat = []
    for r in range(args.requests):
        batch = full_batch(uniform_batch(r), r)
        with obs.timeblock("serve.request") as tb:
            tb.sync(serve(packed, params, batch))
        lat.append(tb.seconds)
        obs.tick()
    lat_us = np.asarray(lat[1:] if len(lat) > 1 else lat) * 1e6
    p50 = float(np.percentile(lat_us, 50))
    p99 = float(np.percentile(lat_us, 99))
    qps = args.batch / (np.mean(lat_us) / 1e6)
    print(f"{args.requests} requests x{args.batch}: "
          f"p50 {p50:.0f}us p99 {p99:.0f}us ({where})")
    rec.update({"qps": round(qps, 1),
                "p50_us": round(p50, 1), "p99_us": round(p99, 1),
                "packed_mib": round(packed_mib, 3),
                "packed_fp32_ratio": round(packed_bytes / fp32, 4)})
    obs.flush()
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
