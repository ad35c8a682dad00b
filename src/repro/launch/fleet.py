"""Fleet ops driver: ``python -m repro.launch.fleet --replicas 1,2,4``.

Scales a multi-replica serving fabric (``repro.serve.fleet``) across a
sweep of replica counts under ONE synthetic drifting-zipf request
stream, and emits a ``bench_fleet/v1`` record.  Per replica count:

  1. build N ``OnlineServer`` replicas off the same packed store (each
     with its own named metrics registry, all sharing one jitted
     forward — identical payload shapes means one XLA compile serves
     the whole fleet);
  2. route ``--requests`` single-user requests through the router
     (``--policy round_robin | least_outstanding``), with
     fleet-staggered re-tiers every ``--retier-every`` requests and a
     cross-replica Eq. 7 priority merge every ``--merge-every``;
  3. aggregate: fleet percentiles from the exact cross-replica
     histogram merge (``obs.FleetAggregator``), router overhead from
     the timed routing decision, priority divergence pre/post merge,
     tier-occupancy skew and swap co-scheduling from the fleet gauges.

Replicas are in-process faked hosts timesharing this CPU, so
``aggregate_qps`` is the capacity sum — each replica's steady QPS over
its own busy time — the throughput N independent hosts would deliver
(see ``repro.serve.fleet``; the router/GIL costs ARE measured, as
``router_overhead_frac``).

``--metrics-out DIR`` writes one ``metrics_snapshot/v1`` JSONL stream
per source (``replicasN_replica0.jsonl`` ... ``replicasN_router.jsonl``)
plus the merged fleet stream (``replicasN_fleet.jsonl``) — re-merge
them offline with ``tools/summarize_metrics.py``.  The last stdout
line is the ``bench_fleet/v1`` record (``--emit PATH`` also writes it
to a file; committed as BENCH_fleet.json, validated by
``tools/check_bench_schema.py``).
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro import obs


def main() -> None:
    """CLI wrapper: terminal metrics flush on every exit path (the
    same ``close_sink`` contract as ``launch.serve`` /
    ``launch.pipeline``)."""
    try:
        _main()
    finally:
        obs.close_sink()


def _main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-rm2")
    ap.add_argument("--requests", type=int, default=256,
                    help="single-user requests per replica-count run "
                         "(one shared drifting-zipf stream)")
    ap.add_argument("--serve-batch", type=int, default=8,
                    help="micro-batch capacity per replica")
    ap.add_argument("--replicas", default="1,2,4,8",
                    help="comma-separated replica counts to sweep")
    ap.add_argument("--policy", default="round_robin",
                    choices=("round_robin", "least_outstanding"))
    ap.add_argument("--merge-every", type=int, default=64,
                    help="fleet requests between cross-replica Eq. 7 "
                         "priority merges (0 = never merge)")
    ap.add_argument("--retier-every", type=int, default=64,
                    help="per-replica re-tier cadence in fleet "
                         "requests, staggered across replicas "
                         "(0 = never)")
    ap.add_argument("--retier-async", action="store_true",
                    help="shadow-build re-tiers off the request path "
                         "(repro.serve.shadow) instead of inline "
                         "repacks")
    ap.add_argument("--cache-rows", type=int, default=128,
                    help="top-K fp32 hot rows per replica (0 disables)")
    ap.add_argument("--drift", type=float, default=4.0,
                    help="zipf hot-set drift in ids/request")
    ap.add_argument("--metrics-out", default=None, metavar="DIR",
                    help="write per-source metrics_snapshot/v1 JSONL "
                         "streams (one per replica + router + merged "
                         "fleet) into this directory")
    ap.add_argument("--emit", default=None, metavar="PATH",
                    help="also write the bench_fleet/v1 record here")
    args = ap.parse_args()
    counts = sorted({int(c) for c in args.replicas.split(",") if c})
    if not counts or min(counts) < 1:
        ap.error("--replicas needs positive integers")

    from repro.launch import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core.packed_store import lookup_fused
    from repro.launch.serve import build_serving_store
    from repro.models import embedding as E
    from repro.serve import (Fleet, FleetConfig, OnlineConfig,
                             OnlineServer, Replica, drifting_zipf_batch,
                             run_fleet)
    from repro.serve.cache import cached_lookup

    arch = configs.get(args.arch)
    if arch.family != "recsys" or arch.seq_model:
        raise SystemExit("fleet driver supports field-based recsys "
                         "archs")
    # the smoke config on every backend: each replica holds its own
    # packed store (~1.4 GB at the chip config), so 8 replicas of the
    # chip config would not fit one 16 GB chip, and no fleet run on a
    # chip exists yet
    model, num_dense, _ = arch.driver_model(chip=False)
    spec = model.spec
    params = model.init(jax.random.PRNGKey(0))
    store, cfg = build_serving_store(spec, params["embed_table"], seed=0)

    cards = np.asarray(spec.cardinalities, np.int64)
    offsets = np.asarray(spec.offsets(), np.int64)

    # ONE jitted forward for every replica at every replica count:
    # identical payload shapes -> the whole sweep shares one compile
    # (re-tiers recompile per new shape, also shared when replicas'
    # shapes coincide)
    @jax.jit
    def fwd(packed, cache, net, b, valid):
        gidx = E.globalize(b["indices"], spec)
        emb, hits = cached_lookup(packed, cache, gidx, lookup_fused,
                                  valid=valid[:, None])
        return model.head(net, emb, b), hits, gidx

    def make_replica(rid: int) -> Replica:
        server = OnlineServer(
            store, cfg,
            OnlineConfig(cache_rows=args.cache_rows,
                         retier_every=0,   # the FLEET schedules
                                           # (staggered) re-tiers
                         retier_async=args.retier_async))
        last: dict = {}
        counter = {"b": 0}

        def _warm(staged) -> None:
            if "a" in last:
                b, valid = last["a"]
                jax.block_until_ready(
                    fwd(staged, server.cache, params, b, valid))
        server.warmup_fn = _warm

        def serve_fn(mb):
            r = counter["b"]
            counter["b"] += 1
            b = {"indices": jnp.asarray(mb.indices),
                 "labels": jnp.zeros((mb.indices.shape[0],))}
            if num_dense:
                rr = np.random.default_rng(20_000 + r)
                b["dense"] = jnp.asarray(rr.standard_normal(
                    (mb.indices.shape[0], num_dense))
                    .astype(np.float32))
            valid = jnp.asarray(mb.valid)
            last["a"] = (b, valid)
            with obs.span("serve.lookup"):
                out, hits, gidx = fwd(server.packed, server.cache,
                                      params, b, valid)
                jax.block_until_ready(out)
            server.observe(gidx, int(hits),
                           valid=mb.valid[:, None], count=mb.count)
            return out

        return Replica(
            rid, server, serve_fn, args.serve_batch, spec.num_fields,
            globalize=lambda idx: idx.astype(np.int64)
            + offsets[None, :])

    if args.metrics_out:
        os.makedirs(args.metrics_out, exist_ok=True)

    # warm the shared forward once so the first sweep entry's latency
    # stream doesn't carry the XLA compile (re-tier recompiles stay in
    # — they are flagged out of the steady windows instead)
    wsrv = OnlineServer(store, cfg,
                        OnlineConfig(cache_rows=args.cache_rows))
    wb = {"indices": jnp.zeros((args.serve_batch, spec.num_fields),
                               jnp.int32),
          "labels": jnp.zeros((args.serve_batch,))}
    if num_dense:
        wb["dense"] = jnp.zeros((args.serve_batch, num_dense),
                                jnp.float32)
    jax.block_until_ready(
        fwd(wsrv.packed, wsrv.cache, params, wb,
            jnp.ones((args.serve_batch,), bool))[0])
    del wsrv, wb

    sweep = []
    for n in counts:
        fleet = Fleet([make_replica(i) for i in range(n)],
                      FleetConfig(policy=args.policy,
                                  serve_batch=args.serve_batch,
                                  merge_every=args.merge_every,
                                  retier_every=args.retier_every))
        paths = None
        if args.metrics_out:
            paths = [os.path.join(args.metrics_out,
                                  f"replicas{n}_replica{i}.jsonl")
                     for i in range(n)]
            paths.append(os.path.join(args.metrics_out,
                                      f"replicas{n}_router.jsonl"))
        res = run_fleet(
            fleet,
            lambda r: drifting_zipf_batch(
                cards, 1, r, args.requests, drift=args.drift)[0],
            args.requests, jsonl_paths=paths)
        if args.metrics_out:
            # the merged fleet stream: same schema, one line, proven
            # equal to re-merging the per-source lines offline
            sink = obs.JsonlSink(os.path.join(
                args.metrics_out, f"replicas{n}_fleet.jsonl"))
            sink.write(fleet.aggregate().merged())
        entry = res.as_dict()
        sweep.append(entry)
        print(f"replicas={n}: aggregate {entry['aggregate_qps']:.0f} "
              f"qps, fleet p50 {entry['p50_us']:.0f}us "
              f"p99 {entry['p99_us']:.0f}us, route p50 "
              f"{entry['route_p50_us']:.1f}us "
              f"({entry['router_overhead_frac']:.2%} of per-request "
              f"p50), merges {entry['merges']}, divergence "
              f"{entry['divergence_premerge']:.4f} -> "
              f"{entry['divergence']:.4f}")

    rec = {"schema": "bench_fleet/v1", "benchmark": "fleet",
           "arch": args.arch, "policy": args.policy,
           "serve_batch": args.serve_batch, "requests": args.requests,
           "merge_every": args.merge_every,
           "retier_every": args.retier_every,
           "retier_async": bool(args.retier_async),
           "drift": args.drift, "sweep": sweep}
    if args.emit:
        with open(args.emit, "w") as f:
            json.dump(rec, f, indent=1, sort_keys=True)
        print(f"wrote {args.emit}")
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
