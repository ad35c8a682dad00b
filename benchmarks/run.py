"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived carries the
table-specific payload as key=value pairs).

    PYTHONPATH=src python -m benchmarks.run [--fast]

``--emit PATH`` instead regenerates ONE committed benchmark artifact
and skips the CSV jobs: the output basename is looked up in
``benchmarks.manifest.COMMITTED_BENCH`` (BENCH_qps.json,
BENCH_hier.json, BENCH_pipeline.json, BENCH_kernel.json,
BENCH_hash.json; BENCH_fleet.json points at its own driver) and the
matching stable-schema record is written — the perf-trajectory files
future PRs diff against.  ``tools/check_bench_schema.py --committed``
validates the same manifest, so the emit and gate lists cannot drift.
"""

from __future__ import annotations

import argparse
import sys
import time


def _emit(name: str, t0: float, rows) -> None:
    us = (time.perf_counter() - t0) * 1e6
    for row in rows:
        payload = ";".join(f"{k}={v}" for k, v in row.items())
        print(f"{name},{us:.0f},{payload}")
    sys.stdout.flush()


def _emit_bench_record(name: str, path: str, args) -> None:
    """Emit one committed benchmark artifact, dispatched on the output
    file's basename through ``benchmarks.manifest.COMMITTED_BENCH`` —
    the same table the bench-schema CI gate validates against, so the
    set of emittable records and the set of gated records cannot
    drift."""
    import json

    from benchmarks.manifest import COMMITTED_BENCH

    fast = args.fast
    entry = COMMITTED_BENCH.get(name)
    if entry is None:
        known = ", ".join(sorted(COMMITTED_BENCH))
        raise SystemExit(f"--emit {name}: not a committed benchmark "
                         f"artifact (manifest: {known})")

    if name == "BENCH_qps.json":
        from benchmarks import qps

        rec = qps.run_online_sweep(
            qps._parse_serve_batches(args.serve_batches),
            requests=96 if fast else 384,
            retier_every=32 if fast else 128,
            retier_async=args.retier_async)
    elif name == "BENCH_pipeline.json":
        from repro.launch.pipeline import (PipelineConfig, fast_config,
                                           run_pipeline,
                                           verify_failures)

        cfg = fast_config() if fast else PipelineConfig()
        rec = run_pipeline(cfg)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, sort_keys=True)
        print(f"wrote {path}")
        failures = verify_failures(rec)
        if failures:
            raise SystemExit(f"pipeline verify FAILED: {failures}")
        return
    elif name == "BENCH_hier.json":
        from benchmarks import hier

        rec = hier.run_hier_sweep(
            fractions=(0.1, 0.5) if fast else (0.05, 0.15, 0.4, 1.0),
            requests=64 if fast else 256,
            retier_async=args.retier_async)
    elif name == "BENCH_hash.json":
        from benchmarks import hashed

        rec = hashed.run_hashed_sweep(
            ratios=(4.0, 100.0) if fast else (1.0, 4.0, 20.0, 100.0,
                                              1000.0),
            train_steps=120 if fast else 700,
            requests=32 if fast else 96,
            eval_batches=4 if fast else 16)
    elif name == "BENCH_kernel.json":
        from benchmarks import kernels

        rec = kernels.run(iters=1 if fast else 2)
    else:
        _, hint = entry
        raise SystemExit(f"{name} is emitted by its own driver: "
                         f"`{hint}`")

    from benchmarks.qps import write_bench_json

    write_bench_json(rec, path)
    print(f"wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced budgets (CI)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--emit", default=None, metavar="PATH",
                    help="write the micro-batched serving sweep as a "
                         "stable-schema bench_qps/v1 JSON file and skip "
                         "the CSV jobs")
    ap.add_argument("--emit-pipeline", default=None, metavar="PATH",
                    help="run the end-to-end train->prune->quantize->"
                         "pack->serve pipeline and write its "
                         "bench_pipeline/v1 record (repro.launch."
                         "pipeline); skips the CSV jobs")
    ap.add_argument("--serve-batches", default="1,8,32",
                    help="fusion factors for --emit (comma-separated)")
    ap.add_argument("--retier-async", action="store_true",
                    help="--emit serves with the chunked shadow build "
                         "+ swap instead of the synchronous repack")
    args = ap.parse_args()
    fast = args.fast

    if args.emit_pipeline:
        _emit_bench_record("BENCH_pipeline.json", args.emit_pipeline,
                           args)
        return

    if args.emit:
        import os

        _emit_bench_record(os.path.basename(args.emit), args.emit,
                           args)
        return

    from benchmarks import (fig2_fperm, fig3_thresholds, freq_error,
                            hashed, qps, qps_sharded, roofline,
                            table2_time, table3_fquant,
                            table4_combined)

    # qps_sharded first: its serve children each need the device, which
    # this process holds from the first job that touches a backend on
    jobs = {
        "qps_sharded": lambda: qps_sharded.run(
            requests=24 if fast else 48,
            serve_batches=(8,) if fast else (1, 8)),
        "table2_time": lambda: table2_time.run(
            eval_batches=2 if fast else 4, shuffles=1 if fast else 2),
        "table3_fquant": lambda: table3_fquant.run(
            train_steps=150 if fast else 800),
        "fig3_thresholds": lambda: fig3_thresholds.run(
            train_steps=150 if fast else 800,
            t16_grid=(1e-1, 1e1) if fast else (1e-2, 1e-1, 1e0, 1e1),
            t8_grid=(1e-1, 1e1) if fast else (1e-2, 1e-1, 1e0, 1e1)),
        "table4_combined": lambda: table4_combined.run(
            train_steps=150 if fast else 800),
        "fig2_fperm": lambda: fig2_fperm.run(
            train_steps=150 if fast else 800,
            keep_counts=(6,) if fast else (8, 6, 4),
            finetune_steps=40 if fast else 150),
        "qps": lambda: qps.run(iters=5 if fast else 20),
        "freq_error": lambda: freq_error.run(
            train_steps=100 if fast else 400),
        "hashed": lambda: hashed.run(fast=fast),
        "roofline": roofline.run,
    }
    if args.only:
        jobs = {k: v for k, v in jobs.items() if k == args.only}

    failed = []
    for name, job in jobs.items():
        t0 = time.perf_counter()
        try:
            rows = job()
        except Exception as e:  # noqa: BLE001 - report, run the rest
            print(f"{name},0,error={type(e).__name__}:{e}")
            failed.append(name)
            continue
        _emit(name, t0, rows)
    if failed:
        raise SystemExit(f"benchmark jobs failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
