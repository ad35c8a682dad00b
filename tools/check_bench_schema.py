#!/usr/bin/env python
"""Validate benchmark / metrics JSON files (``bench_qps/v1`` /
``bench_hier/v1`` / ``bench_pipeline/v1`` / ``bench_kernel/v1`` /
``metrics_snapshot/v1``).

    python tools/check_bench_schema.py [FILE ...]

Accepts any number of files (default ``BENCH_qps.json``).  ``.jsonl``
files are validated line by line — every line must be a valid record
(this is how ``--metrics-out`` snapshot streams are checked).

The schemas are the stable contract between PRs: benchmarks emit them
(``benchmarks/qps.py --online --serve-batch ...``,
``benchmarks/qps_sharded.py``, ``benchmarks/run.py --emit``,
``benchmarks/hier.py``, ``benchmarks/kernels.py --emit``,
``repro.launch.pipeline --emit``), the launch
drivers emit metrics snapshots (``--metrics-out``), CI validates them,
future PRs diff the entries for regressions.  Documented in
docs/serving.md, docs/storage.md, docs/training.md and
docs/observability.md.  The schema is picked from the record's
``"schema"`` key.

Exit 0 = valid; exit 1 prints every violation found.
"""

from __future__ import annotations

import json
import numbers
import sys

QPS_TOP = {
    "schema": str,
    "benchmark": str,
    "requests": numbers.Integral,
    "cache_rows": numbers.Integral,
    "retier_every": numbers.Integral,
    "drift": numbers.Real,
    "retier_async": bool,
    "packed_fp32_ratio": numbers.Real,
    "bytes_per_request_fp32": numbers.Integral,
    "bytes_per_request_packed": numbers.Integral,
    "sweep": list,
}

# histogram-derived latency columns every online sweep entry carries
# (serve.loop.LoopResult.as_dict); p99_retier_attributed is the
# fraction of the p99 tail's wall time spent inside retier/migrate,
# p99_while_retiering the p99 over only the warm batches that
# overlapped shadow build / swap work (0.0 when there were none)
LATENCY_KEYS = {
    "p95_us": numbers.Real,
    "latency_p50": numbers.Real,
    "latency_p95": numbers.Real,
    "latency_p99": numbers.Real,
    "p99_retier_attributed": numbers.Real,
    "p99_while_retiering": numbers.Real,
}

# with --retier-async the re-tier runs as a chunked shadow build off
# the request path; the whole point is the tail, so entries must hold
# the p99 (overall AND during re-tiering) to this multiple of the p50
RETIER_TAIL_BUDGET = 10.0

QPS_SWEEP = {
    "serve_batch": numbers.Integral,
    "qps": numbers.Real,
    "steady_qps": numbers.Real,
    "p50_us": numbers.Real,
    "p99_us": numbers.Real,
    "requests": numbers.Integral,
    "lookups": numbers.Integral,
    "hits": numbers.Integral,
    "cache_hit_rate": numbers.Real,
    "retiers": numbers.Integral,
    "rows_moved": numbers.Integral,
    "swaps": numbers.Integral,
    "shadow_builds": numbers.Integral,
    "bytes_per_request_fp32": numbers.Integral,
    "bytes_per_request_packed": numbers.Integral,
    **LATENCY_KEYS,
}

HIER_TOP = {
    "schema": str,
    "benchmark": str,
    "requests": numbers.Integral,
    "serve_batch": numbers.Integral,
    "cache_rows": numbers.Integral,
    "retier_every": numbers.Integral,
    "drift": numbers.Real,
    "retier_async": bool,
    "packed_fp32_ratio": numbers.Real,
    "full_store_bytes": numbers.Integral,
    "sweep": list,
}

HIER_SWEEP = {
    "hbm_budget_fraction": numbers.Real,
    "hot_rows": numbers.Integral,
    "warm_rows": numbers.Integral,
    "cold_rows": numbers.Integral,
    "qps": numbers.Real,
    "steady_qps": numbers.Real,
    "p50_us": numbers.Real,
    "p99_us": numbers.Real,
    "lookups": numbers.Integral,
    "cache_hit_rate": numbers.Real,
    "hier_miss_rate": numbers.Real,
    "warm_hits": numbers.Integral,
    "cold_hits": numbers.Integral,
    "staged_rows": numbers.Integral,
    "migrations": numbers.Integral,
    "promoted": numbers.Integral,
    "demoted": numbers.Integral,
    "swaps": numbers.Integral,
    "shadow_builds": numbers.Integral,
    **LATENCY_KEYS,
}


def _check_keys(obj: dict, spec: dict, where: str, errors: list) -> None:
    for key, typ in spec.items():
        if key not in obj:
            errors.append(f"{where}: missing key {key!r}")
            continue
        val = obj[key]
        if typ is bool:
            if not isinstance(val, bool):
                errors.append(f"{where}: {key!r} should be bool, "
                              f"got {type(val).__name__}")
        elif isinstance(val, bool) or not isinstance(val, typ):
            errors.append(f"{where}: {key!r} should be {typ.__name__}, "
                          f"got {type(val).__name__}")


def _check_sweep(rec: dict, spec: dict, errors: list) -> list[dict]:
    sweep = rec.get("sweep")
    entries = []
    if isinstance(sweep, list):
        if not sweep:
            errors.append("sweep: empty")
        for i, entry in enumerate(sweep):
            if not isinstance(entry, dict):
                errors.append(f"sweep[{i}]: not an object")
                continue
            _check_keys(entry, spec, f"sweep[{i}]", errors)
            entries.append(entry)
    return entries


def _is_num(v) -> bool:
    return isinstance(v, numbers.Real) and not isinstance(v, bool)


def _check_latency(entries: list[dict], errors: list) -> None:
    """Shared latency-column invariants for online sweep entries."""
    for i, e in enumerate(entries):
        att = e.get("p99_retier_attributed")
        if _is_num(att) and not 0.0 <= att <= 1.0:
            errors.append(f"sweep[{i}]: p99_retier_attributed {att} "
                          "out of [0, 1]")
        ps = [e.get(k) for k in ("latency_p50", "latency_p95",
                                 "latency_p99")]
        if all(_is_num(p) for p in ps) and \
                not (ps[0] <= ps[1] + 1e-9 <= ps[2] + 2e-9):
            errors.append(f"sweep[{i}]: latency percentiles not "
                          f"monotone (p50 {ps[0]} / p95 {ps[1]} / "
                          f"p99 {ps[2]})")


def _check_tail_budget(rec: dict, entries: list[dict],
                       errors: list) -> None:
    """Async re-tiering's contract: the p99 tail — overall and over the
    batches that overlapped shadow work — stays within
    ``RETIER_TAIL_BUDGET`` x the p50.  Enforced only on records that
    actually re-tiered asynchronously (``retier_async`` true and a
    positive cadence); the synchronous path is what this budget exists
    to indict."""
    if rec.get("retier_async") is not True:
        return
    cadence = rec.get("retier_every")
    if not (isinstance(cadence, numbers.Integral) and cadence > 0):
        return
    for i, e in enumerate(entries):
        p50 = e.get("latency_p50")
        if not _is_num(p50) or p50 <= 0:
            continue
        for key in ("latency_p99", "p99_while_retiering"):
            val = e.get(key)
            if _is_num(val) and val > RETIER_TAIL_BUDGET * p50:
                errors.append(
                    f"sweep[{i}]: {key} {val} exceeds the async "
                    f"re-tier tail budget ({RETIER_TAIL_BUDGET:g}x "
                    f"p50 = {RETIER_TAIL_BUDGET * p50:.1f})")


def _validate_qps(rec: dict) -> list[str]:
    errors: list[str] = []
    _check_keys(rec, QPS_TOP, "top-level", errors)
    entries = _check_sweep(rec, QPS_SWEEP, errors)
    _check_latency(entries, errors)
    _check_tail_budget(rec, entries, errors)
    batches = [e.get("serve_batch") for e in entries]
    if len(set(batches)) != len(batches):
        errors.append("sweep: duplicate serve_batch entries")
    # the whole point of the record: byte traffic must not depend
    # on the fusion factor
    packed = {e.get("bytes_per_request_packed") for e in entries}
    if len(packed) > 1:
        errors.append("sweep: bytes_per_request_packed differs "
                      f"across serve_batch values: {sorted(packed)}")
    return errors


def _validate_hier(rec: dict) -> list[str]:
    errors: list[str] = []
    _check_keys(rec, HIER_TOP, "top-level", errors)
    entries = _check_sweep(rec, HIER_SWEEP, errors)
    _check_latency(entries, errors)
    _check_tail_budget(rec, entries, errors)
    fracs = [e.get("hbm_budget_fraction") for e in entries]
    if len(set(fracs)) != len(fracs):
        errors.append("sweep: duplicate hbm_budget_fraction entries")
    # the whole point of the record: a bigger HBM budget holds a
    # superset of a smaller one's hot rows (prefix placement), so the
    # spill miss rate must fall (weakly) as the budget fraction rises
    ok = [e for e in entries
          if isinstance(e.get("hbm_budget_fraction"), numbers.Real)
          and isinstance(e.get("hier_miss_rate"), numbers.Real)]
    ok.sort(key=lambda e: e["hbm_budget_fraction"])
    for lo, hi in zip(ok, ok[1:]):
        if hi["hier_miss_rate"] > lo["hier_miss_rate"] + 1e-9:
            errors.append(
                "sweep: hier_miss_rate rises with the HBM budget "
                f"fraction ({lo['hbm_budget_fraction']}: "
                f"{lo['hier_miss_rate']} -> "
                f"{hi['hbm_budget_fraction']}: {hi['hier_miss_rate']})")
    return errors


PIPELINE_TOP = {
    "schema": str,
    "benchmark": str,
    "arch": str,
    "mesh": numbers.Integral,
    "train_steps": numbers.Integral,
    "batch": numbers.Integral,
    "train_loss_first": numbers.Real,
    "train_loss_last": numbers.Real,
    "gradcheck_max_abs_err": numbers.Real,
    "fields_total": numbers.Integral,
    "fields_pruned": numbers.Integral,
    "kept_memory_fraction": numbers.Real,
    "tier_rows_int8": numbers.Integral,
    "tier_rows_half": numbers.Integral,
    "tier_rows_fp32": numbers.Integral,
    "bytes_fp32": numbers.Integral,
    "bytes_packed": numbers.Integral,
    "compression_ratio": numbers.Real,
    "eval_loss_fp32": numbers.Real,
    "eval_loss_packed": numbers.Real,
    "eval_auc_fp32": numbers.Real,
    "eval_auc_packed": numbers.Real,
    "serve_requests": numbers.Integral,
    "serve_batch": numbers.Integral,
    "steady_qps": numbers.Real,
    "cache_hit_rate": numbers.Real,
    "retiers": numbers.Integral,
    "verify_pack_bit_identical": bool,
    "verify_serve_bit_identical": bool,
    "verify_grad_fp32_tolerance": bool,
    "verify_accum_checkpointed": bool,
    "stage_seconds": dict,
}

PIPELINE_STAGES = ("train", "prune", "quantize", "pack", "serve")


def _validate_pipeline(rec: dict) -> list[str]:
    errors: list[str] = []
    _check_keys(rec, PIPELINE_TOP, "top-level", errors)
    if errors:
        return errors
    # the whole point of the record: the pipeline must actually
    # compress, and every end-to-end verification must have held
    if rec["bytes_packed"] >= rec["bytes_fp32"]:
        errors.append("bytes_packed >= bytes_fp32: pipeline did not "
                      "compress")
    ratio = rec["bytes_packed"] / max(rec["bytes_fp32"], 1)
    if abs(rec["compression_ratio"] - ratio) > 1e-3:
        errors.append(f"compression_ratio {rec['compression_ratio']} "
                      f"inconsistent with byte counts ({ratio:.4f})")
    for key in ("verify_pack_bit_identical", "verify_serve_bit_identical",
                "verify_grad_fp32_tolerance",
                "verify_accum_checkpointed"):
        if rec[key] is not True:
            errors.append(f"{key}: must be true")
    if not 0 <= rec["fields_pruned"] < rec["fields_total"]:
        errors.append("fields_pruned out of range")
    # the tolerance judgement itself is the driver's (relative to the
    # gradient scale; verify_grad_fp32_tolerance above) — here only
    # sanity-check the recorded error is a valid measurement
    if rec["gradcheck_max_abs_err"] < 0:
        errors.append("gradcheck_max_abs_err negative")
    if not 0.0 <= rec["cache_hit_rate"] <= 1.0:
        errors.append("cache_hit_rate out of [0, 1]")
    tiers = (rec["tier_rows_int8"], rec["tier_rows_half"],
             rec["tier_rows_fp32"])
    if min(tiers) < 0 or sum(tiers) <= 0:
        errors.append("tier_rows_* invalid")
    if rec["mesh"] < 1:
        errors.append("mesh must be >= 1")
    stages = rec["stage_seconds"]
    for stage in PIPELINE_STAGES:
        sec = stages.get(stage)
        if not isinstance(sec, numbers.Real) or isinstance(sec, bool) \
                or sec < 0:
            errors.append(f"stage_seconds[{stage!r}] missing or "
                          "invalid")
    return errors


METRICS_TOP = {
    "schema": str,
    "seq": numbers.Integral,
    "ticks": numbers.Integral,
    "counters": dict,
    "gauges": dict,
    "histograms": dict,
}

METRICS_HIST = {
    "count": numbers.Integral,
    "sum": numbers.Real,
    "min": numbers.Real,
    "max": numbers.Real,
    "p50": numbers.Real,
    "p95": numbers.Real,
    "p99": numbers.Real,
    "buckets": dict,
}


def _validate_metrics(rec: dict) -> list[str]:
    """One ``metrics_snapshot/v1`` record (one ``--metrics-out`` JSONL
    line): name -> number maps plus per-histogram summaries whose
    percentiles must be ordered inside the [min, max] envelope and
    whose sparse bucket counts must re-add to ``count`` (the offline
    re-merge contract)."""
    errors: list[str] = []
    _check_keys(rec, METRICS_TOP, "top-level", errors)
    if errors:
        return errors
    for kind in ("counters", "gauges"):
        for name, val in rec[kind].items():
            if not _is_num(val):
                errors.append(f"{kind}[{name!r}]: not a number")
        if kind == "counters":
            for name, val in rec[kind].items():
                if _is_num(val) and val < 0:
                    errors.append(f"counters[{name!r}]: negative")
    for name, h in rec["histograms"].items():
        where = f"histograms[{name!r}]"
        if not isinstance(h, dict):
            errors.append(f"{where}: not an object")
            continue
        _check_keys(h, METRICS_HIST, where, errors)
        if any(e.startswith(where) for e in errors):
            continue
        n = h["count"]
        if n < 0:
            errors.append(f"{where}: negative count")
        bsum = 0
        for idx, c in h["buckets"].items():
            if not (isinstance(c, numbers.Integral) and c > 0
                    and str(idx).isdigit()):
                errors.append(f"{where}: bad bucket {idx!r}: {c!r}")
                break
            bsum += int(c)
        else:
            if bsum != n:
                errors.append(f"{where}: bucket counts sum to {bsum}, "
                              f"count is {n}")
        if n > 0 and not (h["min"] - 1e-9 <= h["p50"]
                          <= h["p95"] + 1e-9 <= h["p99"] + 2e-9
                          <= h["max"] + 3e-9):
            errors.append(
                f"{where}: percentiles not ordered within [min, max] "
                f"(min {h['min']} p50 {h['p50']} p95 {h['p95']} "
                f"p99 {h['p99']} max {h['max']})")
    return errors


KERNEL_TOP = {
    "schema": str,
    "benchmark": str,
    "backend": str,
    "interpret": bool,
    "hbm_peak_gbs": numbers.Real,
    "sweep": list,
}

KERNEL_SWEEP = {
    "kernel": str,
    "dtype": str,
    "b": numbers.Integral,
    "k": numbers.Integral,
    "d": numbers.Integral,
    "h": numbers.Integral,
    "block_analytic": list,
    "analytic_us": numbers.Real,
    "block_measured": list,
    "measured_us": numbers.Real,
    "speedup": numbers.Real,
    "bytes_moved": numbers.Integral,
    "achieved_gbs": numbers.Real,
    "peak_fraction": numbers.Real,
}

# timing jitter allowance for the measured-vs-analytic invariant;
# the analytic pick is itself a sweep candidate, so only noise between
# two timings of the same tiling can push "measured" past "analytic"
KERNEL_TUNE_EPS = 1e-6


def _validate_kernel(rec: dict) -> list[str]:
    """``bench_kernel/v1`` (benchmarks/kernels.py): measured tiling
    sweeps.  The whole point of the record: the measured-autotune
    winner is at least as fast as the analytic pick on EVERY swept
    shape — the sweep includes the analytic pick as a candidate, so a
    violation means the sweep/cache machinery regressed, not that the
    analytic model is good."""
    errors: list[str] = []
    _check_keys(rec, KERNEL_TOP, "top-level", errors)
    entries = _check_sweep(rec, KERNEL_SWEEP, errors)
    seen = set()
    for i, e in enumerate(entries):
        key = (e.get("kernel"), e.get("dtype"), e.get("b"),
               e.get("k"), e.get("d"), e.get("h"))
        if key in seen:
            errors.append(f"sweep[{i}]: duplicate shape entry {key}")
        seen.add(key)
        ua, um = e.get("analytic_us"), e.get("measured_us")
        if _is_num(ua) and _is_num(um):
            if um <= 0 or ua <= 0:
                errors.append(f"sweep[{i}]: non-positive timing "
                              f"(analytic {ua}, measured {um})")
            elif um > ua * (1.0 + KERNEL_TUNE_EPS):
                errors.append(
                    f"sweep[{i}]: measured tiling slower than the "
                    f"analytic pick ({e.get('kernel')} b={e.get('b')} "
                    f"k={e.get('k')} d={e.get('d')}: measured {um}us "
                    f"> analytic {ua}us)")
            sp = e.get("speedup")
            if _is_num(sp) and um > 0 and abs(sp - ua / um) > 1e-3 * sp:
                errors.append(f"sweep[{i}]: speedup {sp} inconsistent "
                              f"with timings ({ua / um:.4f})")
        for kk in ("block_analytic", "block_measured"):
            blk = e.get(kk)
            # (block_b,) for the bag kernels, (block_b, block_h) for
            # bag_matmul
            if isinstance(blk, list) and not (
                    len(blk) in (1, 2)
                    and all(isinstance(x, numbers.Integral)
                            and not isinstance(x, bool) and x >= 1
                            for x in blk)):
                errors.append(f"sweep[{i}]: {kk} must be one or two "
                              f"ints >= 1, got {blk!r}")
        for kk in ("bytes_moved", "achieved_gbs", "peak_fraction"):
            v = e.get(kk)
            if _is_num(v) and v <= 0:
                errors.append(f"sweep[{i}]: {kk} must be positive, "
                              f"got {v}")
    return errors


FLEET_TOP = {
    "schema": str,
    "benchmark": str,
    "arch": str,
    "policy": str,
    "serve_batch": numbers.Integral,
    "requests": numbers.Integral,
    "merge_every": numbers.Integral,
    "retier_every": numbers.Integral,
    "retier_async": bool,
    "drift": numbers.Real,
    "sweep": list,
}

FLEET_SWEEP = {
    "replicas": numbers.Integral,
    "policy": str,
    "aggregate_qps": numbers.Real,
    "per_replica_qps": list,
    "p50_us": numbers.Real,
    "p95_us": numbers.Real,
    "p99_us": numbers.Real,
    "route_p50_us": numbers.Real,
    "router_overhead_frac": numbers.Real,
    "requests": numbers.Integral,
    "merges": numbers.Integral,
    "divergence": numbers.Real,
    "divergence_premerge": numbers.Real,
    "swaps_colocated": numbers.Integral,
}

# the routing decision must be noise next to the work it routes:
# route-time p50 stays under this fraction of the per-request p50
FLEET_ROUTER_BUDGET = 0.10

# aggregate capacity QPS must not DROP as replicas are added, up to
# this replica count (beyond it, per-replica request starvation on the
# fixed smoke stream makes steady windows too thin to gate on)
FLEET_MONOTONE_UPTO = 4


def _validate_fleet(rec: dict) -> list[str]:
    """``bench_fleet/v1`` (repro.launch.fleet): replica-scaling sweep.
    The load-bearing invariants: fleet capacity is monotone in replica
    count (up to ``FLEET_MONOTONE_UPTO``), the router's decision cost
    stays under ``FLEET_ROUTER_BUDGET`` of the per-request p50, fleet
    percentiles are ordered (they come from the exact cross-replica
    bucket merge — a violation means the merge regressed), and the
    periodic priority merge drives cross-replica divergence DOWN."""
    errors: list[str] = []
    _check_keys(rec, FLEET_TOP, "top-level", errors)
    entries = _check_sweep(rec, FLEET_SWEEP, errors)
    reps = [e.get("replicas") for e in entries]
    if len(set(reps)) != len(reps):
        errors.append("sweep: duplicate replica-count entries")
    for i, e in enumerate(entries):
        ps = [e.get(k) for k in ("p50_us", "p95_us", "p99_us")]
        if all(_is_num(p) for p in ps) and \
                not (ps[0] <= ps[1] + 1e-9 <= ps[2] + 2e-9):
            errors.append(f"sweep[{i}]: fleet percentiles not monotone "
                          f"(p50 {ps[0]} / p95 {ps[1]} / p99 {ps[2]})")
        frac = e.get("router_overhead_frac")
        if _is_num(frac) and not 0.0 <= frac < FLEET_ROUTER_BUDGET:
            errors.append(
                f"sweep[{i}]: router_overhead_frac {frac} outside "
                f"[0, {FLEET_ROUTER_BUDGET}) — the routing decision "
                "must be noise next to the per-request p50")
        per = e.get("per_replica_qps")
        n = e.get("replicas")
        if isinstance(per, list) and isinstance(n, numbers.Integral):
            if len(per) != n:
                errors.append(f"sweep[{i}]: per_replica_qps has "
                              f"{len(per)} entries for {n} replicas")
            if not all(_is_num(q) and q > 0 for q in per):
                errors.append(f"sweep[{i}]: per_replica_qps entries "
                              "must be positive numbers")
        d, dp = e.get("divergence"), e.get("divergence_premerge")
        if _is_num(d) and d < 0:
            errors.append(f"sweep[{i}]: divergence negative")
        if _is_num(d) and _is_num(dp) and e.get("merges", 0) \
                and isinstance(n, numbers.Integral) and n > 1 \
                and d > dp + 1e-9:
            errors.append(
                f"sweep[{i}]: divergence {d} above pre-merge "
                f"divergence {dp} — the periodic Eq. 7 merge must "
                "drive it down")
    ok = [e for e in entries
          if isinstance(e.get("replicas"), numbers.Integral)
          and _is_num(e.get("aggregate_qps"))]
    ok.sort(key=lambda e: e["replicas"])
    for lo, hi in zip(ok, ok[1:]):
        if hi["replicas"] > FLEET_MONOTONE_UPTO:
            break
        if hi["aggregate_qps"] + 1e-9 < lo["aggregate_qps"]:
            errors.append(
                "sweep: aggregate_qps drops with replica count "
                f"({lo['replicas']}: {lo['aggregate_qps']} -> "
                f"{hi['replicas']}: {hi['aggregate_qps']})")
    return errors


HASH_TOP = {
    "schema": str,
    "benchmark": str,
    "vocab": numbers.Integral,
    "dim": numbers.Integral,
    "chunk_dim": numbers.Integral,
    "num_hashes": numbers.Integral,
    "train_steps": numbers.Integral,
    "table_lr": numbers.Real,
    "head_lr": numbers.Real,
    "requests": numbers.Integral,
    "serve_batch": numbers.Integral,
    "cache_rows": numbers.Integral,
    "retier_every": numbers.Integral,
    "drift": numbers.Real,
    "retier_async": bool,
    "bytes_fp32": numbers.Integral,
    "auc_fp32": numbers.Real,
    "sweep": list,
}

HASH_SWEEP = {
    "ratio_target": numbers.Real,
    "ratio_actual": numbers.Real,
    "pool_slots": numbers.Integral,
    "bytes": numbers.Integral,
    "bytes_combined": numbers.Integral,
    "auc": numbers.Real,
    "auc_gap": numbers.Real,
    "auc_combined": numbers.Real,
    "qps": numbers.Real,
    "steady_qps": numbers.Real,
    "p50_us": numbers.Real,
    "p99_us": numbers.Real,
    "lookups": numbers.Integral,
    "hits": numbers.Integral,
    "cache_hit_rate": numbers.Real,
    "retiers": numbers.Integral,
    **LATENCY_KEYS,
}

# a hashed sweep that never reaches this target ratio has not
# demonstrated the memory bound the backend exists for
HASH_MIN_TOP_RATIO = 100.0


def _validate_hash(rec: dict) -> list[str]:
    """``bench_hash/v1`` (benchmarks/hashed.py): pool-ratio sweep.
    The load-bearing invariants: pool bytes fall STRICTLY as the
    target ratio rises (the compression knob must actually compress),
    the int8-combined pool is smaller than the fp32 pool at every
    ratio, latency percentiles are ordered, and the sweep reaches at
    least ``HASH_MIN_TOP_RATIO`` x."""
    errors: list[str] = []
    _check_keys(rec, HASH_TOP, "top-level", errors)
    entries = _check_sweep(rec, HASH_SWEEP, errors)
    _check_latency(entries, errors)
    ratios = [e.get("ratio_target") for e in entries]
    if len(set(ratios)) != len(ratios):
        errors.append("sweep: duplicate ratio_target entries")
    ok = [e for e in entries
          if _is_num(e.get("ratio_target"))
          and isinstance(e.get("bytes"), numbers.Integral)]
    ok.sort(key=lambda e: e["ratio_target"])
    for lo, hi in zip(ok, ok[1:]):
        if hi["bytes"] >= lo["bytes"]:
            errors.append(
                "sweep: pool bytes must fall strictly as the target "
                f"ratio rises ({lo['ratio_target']:g}x: {lo['bytes']} "
                f"-> {hi['ratio_target']:g}x: {hi['bytes']})")
    if ok and ok[-1]["ratio_target"] < HASH_MIN_TOP_RATIO:
        errors.append(
            f"sweep: top ratio {ok[-1]['ratio_target']:g}x below the "
            f"required {HASH_MIN_TOP_RATIO:g}x")
    bf = rec.get("bytes_fp32")
    for i, e in enumerate(entries):
        b, bc = e.get("bytes"), e.get("bytes_combined")
        if isinstance(b, numbers.Integral) \
                and isinstance(bc, numbers.Integral) and bc >= b:
            errors.append(f"sweep[{i}]: int8-combined bytes {bc} not "
                          f"below fp32 pool bytes {b}")
        ra = e.get("ratio_actual")
        if isinstance(b, numbers.Integral) and b > 0 \
                and isinstance(bf, numbers.Integral) and _is_num(ra) \
                and abs(ra - bf / b) > 0.02 * max(ra, 1.0):
            errors.append(f"sweep[{i}]: ratio_actual {ra} "
                          f"inconsistent with byte counts "
                          f"({bf / b:.2f})")
        if _is_num(e.get("cache_hit_rate")) \
                and not 0.0 <= e["cache_hit_rate"] <= 1.0:
            errors.append(f"sweep[{i}]: cache_hit_rate out of [0, 1]")
    return errors


SCHEMAS = {
    "bench_qps/v1": _validate_qps,
    "bench_hier/v1": _validate_hier,
    "bench_pipeline/v1": _validate_pipeline,
    "bench_kernel/v1": _validate_kernel,
    "bench_fleet/v1": _validate_fleet,
    "bench_hash/v1": _validate_hash,
    "metrics_snapshot/v1": _validate_metrics,
}


def validate(rec: dict) -> list[str]:
    schema = rec.get("schema")
    fn = SCHEMAS.get(schema)
    if fn is None:
        return [f"top-level: schema is {schema!r}, expected one of "
                f"{sorted(SCHEMAS)}"]
    return fn(rec)


def _load_records(path: str) -> list[dict]:
    """One record per file, or one per line for ``.jsonl`` streams."""
    with open(path) as f:
        text = f.read()
    if path.endswith(".jsonl"):
        return [json.loads(line) for line in text.splitlines()
                if line.strip()]
    return [json.loads(text)]


def _committed_manifest() -> tuple[dict[str, tuple[str, str]], str]:
    """Load ``benchmarks.manifest.COMMITTED_BENCH`` by file path (and
    return the repo root), so the gate works regardless of
    PYTHONPATH/cwd."""
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_manifest", os.path.join(root, "benchmarks",
                                       "manifest.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return dict(mod.COMMITTED_BENCH), root


def main() -> int:
    args = sys.argv[1:]
    expected: dict[str, str] = {}
    if "--committed" in args:
        args.remove("--committed")
        manifest, root = _committed_manifest()
        import os
        committed = [os.path.join(root, name) for name in
                     sorted(manifest)]
        expected = {os.path.join(root, name): schema
                    for name, (schema, _) in manifest.items()}
        paths = args + committed
    else:
        paths = args or ["BENCH_qps.json"]
    failed = False
    for path in paths:
        try:
            recs = _load_records(path)
        except (OSError, json.JSONDecodeError) as e:
            print(f"{path}: unreadable: {e}")
            failed = True
            continue
        if not recs:
            print(f"{path}: no records")
            failed = True
            continue
        file_errors = 0
        for ln, rec in enumerate(recs, 1):
            where = f"{path}:{ln}" if len(recs) > 1 else path
            errors = validate(rec)
            want = expected.get(path)
            if want is not None and rec.get("schema") != want:
                errors.append(f"schema is {rec.get('schema')!r}, the "
                              f"committed manifest expects {want!r}")
            for err in errors:
                print(f"{where}: {err}")
            file_errors += len(errors)
        if file_errors:
            failed = True
        else:
            rec = recs[-1]
            sweep = rec.get("sweep")
            if isinstance(sweep, list):
                detail = f"{len(sweep)} sweep entries"
            elif len(recs) > 1:
                detail = f"{len(recs)} records"
            else:
                detail = "single record"
            print(f"{path}: valid {rec['schema']} ({detail})")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
