"""Sharded serving QPS: the SHARK +30% QPS claim under distribution.

Runs ``repro.launch.serve --online --serve-batch ...`` over 1/2/4-way
row-sharded host meshes (each in its own subprocess — the XLA
host-device count must be fixed before jax initialises, and on a TPU
each child needs the chip, so this process stays off JAX) and emits one
stable-schema ``bench_qps/v1`` record per mesh size: the same contract
as ``benchmarks/qps.py --online --serve-batch`` (PR 3), so
``tools/check_bench_schema.py`` validates every record and future PRs
diff the sweeps.  On this CPU container the absolute numbers are a
proxy; the trajectory establishes that the row-sharded online path
works end-to-end at every mesh size and what the collective overhead
per request looks like.

    PYTHONPATH=src python -m benchmarks.qps_sharded \
        --emit-dir /tmp  # writes BENCH_qps_mesh{1,2,4}.json, validated
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

TOP_ECHO = ("requests", "cache_rows", "retier_every", "drift",
            "retier_async", "packed_fp32_ratio",
            "bytes_per_request_fp32", "bytes_per_request_packed")
SWEEP_KEYS = ("serve_batch", "qps", "steady_qps", "p50_us", "p95_us",
              "p99_us", "latency_p50", "latency_p95", "latency_p99",
              "p99_retier_attributed", "p99_while_retiering",
              "requests", "lookups", "hits", "cache_hit_rate",
              "retiers", "rows_moved", "swaps", "shadow_builds",
              "bytes_per_request_fp32", "bytes_per_request_packed")


def _parent_holds_device() -> bool:
    """True once this process has initialised an accelerator backend:
    it then owns the chip, and a child asking for it fails or hangs
    (CPU backends are per process and never conflict)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not xb.backends_are_initialized():
        return False
    import jax
    return jax.default_backend() != "cpu"


def serve_record(mesh: int, requests: int, serve_batch: int,
                 retier_every: int, arch: str = "dlrm-rm2",
                 retier_async: bool = False) -> dict:
    """One online micro-batched serve run in a subprocess -> its JSON
    record (the last stdout line).  The parent must stay off the
    device; this raises if it has initialised a JAX backend."""
    if _parent_holds_device():
        raise RuntimeError("qps_sharded: this process already holds a "
                           "JAX backend; run the sweep before any job "
                           "that touches a device")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"),
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", arch,
           "--requests", str(requests), "--mesh", str(mesh),
           "--online", "--serve-batch", str(serve_batch),
           "--retier-every", str(retier_every)]
    if retier_async:
        cmd.append("--retier-async")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=REPO)
    rec = None
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
    if rec is None:
        raise RuntimeError(
            f"serve --mesh {mesh} emitted no JSON record:\n"
            f"{r.stderr[-2000:]}")
    return rec


def mesh_bench(mesh: int, serve_batches=(1, 8), requests: int = 48,
               retier_every: int = 24,
               retier_async: bool = False) -> dict:
    """One validated ``bench_qps/v1`` record: serve_batch sweep at a
    fixed mesh size (the sweep axis must stay serve_batch — the schema
    pins bytes_per_request as sweep-invariant, which only holds when
    every entry serves the same stream against the same pack)."""
    recs = [serve_record(mesh, requests, sb, retier_every,
                         retier_async=retier_async)
            for sb in serve_batches]
    out = {"schema": "bench_qps/v1",
           "benchmark": "qps_online_microbatch_sharded",
           "mesh": mesh}
    out.update({k: recs[0][k] for k in TOP_ECHO})
    out["sweep"] = [{k: rec[k] for k in SWEEP_KEYS} for rec in recs]

    sys.path.insert(0, os.path.join(REPO, "tools"))
    from check_bench_schema import validate
    errors = validate(out)
    if errors:
        raise RuntimeError(
            f"mesh={mesh} record is not bench_qps/v1: {errors}")
    return out


def run(meshes=(1, 2, 4), requests=48, batch=None,
        serve_batches=(1, 8)) -> list[dict]:
    """benchmarks.run entry: one CSV row per (mesh, serve_batch) from
    the validated records.  ``batch`` is accepted for driver-signature
    compatibility and unused (the online path is micro-batched)."""
    del batch
    rows = []
    for n in meshes:
        rec = mesh_bench(n, serve_batches, requests=requests)
        for entry in rec["sweep"]:
            rows.append({
                "metric": f"qps_mesh{n}_sb{entry['serve_batch']}",
                "value": entry["steady_qps"],
                "p50_us": entry["p50_us"], "p99_us": entry["p99_us"],
                "cache_hit_rate": entry["cache_hit_rate"]})
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--meshes", default="1,2,4")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--serve-batches", default="1,8")
    ap.add_argument("--retier-async", action="store_true",
                    help="serve with the chunked shadow build + swap")
    ap.add_argument("--emit-dir", default=None, metavar="DIR",
                    help="write BENCH_qps_mesh<N>.json per mesh size "
                         "(validated bench_qps/v1)")
    args = ap.parse_args()
    meshes = [int(x) for x in args.meshes.split(",") if x.strip()]
    sbs = tuple(int(x) for x in args.serve_batches.split(",")
                if x.strip())
    for n in meshes:
        rec = mesh_bench(n, sbs, requests=args.requests,
                         retier_async=args.retier_async)
        if args.emit_dir:
            path = os.path.join(args.emit_dir, f"BENCH_qps_mesh{n}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1, sort_keys=True)
                f.write("\n")
            print(f"wrote {path}")
        else:
            print(json.dumps(rec))
