"""Kernel microbench: measured tilings + the pipelining/fusion ladder.

Times the serving kernels at swept (B, K, D[, H]) shapes and emits ONE
stable-schema ``bench_kernel/v1`` JSON record (``--emit``, default
``BENCH_kernel.json``) with, per shape:

  * the analytic block-size pick and its time,
  * the measured-best tiling from the autotune sweep and its time —
    the sweep always includes the analytic pick as a candidate, so
    measured time <= analytic time *by construction* (the schema
    validator enforces it: a regression here means the sweep machinery
    broke, not that the analytic model won),
  * a bytes-touched model and the achieved bytes/s it implies —
    ``benchmarks/roofline.py`` turns these into achieved-vs-peak
    HBM-bandwidth fractions.

The kernel ladder makes the two optimisations this record tracks
directly comparable:

  dequant_bag_rowgrid   one row per grid step, no pipelining (baseline)
  dequant_bag           tiled + double-buffered row-DMA pipeline
  bag_grad              tiled scatter-add backward (pipelined RMW)
  unfused_bag_matmul    dequant_bag per field -> HBM -> XLA matmul
  bag_matmul            the fused kernel (no (B, F*D) round-trip)

``--seed-cache`` additionally persists each swept shape's measured-best
tiling into the autotune cache (``REPRO_AUTOTUNE_CACHE``, default
``results/autotune.json``) — the file ``resolve_block_b`` consults
at serve time.  CI seeds the cache on the interpret backend this way;
on a real TPU the same command measures compiled kernels.

Interpret-mode timings (this CPU container) are *relative* numbers —
the kernel interpreter is orders of magnitude off compiled TPU — but
the sweep ordering and cache plumbing are identical, which is what the
smoke validates.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from benchmarks.roofline import TARGET_KIND, chip_peaks


def hbm_peak() -> float:
    """HBM bytes/s the entries' ``peak_fraction`` divides by: the
    measured device's published peak (an unknown kind raises), or,
    under the Pallas interpreter, the target chip's — interpreter
    timings make that fraction a ratio between rungs, not a share of
    any device."""
    from repro.kernels import autotune
    kind = (TARGET_KIND if autotune.backend_name() == "interpret"
            else jax.devices()[0].device_kind)
    return chip_peaks(kind)["hbm_bw"]

# (b, k, d, h) swept by default: a serving-ish bag shape and a smaller
# awkward-D shape (exercises the 128-aligned edge-tile path)
DEFAULT_SHAPES = ((64, 8, 64, 32), (32, 4, 96, 16))
VOCAB = 512


def _case(b: int, k: int, d: int, h: int, seed: int = 0):
    kp, ks, ki, kw, k3 = jax.random.split(jax.random.PRNGKey(seed), 5)
    payload = jax.random.randint(kp, (VOCAB, d), -128, 127, jnp.int8)
    scales = jax.random.uniform(ks, (VOCAB,)) * 0.01
    idx = jax.random.randint(ki, (b, k), 0, VOCAB)
    weights = jax.random.uniform(kw, (b, k)) + 0.1
    w3 = jax.random.normal(k3, (k, d, h)) * 0.1
    g = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, d))
    return payload, scales, idx, weights, w3, g


def _bytes_dequant(b, k, d, itemsize):
    """HBM bytes one dequant-bag call touches: payload rows + gathered
    scale/weight/index words in, (B, D) fp32 out."""
    return b * k * (d * itemsize + 12) + b * d * 4


def _bytes_bag_grad(b, k, d):
    """Backward scatter: (B, D) fp32 grads + coeff/idx words in, one
    read-modify-write of every addressed table row."""
    return b * d * 4 + b * k * 8 + 2 * b * k * d * 4


def _bytes_bag_matmul(b, k, d, h, itemsize):
    """Fused kernel: payload rows + gathered words + the (K, D, H)
    weight block in, (B, H) fp32 out — no (B, K*D) intermediate."""
    return b * k * (d * itemsize + 12) + k * d * h * 4 + b * h * 4


def _bytes_unfused(b, k, d, h, itemsize):
    """The round-trip the fusion deletes: dequant writes (B, K, D) fp32
    to HBM, the matmul reads it back."""
    return (_bytes_dequant(b, k, d, itemsize) - b * d * 4
            + 2 * b * k * d * 4 + k * d * h * 4 + b * h * 4)


def bench_shape(b: int, k: int, d: int, h: int, *, iters: int,
                seed_cache: bool) -> list[dict]:
    from repro.kernels import autotune
    from repro.kernels.bag_matmul.kernel import bag_matmul_pallas
    from repro.kernels.bag_matmul.ops import _auto_block_h, _bm_auto_block_b
    from repro.kernels.dequant_bag.kernel import (
        bag_grad_pallas,
        dequant_bag_pallas,
        dequant_bag_pallas_rowgrid,
    )
    from repro.kernels.dequant_bag.ops import (
        _VMEM_SCRATCH_BUDGET,
        _auto_block_b,
    )

    payload, scales, idx, weights, w3, g = _case(b, k, d, h)
    itemsize = payload.dtype.itemsize
    rows: list[dict] = []

    def entry(kernel, dtype, blocks_a, us_a, blocks_m, us_m, nbytes,
              hh=0):
        us = min(us_a, us_m)
        rows.append({
            "kernel": kernel, "dtype": dtype, "b": b, "k": k, "d": d,
            "h": hh,
            "block_analytic": list(blocks_a), "analytic_us": us_a,
            "block_measured": list(blocks_m), "measured_us": us_m,
            "speedup": us_a / us_m if us_m > 0 else 1.0,
            "bytes_moved": int(nbytes),
            "achieved_gbs": nbytes / us * 1e6 / 1e9 if us > 0 else 0.0,
            "peak_fraction": (nbytes / (us * 1e-6)) / hbm_peak()
            if us > 0 else 0.0,
        })

    def tune(kernel, dtype, run, candidates, analytic, names, nbytes,
             hh=0, extra=""):
        """Time the analytic pick, sweep the candidates (analytic is
        always among them, so best <= analytic), optionally persist
        the winner under the block ``names``."""
        cands = [tuple(c) for c in candidates]
        if tuple(analytic) not in cands:
            cands.insert(0, tuple(analytic))
        res = autotune.sweep(run, cands, iters=iters)
        us_a = next(r["us"] for r in res["sweep"]
                    if tuple(r["blocks"]) == tuple(analytic))
        if us_a is None:  # analytic pick failed to launch: best wins
            us_a = res["best_us"]
        entry(kernel, dtype, analytic, us_a, res["best"],
              res["best_us"], nbytes, hh)
        if seed_cache:
            autotune.store(kernel, dtype, b, k, d,
                           dict(zip(names, res["best"])), res["best_us"],
                           extra=extra)
        return res

    # -- rowgrid baseline: no tiling, no pipeline ----------------------
    us = autotune.time_us(
        lambda: dequant_bag_pallas_rowgrid(payload, scales, idx,
                                           weights), iters=iters)
    entry("dequant_bag_rowgrid", "int8", [1], us, [1], us,
          _bytes_dequant(b, k, d, itemsize))

    # -- tiled + pipelined forward -------------------------------------
    # pure analytic picks (the private helpers), NOT resolve_block_b:
    # that would consult the very cache this bench may have just seeded
    analytic = (_auto_block_b(b, k, d, itemsize, _VMEM_SCRATCH_BUDGET),)
    tune("dequant_bag", "int8",
         lambda bb: lambda: dequant_bag_pallas(
             payload, scales, idx, weights, block_b=bb),
         [(bb,) for bb in autotune.candidate_block_b(b, k, d, itemsize)],
         analytic, ("block_b",), _bytes_dequant(b, k, d, itemsize))

    # -- pipelined backward scatter ------------------------------------
    analytic_g = (_auto_block_b(b, k, d, 4, _VMEM_SCRATCH_BUDGET),)
    tune("bag_grad", "float32",
         lambda bb: lambda: bag_grad_pallas(
             g, scales, idx, weights, VOCAB, block_b=bb),
         [(bb,) for bb in autotune.candidate_block_b(b, k, d, 4)],
         analytic_g, ("block_b",), _bytes_bag_grad(b, k, d))

    # -- fusion before/after -------------------------------------------
    w2 = w3.reshape(k * d, h)

    @jax.jit
    def unfused(payload, scales, idx, weights):
        # the serving path without bag_matmul: per-field K=1 bags
        # (B*K, D) through the dequant kernel, reshape, XLA matmul
        rows = dequant_bag_pallas(payload, scales,
                                  idx.reshape(b * k, 1),
                                  weights.reshape(b * k, 1))
        return rows.reshape(b, k * d) @ w2

    us_u = autotune.time_us(
        lambda: unfused(payload, scales, idx, weights), iters=iters)
    entry("unfused_bag_matmul", "int8", [1], us_u, [1], us_u,
          _bytes_unfused(b, k, d, h, itemsize), hh=h)

    ah = _auto_block_h(h)
    analytic_m = (_bm_auto_block_b(b, k, d, ah, itemsize), ah)
    cands_m = [(bb, bh)
               for bb in autotune.candidate_block_b(b, k, d, itemsize)
               for bh in sorted({ah, h})]
    tune("bag_matmul", "int8",
         lambda bb, bh: lambda: bag_matmul_pallas(
             payload, scales, idx, weights, w3, block_b=bb, block_h=bh),
         cands_m, analytic_m, ("block_b", "block_h"),
         _bytes_bag_matmul(b, k, d, h, itemsize), hh=h, extra=f"|h={h}")
    return rows


def run(shapes=DEFAULT_SHAPES, iters: int = 2,
        seed_cache: bool = False) -> dict:
    from repro.kernels import autotune

    sweep = []
    for b, k, d, h in shapes:
        sweep.extend(bench_shape(b, k, d, h, iters=iters,
                                 seed_cache=seed_cache))
    return {
        "schema": "bench_kernel/v1",
        "benchmark": "kernels",
        "backend": autotune.backend_name(),
        "interpret": autotune.backend_name() == "interpret",
        "cache_path": autotune.cache_path() if seed_cache else None,
        "hbm_peak_gbs": hbm_peak() / 1e9,
        "sweep": sweep,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=None,
                    help="comma-separated b:k:d:h quads, e.g. "
                         "64:8:64:32,32:4:96:16")
    ap.add_argument("--iters", type=int, default=2,
                    help="timing iterations per candidate (min taken)")
    ap.add_argument("--seed-cache", action="store_true",
                    help="persist each shape's measured-best tiling "
                         "into the autotune cache "
                         "(REPRO_AUTOTUNE_CACHE, default "
                         "results/autotune.json)")
    ap.add_argument("--emit", default=None, metavar="PATH",
                    help="write the bench_kernel/v1 record here "
                         "(default BENCH_kernel.json)")
    args = ap.parse_args()

    shapes = DEFAULT_SHAPES
    if args.shapes:
        shapes = tuple(tuple(int(x) for x in s.split(":"))
                       for s in args.shapes.split(","))
        if any(len(s) != 4 for s in shapes):
            ap.error("--shapes entries must be b:k:d:h")

    rec = run(shapes, iters=args.iters, seed_cache=args.seed_cache)
    for e in rec["sweep"]:
        print(f"{e['kernel']:>20} b={e['b']:<4} k={e['k']:<3} "
              f"d={e['d']:<4} h={e['h']:<4} "
              f"analytic {e['analytic_us']:9.1f}us "
              f"{tuple(e['block_analytic'])} -> measured "
              f"{e['measured_us']:9.1f}us {tuple(e['block_measured'])} "
              f"({e['speedup']:.2f}x)")
    if args.seed_cache:
        print(f"autotune cache seeded: {rec['cache_path']}")
    path = args.emit or "BENCH_kernel.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
