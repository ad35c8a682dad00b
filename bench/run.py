"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Loads the cell (``bench/workloads/<cell>.json``), its config and its
traffic mix by name, sets up through the program's own entries, warms
every shape the window uses, measures for ``--seconds`` (``--trace 1``:
a traced window of at most ``TRACE_SECONDS``, reporting the cell's
per-layer metrics), checks what the window produced against the plain
reference, and prints one JSON line last on stdout.  The numbers
compared, each beside its limit, are the last lines on stderr and the
last key of the JSON line.

It runs only on TPU chips: with no TPU, fewer chips than the cell asks
for, or Pallas interpretation forced, it exits non-zero and prints no
result.  A cell's ``chips`` are the first that many devices; a serve
cell on more than one forms its ``"model"`` mesh from them and serves
from a store row-sharded over them, and a train cell takes one chip.
The per-chip metrics read the cell's devices alone.  JAX's compile
cache lives at ``<checkout>/.bench_cache/jax``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from bench.lib import chip, registry  # noqa: E402
from bench.lib.context import Ctx  # noqa: E402

TRACE_DIR = registry.ROOT / ".bench_cache" / "trace"


def task_of(mix: dict):
    """``bench/lib/<kind>_task.py`` for the mix's ``kind``."""
    import importlib
    return importlib.import_module(f"bench.lib.{mix['kind']}_task")


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             *, t_start: float = None, smoke: bool = False,
             control: bool = False, hooks: dict | None = None) -> dict:
    """One run of one cell; returns the result line as a dict."""
    import jax

    cell = registry.workload(name)
    ctx = Ctx(cell=cell, cfg=registry.config(cell["config"]),
              mix=registry.traffic(cell["traffic"]), seed=seed,
              seconds=seconds, trace=trace, devices=devices,
              t_start=T_START if t_start is None else t_start,
              smoke=smoke, control=control, hooks=hooks or {})
    task = task_of(ctx.mix)
    st = task.setup(ctx)
    ctx.e2e["setup_s"] = ctx.since_start()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    try:
        task.window(ctx, st)
    finally:
        if trace:
            jax.profiler.stop_trace()
    task.footprint(ctx, st)
    device = chip.device_record(devices)
    task.check(ctx, st)
    del st
    gc.collect()
    if trace:
        from bench.lib import trace as tr
        prof = tr.load(tr.find_xplane(str(TRACE_DIR)))
        ctx.trace_data = tr.reduce(prof, {n for n, _, _ in
                                          ctx.spans.records},
                                   [d.id for d in devices])
        del prof
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = ctx.trace_data.busy_s
        device["window_s"] = ctx.trace_data.window_s
    return result(ctx, device)


def result(ctx: Ctx, device: dict) -> dict:
    name = ctx.cell["name"]
    metrics = {}
    if ctx.trace:
        for m in registry.metrics_of(name, "per_layer"):
            v = registry.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in registry.metrics_of(name, "end_to_end"):
            metrics[m["name"]] = {"value": float(ctx.e2e[m["name"]]),
                                  "unit": m["unit"]}
    limits = ctx.cell.get("limits", {})
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in ctx.readings.items()}
    correct = bool(limits) and all(
        k in ctx.readings and math.isfinite(ctx.readings[k])
        and ctx.readings[k] <= lim for k, lim in limits.items())
    out = {"correct": correct, "attempted": int(ctx.attempted),
           "failed": int(ctx.failed), "metrics": metrics,
           "device": device}
    if ctx.trace:
        out["breakdown"] = ctx.trace_data.breakdown()
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = registry.workload(args.workload)
    try:
        chip.use_compile_cache()
        devices = chip.require_chips(int(cell["chips"]))
    except chip.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    res = run_cell(args.workload, args.seed, args.seconds,
                   bool(args.trace), devices)
    for k, c in res["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
