"""Every cell resolves by name and runs its set-up, window and check
at its config's smoke sizes on the CPU, through the harness's own
functions (the command itself refuses the CPU)."""

import json
import shutil
import subprocess
import sys
import time

import pytest

from bench.lib import registry

CELLS = sorted(p.stem for p in (registry.BENCH / "workloads").glob(
    "*.json"))


def test_benchmark_names_every_cell_file():
    bench = registry.benchmark()
    assert sorted(w["name"] for w in bench["workloads"]) == CELLS
    for w in bench["workloads"]:
        cell = registry.workload(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"],
                cell["why"]) == (w["config"], w["traffic"], w["chips"],
                                 w["why"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    w = registry.workload(cell)
    cfg = registry.config(w["config"])
    mix = registry.traffic(w["traffic"])
    assert registry.reference(cfg).logits
    assert mix["kind"] in ("serve", "train")
    e2e = registry.metrics_of(cell, "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    per_layer = registry.metrics_of(cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert callable(registry.metric_reader(m["name"]).read)
    assert w["limits"], "every cell compares at least one number"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_on_smoke_sizes(cell):
    import jax

    from bench import run
    res = run.run_cell(cell, 2 ** 31 + 3, 0.3, False, jax.devices(),
                       t_start=time.perf_counter(), smoke=True)
    names = {m["name"] for m in registry.metrics_of(cell, "end_to_end")}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 or k == "serve_hbm_gib"
               for k, v in res["metrics"].items())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def test_new_metric_found_by_name_alone(tmp_path):
    """A metric added as one file plus its BENCHMARK.json entry is
    found with no other edit."""
    root = tmp_path / "repo"
    shutil.copytree(registry.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = registry.benchmark()
    bench["per_layer"].append({
        "name": "dummy_ms.serve", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "device", "moves": "serve_qps"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "metrics" / "dummy_ms.serve.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    code = (
        "from bench.lib import registry\n"
        "ms = [m['name'] for m in registry.metrics_of("
        "'dlrm-rm2.serve-zipf.sat', 'per_layer')]\n"
        "assert 'dummy_ms.serve' in ms, ms\n"
        "print(registry.metric_reader('dummy_ms.serve').read(None))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "1.5"


def test_command_refuses_the_cpu():
    """No TPU: non-zero exit and no result line."""
    out = subprocess.run(
        [sys.executable, str(registry.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=registry.ROOT, capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_command_needs_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no
    program to run: non-zero exit, no result line."""
    shutil.copytree(registry.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_open_loop_arrivals_on_smoke_sizes():
    """The generator's Poisson arrivals: every request due in the window
    is served, latency runs from its due time, and the open-loop
    readers find their numbers."""
    import jax

    from bench.lib import serve_task
    from bench.lib.context import Ctx
    mix = dict(registry.traffic("serve-zipf.sat"), arrivals="poisson",
               rate_rps=2000.0, pool_requests=4096)
    ctx = Ctx(cell={"name": "open-loop", "limits": {}},
              cfg=registry.config("dlrm-rm2"), mix=mix, seed=11,
              seconds=0.5, trace=False, devices=jax.devices(),
              t_start=time.perf_counter(), smoke=True)
    st = serve_task.setup(ctx)
    serve_task.window(ctx, st)
    serve_task.check(ctx, st)
    n = ctx.counts["requests"]
    assert n == ctx.attempted > 0
    assert ctx.extra["lat_ms"].size == n
    assert (ctx.extra["lat_ms"] >= ctx.extra["queue_ms"]).all()
    assert ctx.e2e["serve_p99_ms"] > 0
    for name in ("queue_ms.lat", "batch_ms.lat"):
        assert registry.metric_reader(name).read(ctx) > 0
    assert ctx.readings["logit_gap"] < 1e-5


def test_wide_deep_config_serves_on_smoke_sizes():
    """The wide-deep config and its reference, kept for the cell a later
    change adds by a workload file alone (PERF.md section 7)."""
    import jax

    from bench.lib import serve_task
    from bench.lib.context import Ctx
    ctx = Ctx(cell={"name": "wide-deep", "limits": {}},
              cfg=registry.config("wide-deep"),
              mix=dict(registry.traffic("serve-zipf.sat"),
                       pool_requests=4096),
              seed=2 ** 31 + 7, seconds=0.3, trace=False,
              devices=jax.devices(), t_start=time.perf_counter(),
              smoke=True, control=True)
    st = serve_task.setup(ctx)
    serve_task.window(ctx, st)
    serve_task.check(ctx, st)
    assert ctx.e2e["serve_qps"] > 0
    assert ctx.readings["logit_gap"] < 1e-5
    assert ctx.readings["control.logit_gap"] > 1e-4
