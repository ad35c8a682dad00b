"""Find every piece of the benchmark by its name.

    bench/configs/<config>.json       sizes, source, cuts, reference
    bench/configs/<reference>.py      plain reference beside the config
    bench/traffic/<mix>.json          parameters the generator reads
    bench/workloads/<cell>.json       config + mix + why + limits
    bench/metrics/<metric>.py         one per-layer metric reader
    BENCHMARK.json                    which metrics each cell reports

A later change adds a config, mix, cell or metric by adding files and
``BENCHMARK.json`` entries; nothing here needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    w = _json(BENCH / "workloads" / f"{name}.json")
    w.setdefault("name", name)
    return w


def config(name: str) -> dict:
    c = _json(BENCH / "configs" / f"{name}.json")
    c.setdefault("name", name)
    return c


def traffic(name: str) -> dict:
    t = _json(BENCH / "traffic" / f"{name}.json")
    t.setdefault("name", name)
    return t


def load_module(path: Path, name: str | None = None):
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: dict):
    """The plain reference module named by the config."""
    return load_module(BENCH / "configs" / cfg["reference"])


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


def metrics_of(cell: str, kind: str) -> list[dict]:
    """``kind`` 'end_to_end' or 'per_layer': the BENCHMARK.json metrics
    the cell reports (a metric without ``workloads`` is in every cell
    that reports the end-to-end metric it moves)."""
    bench = benchmark()
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
