"""Readings behind the limits of ``correct``: run one cell on several
seeds in one process, each with a short window, and print what the
program and the control read on each (the control being the plain
reference one precision step below the config's, put in the program's
place; for train cells also the reference with half of each batch
left out).  The benchmark's own runs never run the control.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \\
        --seconds 2

One JSON line per seed, then a summary line: per number, the largest
program reading (the lower end of its limit) and the smallest control
and fault readings (candidates for the upper end), and the largest
diagnostic reading.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from bench import run  # noqa: E402
from bench.lib import chip, registry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = registry.workload(args.workload)
    try:
        chip.use_compile_cache()
        devices = chip.require_chips(int(cell["chips"]))
    except chip.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           devices, t_start=time.perf_counter(),
                           control=True)
        row = {"seed": seed, "correct": res["correct"],
               "readings": {k: v["value"] for k, v in
                            res["checks"].items()},
               "metrics": {k: v["value"] for k, v in
                           res["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
    summary = {}
    for k in rows[0]["readings"]:
        vals = [r["readings"][k] for r in rows]
        upper = k.split(".")[0] in ("control", "fault_half")
        summary[k] = min(vals) if upper else max(vals)
    print(json.dumps({"summary": summary, "seeds": len(rows)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
