"""The control: the plain reference put in the program's place one
precision step below the one the config states (on a CPU: bfloat16
matmul inputs for float32), at the smoke sizes.  It has to fail one of
the cell's compared numbers, while the program passes the same limits.
On the chip the same readings were taken at each cell's own size
(PERF.md section 2)."""

import time

import pytest

from bench.lib import registry

CELLS = [c["name"] for c in registry.benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell):
    import jax

    from bench import run
    res = run.run_cell(cell, 2 ** 31 + 9, 0.3, False, jax.devices(),
                       t_start=time.perf_counter(), smoke=True,
                       control=True)
    limits = registry.workload(cell)["limits"]
    checks = res["checks"]
    assert res["correct"], checks
    failed = [k for k, lim in limits.items()
              if checks[f"control.{k}"]["value"] > lim]
    assert failed, checks
