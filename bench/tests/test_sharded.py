"""A cell's chips reach the program: on four virtual CPU devices the
serve cell row-shards its store and its drawn table over a four-way
``"model"`` mesh, reads correct, and serves what it serves on one
device; a train cell refuses more than one chip.

The four devices exist only in a process that asks for them before
JAX starts, so the multi-device cases run in a child process."""

import json
import os
import subprocess
import sys
import time

import pytest

from bench.lib import registry

SERVE = [c["name"] for c in registry.benchmark()["workloads"]
         if registry.traffic(c["traffic"])["kind"] == "serve"]
TRAIN = [c["name"] for c in registry.benchmark()["workloads"]
         if registry.traffic(c["traffic"])["kind"] == "train"]

_PRELUDE = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, time
import jax
import numpy as np
from jax.sharding import Mesh
"""


def _child(code: str, *args) -> dict:
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(registry.ROOT / "src"), str(registry.ROOT)]))
    out = subprocess.run([sys.executable, "-c", _PRELUDE + code,
                          *map(str, args)],
                         cwd=registry.ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_SERVE_CODE = """
from bench import run
cell, seed = sys.argv[1], int(sys.argv[2])
seen = {}

def keep(n):
    def hook(fwd):
        def first(*args):
            out = fwd(*args)
            if n not in seen:
                leaves = jax.tree.leaves(args[0])
                seen[n] = {
                    "devices": [len(x.sharding.device_set) for x in leaves],
                    "specs": [str(getattr(args[0], f).sharding.spec)
                              for f in ("payload8", "payload16", "payload32")]
                    if n > 1 else [],
                    "logits": np.asarray(out[0])}
            return out
        return first
    return hook

res = {n: run.run_cell(cell, seed, 0.3, False, jax.devices()[:n],
                       t_start=time.perf_counter(), smoke=True,
                       hooks={"forward": keep(n)})
       for n in (4, 1)}
gap = float(np.max(np.abs(seen[4]["logits"].astype(np.float64)
                          - seen[1]["logits"])))
print(json.dumps({"res": res, "gap": gap,
                  "devices": seen[4]["devices"], "specs": seen[4]["specs"]}))
"""


@pytest.mark.parametrize("cell", SERVE)
def test_serve_cell_on_four_devices(cell):
    """The store's payloads sit row-sharded on the four devices, the
    run reads correct, and its first micro-batch's logits agree with
    the one-device run of the same seed within the cell's limit."""
    got = _child(_SERVE_CODE, cell, 2 ** 31 + 21)
    four = got["res"]["4"]
    assert four["device"]["count"] == 4
    assert set(got["devices"]) == {4}
    assert got["specs"] == ["PartitionSpec('model', None)"] * 3
    assert four["correct"] and four["failed"] == 0, four["checks"]
    assert got["res"]["1"]["correct"], got["res"]["1"]["checks"]
    limit = registry.workload(cell)["limits"]["logit_gap"]
    assert got["gap"] <= limit


_WEIGHTS_CODE = """
from bench.lib import registry, weights
from bench.lib.context import build_model
cfg = registry.config("dlrm-rm2")
model = build_model(cfg, cfg["smoke"])
shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
seed = 2 ** 40 + 17
one = weights.make(shapes, cfg["init"], seed)
four = weights.make(shapes, cfg["init"], seed, mesh)
table = cfg["program"]["table"]
out = {}
for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(one)[0],
                        jax.tree.leaves(four), strict=True):
    out[jax.tree_util.keystr(path)] = {
        "equal": a.dtype == b.dtype and a.shape == b.shape
        and np.asarray(a).tobytes() == np.asarray(b).tobytes(),
        "spec": str(b.sharding.spec),
        "replicated": b.sharding.is_fully_replicated,
        "shard_rows": sorted({s.data.shape[0] for s in b.addressable_shards}),
        "rows": b.shape[0] if b.ndim else 0,
        "devices": len(b.sharding.device_set)}
print(json.dumps({"table": f"['{table}']", "leaves": out}))
"""


def test_sharded_draw_equals_the_one_device_draw():
    """Under a four-way mesh every leaf is bit-identical to the draw on
    one device; the table comes out row-sharded, a quarter on each
    device, and every other leaf replicated."""
    got = _child(_WEIGHTS_CODE)
    leaves = got["leaves"]
    assert len(leaves) > 1
    for name, leaf in leaves.items():
        assert leaf["equal"], name
        assert leaf["devices"] == 4, name
        if name == got["table"]:
            assert leaf["spec"] == "PartitionSpec('model', None)"
            assert leaf["shard_rows"] == [leaf["rows"] // 4]
            assert leaf["rows"] % 4 == 0
        else:
            assert leaf["replicated"], name


@pytest.mark.parametrize("cell", TRAIN)
def test_train_cell_refuses_two_chips(cell):
    import jax

    from bench.lib import train_task
    from bench.lib.context import Ctx
    w = registry.workload(cell)
    ctx = Ctx(cell=w, cfg=registry.config(w["config"]),
              mix=registry.traffic(w["traffic"]), seed=1, seconds=0.3,
              trace=False, devices=jax.devices()[:1] * 2,
              t_start=time.perf_counter(), smoke=True)
    with pytest.raises(ValueError, match="one chip"):
        train_task.setup(ctx)
