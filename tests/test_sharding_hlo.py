"""Sharding rules, HLO analyzer, split-KV decode collective."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as sh
from repro.launch.hlo_analysis import analyze


def test_lm_param_rules():
    params = {
        "embed": jax.ShapeDtypeStruct((512, 64), jnp.float32),
        "layers": {"attn": {"wq": {"w": jax.ShapeDtypeStruct(
            (8, 64, 64), jnp.float32)}}},
        "final_norm": {"g": jax.ShapeDtypeStruct((64,), jnp.float32)},
    }
    specs = sh.param_specs(params, "lm")
    assert specs["embed"] == P("model", "data")
    # stacked layer param: leading L axis unsharded
    assert specs["layers"]["attn"]["wq"]["w"] == P(None, "data", "model")
    assert specs["final_norm"]["g"] == P()


def test_recsys_rules_row_shard_tables_only():
    params = {
        "embed_table": jax.ShapeDtypeStruct((1024, 16), jnp.float32),
        "wide_table": jax.ShapeDtypeStruct((1024, 1), jnp.float32),
        "net": {"deep": {"l0": {"w": jax.ShapeDtypeStruct(
            (128, 64), jnp.float32)}}},
    }
    specs = sh.param_specs(params, "recsys")
    assert specs["embed_table"] == P("model", None)
    assert specs["wide_table"] == P("model", None)
    assert specs["net"]["deep"]["l0"]["w"] == P()


def test_ep_rules_shard_experts():
    params = {"layers": {"moe": {
        "gate": jax.ShapeDtypeStruct((8, 64, 64, 32), jnp.float32)}}}
    specs = sh.param_specs(params, "lm_ep")
    assert specs["layers"]["moe"]["gate"] == P(None, "model", "data", None)


def test_zero1_specs_add_data_axis():
    pspec = {"w": P("model", None)}
    params = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}
    z = sh.zero1_specs(pspec, params, data_size=16)
    assert z["w"] == P("model", "data")


def test_validate_divisibility_flags_bad_dims():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = {"w": jax.ShapeDtypeStruct((7, 3), jnp.float32)}
    # trivial 1x1 mesh: everything divides
    assert sh.validate_divisibility(params, {"w": P("data", None)},
                                    mesh) == []


# ------------------------------------------------------------ HLO analyzer

def test_analyzer_counts_scan_trip_multipliers():
    n, L = 64, 7

    def f(x):
        def body(c, _):
            return c @ jnp.eye(n), None
        return jax.lax.scan(body, x, None, length=L)[0]

    hlo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32)).compile().as_text()
    stats = analyze(hlo)
    assert stats.flops == pytest.approx(L * 2 * n ** 3, rel=0.01)


def test_analyzer_nested_scans_multiply():
    n, L1, L2 = 32, 3, 5

    def f(x):
        def inner(c, _):
            return c @ jnp.eye(n), None

        def outer(c, _):
            c2, _ = jax.lax.scan(inner, c, None, length=L2)
            return c2, None
        return jax.lax.scan(outer, x, None, length=L1)[0]

    hlo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32)).compile().as_text()
    stats = analyze(hlo)
    assert stats.flops == pytest.approx(L1 * L2 * 2 * n ** 3, rel=0.01)


def test_analyzer_plain_dot():
    def f(a, b):
        return a @ b

    s = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    t = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    hlo = jax.jit(f).lower(s, t).compile().as_text()
    stats = analyze(hlo)
    assert stats.flops == pytest.approx(2 * 128 * 256 * 64, rel=0.01)
    assert stats.collective_total() == 0


# ----------------------------------------------- split-KV decode collective

def test_split_kv_decode_matches_full_softmax():
    """Run the shard_map split-KV decode on a 4-device host mesh in a
    subprocess (device count must be set before jax init)."""
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.dist.collectives import split_kv_decode_attention
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
b, s, h, d = 2, 32, 4, 16
q = jax.random.normal(jax.random.PRNGKey(0), (b, h, d))
k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
cache_len = jnp.asarray(19)
scale = d ** -0.5
out = split_kv_decode_attention(mesh, q, k, v, cache_len, scale)
# reference: full softmax over valid positions
sc = jnp.einsum("bhd,bkhd->bhk", q, k) * scale
mask = (jnp.arange(s) <= cache_len)[None, None, :]
sc = jnp.where(mask, sc, -1e30)
p = jax.nn.softmax(sc, axis=-1)
ref = jnp.einsum("bhk,bkhd->bhd", p, v)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("SPLIT_KV_OK")
"""
    env = dict(**__import__("os").environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=__import__("os").path.join(
                           __import__("os").path.dirname(__file__), ".."))
    assert "SPLIT_KV_OK" in r.stdout, r.stderr[-2000:]
