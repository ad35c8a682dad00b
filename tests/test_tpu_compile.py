"""The main-path Pallas kernels compile for a TPU v5e at dlrm-rm2 widths.

Each test lowers a kernel with ``interpret=False`` against a described
(not attached) v5e chip and compiles it with the TPU compiler, which
refuses what the interpreter accepts: lane-misaligned slices, packed
row offsets, SMEM overflow, block shapes off the 8 x 128 tile rules.
Nothing runs, so these say nothing about results or speed.  One test
compiles the whole micro-batched serve forward (smoke widths) to check
that a placed store reaches the kernels with no table relayout.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library, and
every test worker imports this file.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bag_matmul.kernel import bag_matmul_pallas
from repro.kernels.dequant_bag.kernel import (bag_grad_pallas,
                                              dequant_bag_pallas)
from repro.kernels.hashed_gather.kernel import hashed_gather_pallas
from repro.kernels.rowwise_quant.kernel import quantize_rowwise_pallas

V = 1 << 20          # table rows
D = 64               # dlrm-rm2 embedding dim
F = 26               # dlrm-rm2 sparse fields
SERVE_IDS = 4096 * F  # K = 1 serve gather: one id per slot


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16, jnp.float32],
                         ids=["int8", "bf16", "f32"])
@pytest.mark.parametrize("b,k", [(SERVE_IDS, 1), (4096, F)],
                         ids=["k1", "k26"])
def test_dequant_bag_compiles(one_chip, dtype, b, k):
    _compile(one_chip,
             lambda p, s, i, w: dequant_bag_pallas(p, s, i, w,
                                                   interpret=False),
             ((V, D), dtype), ((V,), jnp.float32), ((b, k), jnp.int32),
             ((b, k), jnp.float32))


@pytest.mark.parametrize("b,k", [(8192 * F, 1), (8192, F)],
                         ids=["k1", "k26"])
def test_bag_grad_compiles(one_chip, b, k):
    compiled = _compile(
        one_chip,
        lambda g, i, w: bag_grad_pallas(g, None, i, w, V, interpret=False),
        ((b, D), jnp.float32), ((b, k), jnp.int32), ((b, k), jnp.float32))
    # the dense (V, D) fp32 gradient is the output
    assert compiled.memory_analysis().output_size_in_bytes == V * D * 4


@pytest.mark.parametrize("scale_after", [False, True],
                         ids=["dequant", "scale_after"])
def test_bag_matmul_compiles(one_chip, scale_after):
    _compile(one_chip,
             lambda p, s, i, w, w3: bag_matmul_pallas(
                 p, s, i, w, w3, interpret=False, scale_after=scale_after),
             ((V, D), jnp.int8), ((V,), jnp.float32),
             ((4096, F), jnp.int32), ((4096, F), jnp.float32),
             ((F, D, 512), jnp.float32))


def test_hashed_gather_compiles(one_chip):
    z, chunks, t = 8, D // 8, F * 2        # 2 hashes per chunk
    _compile(one_chip,
             lambda p, s, sl, c: hashed_gather_pallas(
                 p, s, sl, c, num_chunks=chunks, interpret=False),
             ((100_000, z), jnp.float32), ((100_000,), jnp.float32),
             ((512, chunks * t), jnp.int32),
             ((512, chunks * t), jnp.float32))


def test_rowwise_quant_compiles(one_chip):
    _compile(one_chip,
             lambda x: quantize_rowwise_pallas(x, interpret=False),
             ((65536, D), jnp.float32))


# what moves an argument's bytes as they are into faster memory (XLA's
# prefetches), beside parameters and the kernels themselves
_NOT_RELAYOUT = ("parameter", "custom-call", "copy-start", "copy-done",
                 "slice-start", "slice-done")


def _table_ops(hlo: str, table_elements: int) -> list[str]:
    """Instructions of the compiled program, outside fusion bodies,
    whose output holds ``table_elements`` or more elements, other than
    ``_NOT_RELAYOUT``: ``relayout_ms.serve``'s rule, with "a table's
    worth" scaled to the tables compiled."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", " ".join(
        line for line in hlo.splitlines() if " fusion(" in line)))
    found, skip = [], False
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            skip = line.split(" ")[0].lstrip("%") in fused
            continue
        inst = line.strip().removeprefix("ROOT ")
        if skip or not inst.startswith("%") or " = " not in inst:
            continue
        rest = re.sub(r"\{[^}]*\}", "", inst.partition(" = ")[2])
        out = (rest[:rest.find(")") + 1] if rest.startswith("(")
               else rest.split(" ")[0])
        n = sum(math.prod(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"\[([\d,]*)\]", out))
        opcode = rest[len(out):].strip().partition("(")[0]
        if n >= table_elements and opcode not in _NOT_RELAYOUT:
            found.append(inst)
    return found


def test_placed_serve_forward_relays_no_table(one_chip, monkeypatch):
    """The micro-batched serve forward over a placed store, compiled
    for a v5e, holds no op as large as a tier table; over (V, D)
    payloads it lays out every tier on every call."""
    from _smoke_serve import kernel_path, logical, smoke_forward

    server, fwd, (packed, *rest) = smoke_forward()
    table = min(int(np.prod(p.shape)) for p in
                (packed.payload8, packed.payload16, packed.payload32))

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def table_ops(store):
        hlo = fwd.lower(shapes(store), *shapes(rest)).compile().as_text()
        assert "tpu_custom_call" in hlo
        return _table_ops(hlo, table)

    with kernel_path(monkeypatch):
        assert table_ops(packed) == []
        relaid = " ".join(table_ops(logical(packed)))
    for tier in ("gather_int8", "gather_half", "gather_fp32"):
        assert f"{tier}/jit(_tiled_call)/lane_dense" in relaid
