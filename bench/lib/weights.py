"""Weights from the seed, made by the benchmark in one jitted call on
the device, in the dtype the program serves them in.

The tree has the program's shapes and names (``jax.eval_shape`` of its
``init``), filled by the benchmark's own rule, so the reference can
rebuild the very same weights from the seed without taking anything
the program made:

  ``*_table``   normal * ``init.table_scale``
  ``w`` (2-D)   normal / sqrt(fan_in)
  anything else normal * ``init.bias_scale``

Each leaf draws from its own key, ``fold_in(key(seed), leaf number)``.
Under a mesh the tables come out row-sharded over its axis and every
other leaf replicated; with JAX's partitionable threefry (on by default
since JAX 0.5) each device draws only its own rows, and the values are
bit-identical to the draw on one device.  The program's tables have a
multiple of 512 rows (``FieldSpec.total_rows``), so 1, 2 or 4 devices
divide them evenly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps
    only the low 32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _fill(path, leaf, key, init):
    name = _name(path)
    x = jax.random.normal(key, leaf.shape, jnp.float32)
    if name.endswith("_table"):
        x = x * init["table_scale"]
    elif name == "w" and leaf.ndim == 2:
        x = x / jnp.sqrt(jnp.float32(leaf.shape[0]))
    else:
        x = x * init["bias_scale"]
    return x.astype(leaf.dtype)


def make(shapes, init: dict, seed: int, mesh=None):
    """``shapes``: a tree of ShapeDtypeStructs; returns device arrays,
    on ``mesh`` when one is given (tables row-sharded, the rest
    replicated)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _fill(path, leaf, jax.random.fold_in(key, i), init)
            for i, (path, leaf) in enumerate(flat)])

    if mesh is None:
        return jax.jit(build)(base_key(seed))
    from jax.sharding import NamedSharding, PartitionSpec as P
    rows = P(mesh.axis_names[0], None)
    out = jax.tree_util.tree_unflatten(treedef, [
        NamedSharding(mesh, rows if _name(path).endswith("_table") else P())
        for path, _ in flat])
    return jax.jit(build, out_shardings=out)(base_key(seed))
