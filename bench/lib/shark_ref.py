"""Plain reference of the SHARK semantics the timed path serves and
trains, in straightforward ``jax.numpy``.  It imports nothing of the
program and takes nothing the program made: the table comes from the
seed (``weights``), the priority profile by the law and seed the
config states, and every tier, scale and rounding is worked out here.

Serving: Eq. 8 thresholds planned for the config's memory ratio, each
row of the served store snapped to its tier and packed (round to
nearest; int8 narrow row-wise scale, half tier scaled bfloat16, fp32
kept).  Training: row-wise adagrad on the touched rows, Adam on the
head, the Eq. 7 priority EMA and the Eq. 5-6 sparse snap with the
stochastic rounding of a hashed per-(row, step) uniform.

``dot`` gives every matmul of a reference head its precision: the
platform's default for float32 (bfloat16 inputs with float32
accumulation on a TPU, float32 on a CPU), or a control one step below.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

INT8, HALF, FP32 = 0, 1, 2
_EPS = 1e-12

# dot-input precision per name; "float32" runs at HIGHEST
PRECISIONS = ("float32", "bfloat16", "float8")
BELOW = {"float32": "bfloat16", "bfloat16": "float8"}


def default_precision(platform: str) -> str:
    """What a float32 matmul at JAX's default precision computes."""
    return "bfloat16" if platform == "tpu" else "float32"


def make_dot(precision: str):
    if precision not in PRECISIONS:
        raise ValueError(precision)

    def cast(x):
        if precision == "float8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
        if precision == "bfloat16":
            return x.astype(jnp.bfloat16)
        return x.astype(jnp.float32)

    def dot(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b),
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
    return dot


# ------------------------------------------------------------- tiers


def priority_profile(law: dict, vocab: int, seed: int) -> np.ndarray:
    if law["law"] != "pareto":
        raise ValueError(law)
    rng = np.random.default_rng(seed)
    return (rng.pareto(law["shape"], vocab) * law["scale"]).astype(
        np.float32)


def plan_thresholds(w, dim: int, ratio: float, half_share: float):
    """Eq. 8 cuts (t8, t16) so the store holds ``ratio`` of its fp32
    bytes with ``half_share`` of the quantized rows in the half tier:
    bytes per element p8 + 2 p16 + 4 p32 = 4 ratio."""
    del dim
    w = jnp.asarray(w)
    t = max(0.25, min(4.0, ratio * 4.0))
    q = float(np.clip((t - 4.0) / (half_share - 3.0), 0.0, 1.0))
    p8, p16 = (1.0 - half_share) * q, half_share * q
    eps = 1e-9 + 1e-6 * float(jnp.abs(w).max())
    t8 = (float(jnp.quantile(w, p8)) + eps if p8 > 0
          else float(jnp.min(w)) - 1.0)
    t16 = float(jnp.quantile(w, min(p8 + p16, 1.0))) + eps
    return t8, max(t16, t8)


def tiers_of(w, t8: float, t16: float):
    return jnp.where(w < t8, INT8, jnp.where(w < t16, HALF, FP32)
                     ).astype(jnp.int8)


def _int8_rtn(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), _EPS) \
        / 127.0
    q = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return q.astype(jnp.float32) * scale


def _half(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), _EPS)
    return (x / scale).astype(jnp.bfloat16).astype(jnp.float32) * scale


def served_rows(rows, tiers):
    """Rows as the serving store holds them: snapped to their tier,
    then packed (the same round trip again)."""
    t = tiers[..., None]
    out = rows
    for _ in range(2):
        out = jnp.where(t == INT8, _int8_rtn(out),
                        jnp.where(t == HALF, _half(out), out))
    return out


# ------------------------------------------------------------ training


def hash_uniform(idx, seed, dim: int):
    """Per-(row, step) uniforms in [0, 1) for stochastic rounding."""
    i = idx.astype(jnp.uint32)[:, None]
    j = jnp.arange(dim, dtype=jnp.uint32)[None, :]
    h = (i * jnp.uint32(2654435761) ^ (j * jnp.uint32(40503))
         ^ jnp.asarray(seed).astype(jnp.uint32))
    h = (h ^ (h >> 15)) * jnp.uint32(0x2C1B3C6D)
    h = (h ^ (h >> 12)) * jnp.uint32(0x297A2D39)
    h = h ^ (h >> 15)
    return h.astype(jnp.float32) / jnp.float32(2 ** 32)


def snap_train(rows, tiers, noise):
    """Eq. 5-6 write path: int8 rows by stochastic rounding, half rows
    scaled bfloat16, fp32 rows kept."""
    scale = jnp.maximum(jnp.max(jnp.abs(rows), -1, keepdims=True), _EPS) \
        / 127.0
    y = rows / scale
    lo = jnp.floor(y)
    q = jnp.clip(lo + (noise < (y - lo)), -128, 127).astype(jnp.int8)
    q8 = q.astype(jnp.float32) * scale
    t = tiers[:, None]
    return jnp.where(t == INT8, q8, jnp.where(t == HALF, _half(rows), rows))


def adam_step(p, g, m, v, step: int, lr: float, b1=0.9, b2=0.999,
              eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    bc1 = 1 - b1 ** np.float32(step)
    bc2 = 1 - b2 ** np.float32(step)
    return p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v


def bce_with_logits(x, y):
    return jnp.maximum(x, 0.0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))
