"""Pallas TPU kernels for SHARK's compute hot spots.

  dequant_bag    fused gather + int8/bf16 dequant + embedding-bag reduce
                 (the serving path behind the paper's +30% QPS)
  rowwise_quant  fused per-row max-abs -> scale -> round -> int8 pack
                 (the training write path + gradient compression)
  cin            xDeepFM Compressed Interaction Network layer

Each kernel package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper + interpret/XLA fallback switch), ref.py (pure-jnp oracle).

Interpret mode is auto-detected per process: on TPU the real kernel
compiles, everywhere else (CPU containers, CI) the Pallas interpreter
runs the same program.  ``REPRO_PALLAS_INTERPRET=0|1`` force-overrides
the detection; per-call ``interpret=`` arguments override both.

Every TPU kernel reads its tables through the lane-dense row view of
``kernels.rows``.
"""

from __future__ import annotations

import functools
import os

import jax


@functools.cache
def _default_interpret() -> bool:
    forced = os.environ.get("REPRO_PALLAS_INTERPRET")
    if forced is not None:
        return forced.strip().lower() not in ("0", "false", "")
    return jax.default_backend() != "tpu"


def should_interpret(override: bool | None = None) -> bool:
    """Resolve the Pallas ``interpret=`` flag for this process.

    ``override`` wins when given; else ``REPRO_PALLAS_INTERPRET``; else
    interpret exactly when the default backend is not a TPU, so TPU runs
    compile the real kernel instead of silently interpreting.
    """
    if override is not None:
        return bool(override)
    return _default_interpret()


def use_kernel(use_pallas: bool | None = None,
               interpret: bool | None = None) -> bool:
    """Resolve a ``use_pallas=None`` op argument: kernel or jnp oracle.

    An explicit value wins.  On a TPU backend the answer is always the
    kernel — even with interpretation forced, the oracle never stands
    in for the device path there.  Elsewhere the oracle runs exactly
    when the kernel would be interpreted (the interpreter is far
    slower than the equivalent XLA program on a CPU).
    """
    if use_pallas is not None:
        return bool(use_pallas)
    if jax.default_backend() == "tpu":
        return True
    return not should_interpret(interpret)
