"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Shapes:

    single pod : (data=16, model=16)          = 256 chips (one v5e pod)
    multi-pod  : (pod=2, data=16, model=16)   = 512 chips

"pod" is the slow-interconnect (DCI) axis and is used as pure data
parallelism; "model" carries TP/EP and stays inside a pod's ICI.

``make_elastic_mesh`` derives the shape from whatever jax.device_count()
reports at launch — the elastic-restart path: after losing a pod you
relaunch and the same code builds the largest valid mesh.

Every mesh is built with ``AxisType.Auto`` axes: the sharding rules of
``repro.dist`` are written for compiler-propagated (not explicit)
shardings.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_model_mesh(n: int, axis: str = "model"):
    """1-D ``axis`` mesh over the first ``n`` devices — the drivers'
    ``--mesh N`` row-sharding mesh.

    Fails with a clear message when the backend has fewer than ``n``
    devices: on a TPU the device count is the hardware's (host-device
    faking via ``launch.force_host_device_count`` applies to the CPU
    backend only).
    """
    have = jax.device_count()
    if have < n:
        raise SystemExit(
            f"--mesh {n} needs {n} devices; backend "
            f"{jax.default_backend()!r} has {have}")
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:n])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_elastic_mesh(model_parallel: int = 16, pod_size: int = 256):
    """Largest (pod, data, model) mesh for the currently-alive devices."""
    n = jax.device_count()
    model = math.gcd(model_parallel, n)
    pods = max(1, n // pod_size)
    data = n // (pods * model)
    if pods > 1:
        return _mesh((pods, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def make_host_mesh(model: int = 1):
    """Debug mesh over local devices (smoke tests, examples)."""
    n = jax.device_count()
    return _mesh((n // model, model), ("data", "model"))
