"""Shared recsys training-stage setup for the launch drivers.

``repro.launch.pipeline`` (stage 1) and ``repro.launch.train`` build
the identical training stack — synthetic click-log stream matched to
the arch's FieldSpec, the compressed train step, and the row-sharded
placement of every table-aligned state leaf under a mesh.  One builder
keeps the two drivers from drifting (the placement block in particular
must grow in lockstep with ``TrainState``).

Import only after any ``XLA_FLAGS`` device-count setup: this module
pulls in jax.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs  # noqa: F401  (compile listener before any compile)
from repro.core.qat_store import FQuantConfig
from repro.data.criteo import CriteoConfig, CriteoSynth
from repro.models import embedding as E
from repro.train.steps import TrainState, make_compressed_train_step


class RecsysTrainSetup(NamedTuple):
    model: object
    spec: object
    ds: CriteoSynth
    step: Callable          # (state, batch) -> (state, metrics)
    state: TrainState       # initial state, placed under the mesh
    batch_fn: Callable      # step index -> jnp batch dict
    indices_fn: Callable    # batch -> (B, F) global row ids
    num_dense: int          # dense features per example (0: none)
    lr: float               # the rate the step trains at


def place_train_state(state: TrainState, mesh,
                      axis: str = "model") -> TrainState:
    """Row-shard the table-aligned leaves per the recsys ruleset
    (table + rowwise-adagrad accumulator + priority + access EMA);
    everything else stays replicated."""
    if mesh is None:
        return state
    rows2 = NamedSharding(mesh, P(axis, None))
    rows1 = NamedSharding(mesh, P(axis))
    p = dict(state.params)
    p["embed_table"] = jax.device_put(p["embed_table"], rows2)
    opt = (state.opt[0], jax.device_put(state.opt[1], rows1))
    accum = state.accum
    if accum is not None:
        accum = accum._replace(
            access=jax.device_put(accum.access, rows1))
    priority = state.priority
    if priority is not None:
        priority = jax.device_put(priority, rows1)
    return state._replace(params=p, opt=opt, priority=priority,
                          accum=accum)


def build_recsys_training(arch, *, batch: int, lr: float | None = None,
                          mesh=None, axis: str = "model",
                          seed: int = 0,
                          fq_cfg: FQuantConfig | None = None,
                          use_pallas: bool | None = None
                          ) -> RecsysTrainSetup:
    """Dataset + compressed train step + placed initial state.

    ``arch`` must be a field-based recsys Arch (raises SystemExit
    otherwise, as the drivers' CLI contract).  The model is
    ``arch.driver_model()``: published widths with the chip's share of
    the vocabulary on a TPU, the smoke config elsewhere; ``lr=None``
    trains at that model's driver rate.  Under a mesh the axis size
    must divide the stacked table's rows.
    """
    if arch.family != "recsys" or arch.seq_model:
        raise SystemExit("compressed training supports field-based "
                         "recsys archs")
    model, num_dense, driver_lr = arch.driver_model()
    lr = driver_lr if lr is None else lr
    spec = model.spec
    if mesh is not None and spec.total_rows % mesh.shape[axis]:
        raise SystemExit(f"table rows {spec.total_rows} not divisible "
                         f"by mesh axis {axis}={mesh.shape[axis]}")
    ds = CriteoSynth(CriteoConfig(
        num_fields=spec.num_fields,
        cardinalities=tuple(int(c) for c in spec.cardinalities),
        num_dense=max(num_dense, 1),
        important_fields=max(1, spec.num_fields // 2),
        seed=seed))

    indices_fn = lambda b: E.globalize(b["indices"], spec)  # noqa: E731
    step = make_compressed_train_step(
        model.loss_from_emb, indices_fn, lambda b: b["labels"],
        "embed_table", lr, spec.num_fields,
        fq_cfg=fq_cfg if fq_cfg is not None else FQuantConfig(),
        mesh=mesh, axis=axis, use_pallas=use_pallas)
    state = place_train_state(
        step.init_state(model.init(jax.random.PRNGKey(seed))), mesh,
        axis)

    def batch_fn(s: int) -> dict:
        return {k: jnp.asarray(v) for k, v in ds.batch(batch, s).items()}

    return RecsysTrainSetup(model=model, spec=spec, ds=ds, step=step,
                            state=state, batch_fn=batch_fn,
                            indices_fn=indices_fn, num_dense=num_dense,
                            lr=lr)
