"""The dlrm-rm2 smoke model served through the program's micro-batched
forward on the CPU, for tests that then re-trace that forward on the
path a TPU takes."""

import contextlib

import jax
import numpy as np

import repro.kernels
from repro.configs import dlrm_rm2
from repro.core.packed_store import PackedStore
from repro.launch.serve import build_serving_store
from repro.serve import OnlineConfig, OnlineServer, serve_forward


def smoke_forward(batch: int = 16):
    """(server, jitted forward, its last call's arguments)."""
    arch = dlrm_rm2.arch()
    model = arch.smoke_model
    params = model.init(jax.random.PRNGKey(0))
    store, cfg = build_serving_store(model.spec, params.pop("embed_table"))
    server = OnlineServer(store, cfg, OnlineConfig())
    res = serve_forward(server, model, model.spec, params,
                        serve_batch=batch, requests=batch,
                        num_dense=arch.smoke_num_dense)
    fwd, args = res.forward
    return server, fwd, args


def logical(packed: PackedStore) -> PackedStore:
    """The same store with (V, D) device payloads, as placed before
    stores were held lane-dense."""
    return PackedStore(*(jax.device_put(np.asarray(leaf))
                         for leaf in packed))


@contextlib.contextmanager
def kernel_path(monkeypatch):
    """Trace the kernels as a TPU backend would (``use_kernel`` true,
    ``interpret=False``), with every trace cache cleared on the way in
    and out: the body may trace and lower, never run."""
    jax.clear_caches()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    repro.kernels._default_interpret.cache_clear()
    try:
        yield
    finally:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
        repro.kernels._default_interpret.cache_clear()
        jax.clear_caches()
