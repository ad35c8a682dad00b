"""Smoke run of the SHARK main path on TPU, at dlrm-rm2's published widths.

    python chip_smoke.py [--seed N]     one chip: store, serve, train
    python chip_smoke.py --chips 4      four chips: row-sharded serve and
                                        train against one device

The model is dlrm-rm2's chip config (26 sparse fields, dim 64, 13 dense,
MLPs 512-256-64 and 512-512-256-1, each field's vocabulary capped at 2M
rows — ``configs.dlrm_rm2.CHIP_CFG``), with random weights from
``--seed``.  Phases, one line each:

  store   priority profile from the seed, Eq. 8 plan, snap, pack
          (``launch.serve.build_serving_store`` + ``OnlineServer``)
  serve   micro-batched 26-field requests through ``serve_forward``;
          the fused gather's rows must equal ``packed_store.unpack``
          exactly, and the served logits must match a float32 jnp
          reference (unpack, then ``model.head`` with float32
          matmuls) and, exactly, the same head at the served matmul
          precision
  train   steps of ``make_compressed_train_step`` through
          ``train.setup.build_recsys_training`` at the drivers' default
          learning rate; losses finite, falling

With ``--chips 4`` only the sharded phases run: the store row-sharded
over a 4-device "model" mesh, served and trained, each compared with the
same computation on one device in this process.

The jitted serve and train programs must contain the Pallas kernels
(``tpu_custom_call``).  Runs only on a TPU backend with interpretation
off; anything else, or any failed phase, exits non-zero without the
result line.  The last stdout line is the JSON result.  The compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.launch import use_compile_cache  # noqa: E402

SERVE_BATCH = 512          # requests per micro-batch
SERVE_BATCHES = 4
TRAIN_BATCH = 8192
TRAIN_STEPS = 20
SHARDED_TRAIN_STEPS = 4
GRAD_CHECK_ROWS = 1 << 20
# served logits vs the float32 (precision=highest) reference, absolute.
# On a v5e the served head (default matmul precision: every dot input
# rounded to bf16, f32 accumulation) reads 7.63e-4 at seed 0; the limit
# bounds that rounding.  A head run wholly in bfloat16 rounds the same
# dot inputs and reads about as much (PERF.md), so the path itself is
# pinned by the exact row check and the same-precision check below
LOGIT_TOL_F32 = 1e-3
# served logits vs the same head at the same precision on unpacked rows
LOGIT_TOL_SAME = 1e-6


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2 ** 30:.2f}"


def require_tpu():
    import jax

    from repro.kernels import should_interpret
    backend = jax.default_backend()
    if backend != "tpu":
        raise SmokeFailure(f"backend is {backend!r}, not 'tpu'")
    if should_interpret():
        raise SmokeFailure("Pallas interpretation is forced "
                           "(REPRO_PALLAS_INTERPRET); kernels would not "
                           "compile for the chip")
    return jax.devices()


def has_kernel(lowered, what: str) -> int:
    n = lowered.as_text().count("tpu_custom_call")
    check(n > 0, f"{what} program has no tpu_custom_call")
    return n


def as_bf16(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                        if x.dtype == jnp.float32 else x, tree)


def build_store(arch, seed: int):
    import jax

    from repro.launch.serve import build_serving_store
    model = arch.chip_model
    spec = model.spec
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed))
    store, cfg = build_serving_store(spec, params.pop("embed_table"),
                                     seed=seed)
    jax.block_until_ready(store.table)
    return model, params, store, cfg, time.perf_counter() - t0


def serve(model, params, store, cfg, seed: int, num_dense: int,
          mesh=None):
    """Serve the stream; returns (server, LoopResult, pack seconds)."""
    from repro.serve import OnlineConfig, OnlineServer, serve_forward
    t0 = time.perf_counter()
    server = OnlineServer(store, cfg, OnlineConfig(retier_every=0),
                          mesh=mesh)
    t_pack = time.perf_counter() - t0
    res = serve_forward(server, model, model.spec, params,
                        serve_batch=SERVE_BATCH,
                        requests=SERVE_BATCH * SERVE_BATCHES,
                        num_dense=num_dense, seed=seed)
    return server, res, t_pack


def grad_check(seed: int) -> float:
    """Max |bag_grad kernel - segment_sum oracle| for a train-shaped
    scatter (TRAIN_BATCH x 26 ids, dim 64) into GRAD_CHECK_ROWS rows."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.dequant_bag.kernel import bag_grad_pallas
    from repro.kernels.dequant_bag.ref import bag_grad_ref
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    idx = jax.random.randint(k1, (TRAIN_BATCH, 26), 0, GRAD_CHECK_ROWS)
    g = jax.random.normal(k2, (TRAIN_BATCH, 64), jnp.float32)
    got = jax.jit(lambda g, i: bag_grad_pallas(g, None, i, None,
                                               GRAD_CHECK_ROWS))(g, idx)
    want = jax.jit(lambda g, i: bag_grad_ref(g, None, i, None,
                                             GRAD_CHECK_ROWS))(g, idx)
    return float(jnp.max(jnp.abs(got - want)))


def phase_one_chip(arch, seed: int, dev) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import packed_store as ps
    from repro.models import embedding as E

    # -- store --------------------------------------------------------
    model, params, store, cfg, t_store = build_store(arch, seed)
    spec = model.spec
    server, res, t_pack = serve(model, params, store, cfg, seed,
                                arch.num_dense)
    counts = ps.live_counts(server.host_packed)
    say("store", rows=spec.total_rows, fields=spec.num_fields,
        dim=spec.dim, tiers_int8_half_fp32=",".join(map(str, counts)),
        packed_mib=f"{server.backend.nbytes() / 2 ** 20:.1f}",
        snap_s=f"{t_store:.1f}", pack_s=f"{t_pack:.1f}")
    check(int(counts.sum()) == spec.total_rows, "tier counts != rows")

    # -- serve --------------------------------------------------------
    # the program serve_forward jitted, and its last micro-batch
    fwd, (packed, cache, net, b, valid) = res.forward
    n_kernels = has_kernel(fwd.lower(packed, cache, net, b, valid),
                           "serve")
    gidx = E.globalize(b["indices"], spec)
    # the batch's rows of packed_store.unpack (unpack is this jnp
    # lookup over every row id)
    ref_emb = jax.jit(ps.lookup)(server.host_packed, gidx)
    emb = jax.jit(server.lookup_fn())(packed, gidx)
    emb_err = float(jnp.max(jnp.abs(emb - ref_emb)))
    head = jax.jit(model.head)
    ref_same = np.asarray(head(params, ref_emb, b))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(head(params, ref_emb, b))
    out_bf16 = np.asarray(head(as_bf16(params), as_bf16(ref_emb),
                               as_bf16(b)).astype(jnp.float32))
    del ref_emb, emb
    out = np.asarray(res.last_out)
    check(out.shape == (SERVE_BATCH,), f"logits shape {out.shape}")
    check(bool(np.isfinite(out).all()), "non-finite served logits")
    err = float(np.max(np.abs(out - ref)))
    err_bf16 = float(np.max(np.abs(out_bf16 - ref)))
    # same matmul precision: only the store + kernel path differs
    err_same = float(np.max(np.abs(out - ref_same)))
    say("serve", requests=SERVE_BATCH * SERVE_BATCHES,
        micro_batch=SERVE_BATCH, kernels_in_program=n_kernels,
        emb_max_abs_err=emb_err,
        logit_max_abs_err_vs_f32=err, tol_f32=LOGIT_TOL_F32,
        bf16_head_max_abs_err_vs_f32=err_bf16,
        logit_max_abs_err_vs_same_precision=err_same,
        tol_same=LOGIT_TOL_SAME,
        logit_range=f"{ref.min():.4f}..{ref.max():.4f}",
        peak_gib=peak_gib(dev))
    check(emb_err == 0.0, "fused gather rows differ from unpack")
    check(err <= LOGIT_TOL_F32,
          "served logits differ from the float32 reference")
    check(err_same <= LOGIT_TOL_SAME,
          "served logits differ from the same-precision reference")
    del server, store, params, res, packed, cache, net, b, valid
    gc.collect()

    # -- train --------------------------------------------------------
    from repro.train.setup import build_recsys_training
    # scatter kernel vs its oracle: rows hit twice in a batch sum in
    # another order, so fp32 rounding (|g| ~ 1, a few terms) is allowed
    grad_err = grad_check(seed)
    check(grad_err <= 1e-5, f"bag_grad differs from its oracle by "
                            f"{grad_err}")
    t0 = time.perf_counter()
    setup = build_recsys_training(arch, batch=TRAIN_BATCH, seed=seed)
    check(setup.spec.total_rows == spec.total_rows,
          "train setup did not take the chip config")
    step = jax.jit(setup.step)
    state = setup.state
    setup = setup._replace(state=None)   # the step's outputs replace it
    n_kernels = has_kernel(step.lower(state, setup.batch_fn(0)), "train")
    losses = []
    for s in range(TRAIN_STEPS):
        state, m = step(state, setup.batch_fn(s))
        losses.append(float(m["loss"]))
    t_train = time.perf_counter() - t0
    say("train", batch=TRAIN_BATCH, steps=TRAIN_STEPS, lr=setup.lr,
        bag_grad_max_abs_err=grad_err, kernels_in_program=n_kernels,
        losses=",".join(f"{x:.5f}" for x in losses),
        wall_s_incl_compile=f"{t_train:.1f}", peak_gib=peak_gib(dev))
    check(all(np.isfinite(losses)), "non-finite train loss")
    check(float(np.mean(losses[-5:])) < losses[0],
          "train loss did not fall")


def phase_four_chips(arch, seed: int, devices) -> None:
    import jax

    from repro.launch.mesh import make_model_mesh
    from repro.train.setup import build_recsys_training
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have "
                             f"{len(devices)}")
    mesh = make_model_mesh(4)

    # -- sharded serve vs one device ----------------------------------
    model, params, store, cfg, _ = build_store(arch, seed)
    sharded, res4, _ = serve(model, params, store, cfg, seed,
                             arch.num_dense, mesh=mesh)
    placed = sharded.packed.payload8.sharding.device_set
    check(len(placed) == 4, f"packed store on {len(placed)} devices")
    fwd4, args4 = res4.forward
    n4 = has_kernel(fwd4.lower(*args4), "sharded serve")
    out4 = np.asarray(res4.last_out)
    del sharded, res4, fwd4, args4
    gc.collect()
    single, res1, _ = serve(model, params, store, cfg, seed,
                            arch.num_dense)
    out1 = np.asarray(res1.last_out)
    del single, res1, store, params
    gc.collect()
    check(bool(np.isfinite(out4).all()), "non-finite sharded logits")
    err = float(np.max(np.abs(out4 - out1)))
    tol = 1e-3 * max(1.0, float(np.max(np.abs(out1))))
    say("serve4", devices=len(placed), kernels_in_program=n4,
        logit_max_abs_err_vs_1dev=err, tol=tol)
    check(err <= tol, "sharded logits differ from one device")

    # -- sharded train vs one device ----------------------------------
    def run(m):
        setup = build_recsys_training(arch, batch=TRAIN_BATCH, seed=seed,
                                      mesh=m)
        step = jax.jit(setup.step)
        state = setup.state
        setup = setup._replace(state=None)
        ndev = len(state.params["embed_table"].sharding.device_set)
        nk = has_kernel(step.lower(state, setup.batch_fn(0)), "train")
        losses = []
        for s in range(SHARDED_TRAIN_STEPS):
            state, met = step(state, setup.batch_fn(s))
            losses.append(float(met["loss"]))
        return losses, ndev, nk

    l4, ndev, nk = run(mesh)
    gc.collect()
    l1, _, _ = run(None)
    say("train4", devices=ndev, kernels_in_program=nk,
        losses_4dev=",".join(f"{x:.5f}" for x in l4),
        losses_1dev=",".join(f"{x:.5f}" for x in l1))
    check(ndev == 4, f"train table on {ndev} devices")
    check(all(np.isfinite(l4)), "non-finite sharded train loss")
    check(np.allclose(l4, l1, rtol=1e-3, atol=1e-4),
          "sharded train losses differ from one device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the row-sharded serve and train "
                         "path, against one device")
    args = ap.parse_args()

    cache = use_compile_cache()
    try:
        devices = require_tpu()
        dev = devices[0]
        say("device", platform=dev.platform, kind=repr(dev.device_kind),
            count=len(devices), compile_cache=cache)
        from repro import configs
        arch = configs.get("dlrm-rm2")
        say("config", arch=arch.name, reduced=json.dumps(arch.reduced))
        t0 = time.perf_counter()
        if args.chips == 4:
            phase_four_chips(arch, args.seed, devices)
        else:
            phase_one_chip(arch, args.seed, dev)
        say("done", wall_s=f"{time.perf_counter() - t0:.1f}")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
