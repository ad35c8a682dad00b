"""Training driver: ``python -m repro.launch.train --arch <id>``.

Seed-era plumbing fixed: ``--steps`` / ``--ckpt-dir`` now actually
drive the fault-tolerant loop (they used to be parsed and dropped, and
``--smoke`` was a no-op flag defaulting to True).  Two paths:

  * recsys field archs run the REAL training stack: the compressed
    train step (fused kernel gather/scatter backward, Eq. 5-8 fold,
    in-training Taylor/access accumulation) under ``train.loop.run``
    with atomic versioned checkpoints — rerun the same command after a
    kill and it resumes at the newest checkpoint.  ``--mesh N``
    row-shards the table (host devices on CPU containers).  This is
    the train stage of ``repro.launch.pipeline``, runnable standalone.
  * every other arch keeps its reduced-config family smoke
    (``arch.smoke()``) — the full configs only make sense on a pod.
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=None,
                    help="default: the arch's driver rate for the model "
                         "it runs (RecsysArch.driver_model)")
    ap.add_argument("--mesh", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--smoke", action="store_true",
                    help="force the reduced-config family smoke even "
                         "for recsys archs")
    args = ap.parse_args()

    from repro.launch import (force_host_device_count,
                              use_compile_cache)
    force_host_device_count(args.mesh)
    use_compile_cache()

    import jax

    from repro import configs
    from repro.launch.mesh import make_elastic_mesh

    arch = configs.get(args.arch)
    mesh = make_elastic_mesh(model_parallel=1)
    print(f"arch {arch.name} ({arch.family}); mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}; "
          f"devices {jax.device_count()}")

    if args.smoke or arch.family != "recsys" or arch.seq_model:
        metrics = arch.smoke()
        print("smoke-train metrics:", metrics)
        if not metrics.get("finite", False):
            raise SystemExit("non-finite smoke metrics")
        return

    from repro.train import loop as loop_lib
    from repro.train.setup import build_recsys_training

    model_mesh = None
    if args.mesh > 1:
        from repro.launch.mesh import make_model_mesh
        model_mesh = make_model_mesh(args.mesh)
    setup = build_recsys_training(arch, batch=args.batch, lr=args.lr,
                                  mesh=model_mesh)

    cfg = loop_lib.LoopConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, log_every=max(args.steps // 5, 1))
    result = loop_lib.run(
        setup.state, jax.jit(setup.step), setup.batch_fn, cfg,
        metrics_cb=lambda s, m: print(
            f"step {s}: loss {float(m['loss']):.4f}"))
    if not result.losses:
        print(f"nothing to do: checkpoint in {args.ckpt_dir} is "
              f"already at step {args.steps} "
              f"(resumed_from={result.resumed_from})")
        return
    print(f"trained {result.steps_run} steps at lr {setup.lr} "
          f"(resumed_from={result.resumed_from}): "
          f"loss {result.losses[0]:.4f} -> {result.losses[-1]:.4f}, "
          f"stragglers {result.stragglers}, nan_skips "
          f"{result.nan_skips}")
    # transient non-finite losses are the loop's business (it skips
    # them and aborts on repeats); the driver only fails if training
    # ENDED in a bad state
    import math
    if not math.isfinite(result.losses[-1]):
        raise SystemExit("training ended on a non-finite loss")


if __name__ == "__main__":
    main()
