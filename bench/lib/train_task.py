"""Train cells: the program's compressed train step, fed host to device
each step, checked against the plain reference over its first steps.

Set-up: ``train.setup.build_recsys_training`` gives the step
(``make_compressed_train_step``) and its learning rate; the harness
swaps in weights made from the seed (``weights``) through the step's
own ``init_state``, jits the step once, and drives that same object
through the first ``check_steps`` steps with the window's own feed and
call.  Their readings (each step's loss, the first gradient as the
optimizer holds it, the parameters' change) are taken there; the
window then continues from the state they left.

A train cell runs on one chip: set-up refuses more, since training
under a mesh is not what the harness measures.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import shark_ref, traffic, weights
from bench.lib.context import Ctx, global_ids

# a leaf counts when its reference gradient is at least this share of
# the median leaf's: smaller ones move by round-off alone
LEAF_FLOOR = 1e-3


class Trained:
    pass


def _lr(ctx: Ctx) -> float:
    t = ctx.cfg["training"]
    return t["smoke_lr"] if ctx.smoke else t["lr"]


def feed(batch: dict) -> dict:
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(
        jnp.ravel(x).astype(jnp.float32))) for p, x in flat}


def setup(ctx: Ctx):
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.train.setup import build_recsys_training

    if len(ctx.devices) != 1:
        raise ValueError(f"a train cell takes one chip, {ctx.cell['name']}"
                         f" was given {len(ctx.devices)}: the harness has"
                         " no sharded training")
    tkey = ctx.cfg["program"]["table"]
    arch = configs.get(ctx.cfg["program"]["arch"])
    prog = build_recsys_training(arch, batch=int(ctx.mix["batch"]),
                                 seed=ctx.seed)
    if not ctx.smoke:
        want = ctx.cfg["sizes"]
        got = (list(prog.spec.cardinalities), prog.spec.dim)
        if got != (want["cardinalities"], want["embed_dim"]):
            raise RuntimeError("the program's train config differs from "
                               f"{ctx.cfg['name']}: {got[1]}-dim, "
                               f"{sum(got[0])} rows")
    if abs(prog.lr - _lr(ctx)) > 1e-12:
        raise RuntimeError(f"the program trains at lr {prog.lr}, the "
                           f"config states {_lr(ctx)}")
    st = Trained()
    st.model, st.spec, st.lr = prog.model, prog.spec, prog.lr
    st.shapes = jax.eval_shape(prog.model.init, jax.random.PRNGKey(0))
    init_state = prog.step.init_state
    raw_step = prog.step
    del prog                        # the program's own weights go
    gc.collect()
    if "step" in ctx.hooks:         # tests: break the path
        raw_step = ctx.hooks["step"](raw_step)
    st.step = jax.jit(raw_step)
    params = weights.make(st.shapes, ctx.cfg["init"], ctx.seed)
    head0 = {k: v for k, v in params.items() if k != tkey}
    st.state = init_state(params)
    del params
    st.cards = list(st.spec.cardinalities)
    st.pool = traffic.train_pool(ctx.mix, st.cards,
                                 max(1, int(ctx.sizes.get("num_dense", 1))),
                                 ctx.seed)
    n = int(ctx.mix["check_steps"])
    losses = []
    for s in range(n):
        state, m = st.step(st.state, feed(st.pool[s]))
        st.state = state
        losses.append(m["loss"])
        if s == 0:
            b1 = ctx.cfg["training"]["adam"][0]
            st.grad1 = {k: v / (1.0 - b1) for k, v in
                        _norms(st.state.opt[0].mu).items()}
    st.losses = [float(x) for x in losses]
    change = _norms(jax.tree.map(lambda a, b: a - b,
                                 {k: v for k, v in st.state.params.items()
                                  if k != tkey}, head0))
    seed, init, shapes = ctx.seed, ctx.cfg["init"], st.shapes

    @jax.jit
    def table_change(t):
        t0 = weights.make(shapes, init, seed)[tkey]
        return jnp.linalg.norm((t - t0).ravel())

    change[f"['{tkey}']"] = float(table_change(st.state.params[tkey]))
    st.change = change
    st.next = n
    return st


def window(ctx: Ctx, st: Trained) -> None:
    sp = ctx.spans
    npool = len(st.pool)
    i, steps, prev, losses = st.next, 0, None, []
    t0 = time.perf_counter()
    end = t0 + ctx.window_seconds
    with sp("window"):
        while True:
            with sp("feed"):
                b = feed(st.pool[i % npool])
            with sp("step"):
                st.state, m = st.step(st.state, b)
            if prev is not None:
                with sp("sync"):
                    prev.block_until_ready()
            prev = m["loss"]
            losses.append(prev)
            i += 1
            steps += 1
            if time.perf_counter() >= end:
                break
        with sp("sync"):
            prev.block_until_ready()
    elapsed = time.perf_counter() - t0
    batch = int(ctx.mix["batch"])
    bad = int(np.sum(~np.isfinite(np.asarray([float(x) for x in losses]))))
    ctx.attempted = steps * batch
    ctx.failed = bad * batch
    ctx.counts.update(steps=steps, window_s=elapsed, batch=batch)
    ctx.e2e["train_examples_per_s"] = steps * batch / elapsed


def footprint(ctx: Ctx, st: Trained) -> None:
    pass


def check(ctx: Ctx, st: Trained) -> None:
    n = int(ctx.mix["check_steps"])
    batches = st.pool[:n]
    st.state = st.step = None
    gc.collect()
    prec = shark_ref.default_precision(ctx.platform)
    ref = reference_steps(ctx, st, batches, prec)
    ctx.readings.update(gaps({"losses": st.losses, "grad1": st.grad1,
                              "change": st.change}, ref))
    if ctx.control:
        for name, kw in (("control", {"precision": shark_ref.BELOW[prec]}),
                         ("fault_half", {"precision": prec,
                                         "half": True})):
            other = reference_steps(ctx, st, batches, **kw)
            for k, v in gaps(other, ref).items():
                ctx.readings[f"{name}.{k}"] = v


def gaps(got: dict, ref: dict) -> dict:
    """The compared numbers: the first step's loss, relative; the first
    gradient and the change after the checked steps, both by the worst
    counted leaf, as a share of that leaf's reference norm or the median
    leaf's, whichever is larger.  The later steps' losses are not
    compared: they start from parameters that differ by the first
    update's round-off, and swing from seed to seed about as far as the
    control's do; the change after them is compared instead."""
    loss = float(abs(got["losses"][0] - ref["losses"][0])
                 / abs(ref["losses"][0]))
    g_ref = ref["grad1"]
    med_g = float(np.median(list(g_ref.values())))
    counted = [k for k, v in g_ref.items() if v >= LEAF_FLOOR * med_g]

    def worst(a: dict, b: dict, keys) -> float:
        med = float(np.median([b[k] for k in b]))
        return max(abs(a.get(k, 0.0) - b[k]) / max(b[k], med)
                   for k in keys)

    head = [k for k in counted if k in got["grad1"]]
    return {"loss1_gap": loss,
            "grad_gap": worst(got["grad1"], g_ref, head),
            "change_gap": worst(got["change"], ref["change"], counted)}


def reference_steps(ctx: Ctx, st: Trained, batches, precision: str,
                    half: bool = False) -> dict:
    """The plain reference over the checked steps: same weights from the
    seed, same batches, touched rows only (untouched rows neither move
    nor snap)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.reference()
    t = ctx.cfg["training"]
    lr, eps = _lr(ctx), t["adagrad_eps"]
    b1, b2, adam_eps = t["adam"]
    tkey = ctx.cfg["program"]["table"]
    params = weights.make(st.shapes, ctx.cfg["init"], ctx.seed)
    table = params.pop(tkey)
    head0 = params
    if half:
        batches = [{k: v[:v.shape[0] // 2] for k, v in b.items()}
                   for b in batches]
    gids = [global_ids(st.cards, b["indices"]) for b in batches]
    uniq, inv = np.unique(np.concatenate([g.ravel() for g in gids]),
                          return_inverse=True)
    locs, o = [], 0
    for g in gids:
        locs.append(inv[o:o + g.size].reshape(g.shape).astype(np.int32))
        o += g.size
    rows0 = jnp.take(table, jnp.asarray(uniq, jnp.int32), axis=0)
    del table
    nrow, dim = rows0.shape
    dot = shark_ref.make_dot(precision)

    def loss_fn(head, emb, batch):
        x = ref.logits(head, emb, batch, dot)
        return jnp.mean(shark_ref.bce_with_logits(x, batch["labels"]))

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
    rows, head = rows0, head0
    acc = jnp.full((nrow,), t["adagrad_init"], jnp.float32)
    pri = jnp.zeros((nrow,), jnp.float32)
    m = jax.tree.map(jnp.zeros_like, head)
    v = jax.tree.map(jnp.zeros_like, head)
    losses, grad1 = [], None
    for s, (b, loc, g) in enumerate(zip(batches, locs, gids)):
        loc_d = jnp.asarray(loc)
        batch = {"dense": jnp.asarray(b["dense"]),
                 "labels": jnp.asarray(b["labels"]),
                 "gidx": jnp.asarray(g)}
        emb = jnp.take(rows, loc_d, axis=0)
        loss, (gh, ge) = grad_fn(head, emb, batch)
        g_rows = jax.ops.segment_sum(ge.reshape(-1, dim), loc_d.ravel(),
                                     num_segments=nrow)
        if s == 0:
            grad1 = _norms(gh)
            grad1[f"['{tkey}']"] = float(jnp.linalg.norm(g_rows.ravel()))
        acc = acc + jnp.mean(g_rows * g_rows, axis=-1)
        rows = rows - lr * g_rows / (jnp.sqrt(acc)[:, None] + eps)
        upd = [shark_ref.adam_step(p, gg, mm, vv, s + 1, lr, b1, b2,
                                   adam_eps)
               for p, gg, mm, vv in zip(*(jax.tree.leaves(x) for x in
                                          (head, gh, m, v)))]
        tree = jax.tree.structure(head)
        head = jax.tree.unflatten(tree, [u[0] for u in upd])
        m = jax.tree.unflatten(tree, [u[1] for u in upd])
        v = jax.tree.unflatten(tree, [u[2] for u in upd])
        lab = jnp.broadcast_to(batch["labels"][:, None], loc.shape).ravel()
        c_pos = jax.ops.segment_sum(lab, loc_d.ravel(), num_segments=nrow)
        c_neg = jax.ops.segment_sum(1.0 - lab, loc_d.ravel(),
                                    num_segments=nrow)
        pri = ((1.0 - t["priority_beta"]) * pri + t["priority_beta"]
               * (t["priority_alpha"] * c_pos + c_neg))
        touched = jnp.asarray(np.unique(loc))
        tiers = shark_ref.tiers_of(jnp.take(pri, touched), t["t8"],
                                   t["t16"])
        noise = shark_ref.hash_uniform(
            jnp.asarray(uniq.astype(np.uint32))[touched],
            jnp.uint32(s), dim)
        rows = rows.at[touched].set(shark_ref.snap_train(
            jnp.take(rows, touched, axis=0), tiers, noise))
        losses.append(float(loss))
    change = _norms(jax.tree.map(lambda a, b: a - b, head, head0))
    change[f"['{tkey}']"] = float(jnp.linalg.norm((rows - rows0).ravel()))
    return {"losses": losses, "grad1": grad1, "change": change}
