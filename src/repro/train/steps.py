"""Generic train-step factory with F-Quantization hooks.

The step a pod actually runs:

    grads  = grad(loss)(params, batch)           # remat per model config
    params = optimizer(params, grads)
    # F-Quantization write path (recsys / LM token tables):
    priority = Eq.7(priority, batch indices, labels)
    params[table] = snap(params[table], Eq.8(priority), rng)   # Eq.5-6

Everything is a pure function of (state, batch) -> (state, metrics), so
one jax.jit(..., in_shardings, out_shardings, donate_argnums=0) covers
single-pod and multi-pod meshes.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import qat_store
from repro.core.qat_store import FQuantConfig
from repro.optim.optimizers import Optimizer, apply_updates, global_norm

Array = jax.Array


class TrainState(NamedTuple):
    params: Any
    opt: Any
    step: Array
    priority: Any = None      # fquant row priorities (or None)
    rng: Array | None = None
    accum: Any = None         # train.accum.TaylorAccum (or None)


class FQuantHook(NamedTuple):
    """How F-Quantization attaches to a model's params."""
    cfg: FQuantConfig
    table_path: str                     # params key holding the table
    indices_fn: Callable[[dict], Array]  # batch -> flat/2D row indices
    labels_fn: Callable[[dict], Array]   # batch -> per-sample labels
    sparse_snap: bool = False           # touched-rows-only write path


def init_state(params: Any, optimizer: Optimizer,
               fquant: FQuantHook | None = None,
               seed: int = 0) -> TrainState:
    pri = None
    if fquant is not None:
        vocab = params[fquant.table_path].shape[0]
        pri = jnp.zeros((vocab,), jnp.float32)
    return TrainState(params=params, opt=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32), priority=pri,
                      rng=jax.random.PRNGKey(seed))


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    fquant: FQuantHook | None = None,
                    with_metrics: bool = True) -> Callable:
    """loss_fn(params, batch) -> scalar.  Returns step(state, batch)."""

    def step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, opt = optimizer.update(grads, state.opt, state.params)
        params = apply_updates(state.params, updates)

        priority = state.priority
        rng = state.rng
        if fquant is not None:
            rng, sub = jax.random.split(rng)
            store = qat_store.QATStore(table=params[fquant.table_path],
                                       priority=priority)
            if fquant.sparse_snap:
                store = qat_store.post_step_sparse(
                    store, fquant.indices_fn(batch),
                    fquant.labels_fn(batch), fquant.cfg,
                    seed=state.step.astype(jnp.uint32))
            else:
                store = qat_store.post_step(
                    store, fquant.indices_fn(batch),
                    fquant.labels_fn(batch), fquant.cfg, key=sub)
            params = dict(params)
            params[fquant.table_path] = store.table
            priority = store.priority

        metrics = {"loss": loss}
        if with_metrics:
            metrics["grad_norm"] = global_norm(grads)
        new_state = TrainState(params=params, opt=opt,
                               step=state.step + 1, priority=priority,
                               rng=rng)
        return new_state, metrics

    return step


def make_sparse_table_train_step(embed_fn: Callable, loss_from_emb: Callable,
                                 indices_fn: Callable, labels_fn: Callable,
                                 table_path: str, lr: float,
                                 fq_cfg: FQuantConfig | None = None,
                                 dense_optimizer: Optimizer | None = None,
                                 eps: float = 1e-10) -> Callable:
    """Recsys train step with a SPARSE embedding-table update path.

    The dense path (make_train_step + rowwise_adagrad) reads and writes
    the full (V, D) table every step even though a batch touches <=B*F
    rows; at dlrm-rm2 scale that is ~20 GB/device/step of pure overhead.
    This step differentiates w.r.t. the *gathered rows* instead:

        emb = take(table, idx)                      (B, F, D)
        d loss/d emb -> segment_sum over row ids    (touched rows only)
        adagrad accum/table updated via .at[rows]   (touched rows only)
        F-Quant priority decay (O(V) vector) + sparse snap

    Dense-side params use ``dense_optimizer`` (adam by default).
    State: TrainState with opt = (dense_opt_state, accum (V,)).
    """
    from repro.optim import optimizers as opt_lib
    dense_optimizer = dense_optimizer or opt_lib.adam(lr)

    def init_sparse_state(params) -> TrainState:
        dense = {k: v for k, v in params.items() if k != table_path}
        vocab = params[table_path].shape[0]
        opt = (dense_optimizer.init(dense),
               jnp.full((vocab,), 0.1, jnp.float32))
        pri = jnp.zeros((vocab,), jnp.float32) if fq_cfg else None
        return TrainState(params=params, opt=opt,
                          step=jnp.zeros((), jnp.int32), priority=pri,
                          rng=jax.random.PRNGKey(0))

    def step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        params = state.params
        table = params[table_path]
        dense = {k: v for k, v in params.items() if k != table_path}
        gidx = indices_fn(batch)
        flat = gidx.reshape(-1)
        rows = jnp.take(table, flat, axis=0
                        ).reshape(gidx.shape + (table.shape[1],))

        def loss_fn(dense_params, emb):
            p = dict(dense_params)
            p[table_path] = table      # heads must not touch the table
            return loss_from_emb(p, emb, batch).mean()

        loss, (g_dense, g_emb) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(dense, rows)

        # ---- sparse row-wise adagrad on the table -----------------------
        dense_opt_state, accum = state.opt
        g_rows = g_emb.reshape(-1, table.shape[1])
        # de-duplicate: sum gradients of repeated rows via segment_sum
        # onto the touched set (keep it simple: scatter-add onto V)
        g_sq = (g_rows ** 2).mean(axis=-1)
        accum = accum.at[flat].add(g_sq)
        denom = jnp.sqrt(jnp.take(accum, flat, axis=0)) + eps
        table = table.at[flat].add(-lr * g_rows / denom[:, None])

        # ---- dense params ------------------------------------------------
        upd, dense_opt_state = dense_optimizer.update(
            g_dense, dense_opt_state, dense)
        dense = apply_updates(dense, upd)

        # ---- F-Quant sparse write path ----------------------------------
        priority = state.priority
        if fq_cfg is not None:
            store = qat_store.QATStore(table=table, priority=priority)
            store = qat_store.post_step_sparse(
                store, gidx, labels_fn(batch), fq_cfg,
                seed=state.step.astype(jnp.uint32))
            table, priority = store.table, store.priority

        params = dict(dense)
        params[table_path] = table
        new_state = TrainState(params=params,
                               opt=(dense_opt_state, accum),
                               step=state.step + 1, priority=priority,
                               rng=state.rng)
        return new_state, {"loss": loss,
                           "grad_norm": global_norm(g_dense)}

    step.init_state = init_sparse_state
    return step


def make_compressed_train_step(loss_from_emb: Callable,
                               indices_fn: Callable, labels_fn: Callable,
                               table_path: str, lr: float,
                               num_fields: int,
                               fq_cfg: FQuantConfig | None = None,
                               dense_optimizer: Optimizer | None = None,
                               mesh=None, axis: str = "model",
                               use_pallas: bool | None = None,
                               with_accum: bool = True,
                               field_mask=None,
                               hashed_cfg=None,
                               eps: float = 1e-10) -> Callable:
    """The end-to-end compression train step: serving kernels + Eq. 5-8
    fold + in-training Taylor/access accumulation, in ONE backward.

        emb      = lookup_train(table, gidx)        fused gather kernel
        g_emb    = d loss / d emb                   head backward only
        g_table  = emb_vjp(g_emb)                   fused SCATTER kernel
                                                    (jax.custom_vjp)
        table    = rowwise_adagrad(table, g_table)  touched rows only
        priority = Eq. 7(priority, gidx, labels)    + Eq. 5-6 snap
        accum    = Taylor Eq. 4 fold + Eq. 7 access EMA

    ``field_mask`` (F,) zeroes pruned fields inside the loss (the
    F-Permutation masking contract of ``core.pruning``): their emb and
    therefore their table/Taylor gradients vanish, so post-prune
    finetuning reuses this same step with a mask.

    ``mesh`` switches the gather/scatter pair to the row-sharded form
    (``dist.packed.sharded_lookup_train``: per-shard kernels under
    shard_map, one (B*F, D) psum forward, replicated cotangent
    backward) so ``--mesh N`` training runs the same step.  The table
    must then be placed P(axis, None) and its row count divide the axis
    size (FieldSpec.total_rows is 512-padded for exactly this).

    State: ``TrainState`` with opt = (dense_opt_state, accum (V,)) and
    ``accum`` = ``train.accum.TaylorAccum`` — both checkpoint through
    ``CheckpointManager`` as ordinary state leaves.

    ``hashed_cfg`` (a ``store.hashed.HashedConfig``) switches the table
    to the ROBE-style compositional form: ``params[table_path]`` then
    holds the (S, Z) chunk POOL, the gather/scatter pair is the
    ``kernels.hashed_gather`` custom_vjp (rows materialize on the fly;
    the backward scatter-adds into the pool), and the Eq. 5-6 snap is
    skipped — pool slots are shared across rows, so there is no per-row
    payload to tier; Eq. 7 priority still folds per VIRTUAL row and
    drives the serving-side hot cache.  Row-wise adagrad runs per pool
    slot ((S,) accumulator).
    """
    from repro.kernels.dequant_bag.autodiff import lookup_train
    from repro.optim import optimizers as opt_lib
    from repro.train import accum as accum_lib
    from repro.core import priority as priority_lib
    dense_optimizer = dense_optimizer or opt_lib.adam(lr)
    pcfg = (fq_cfg.priority if fq_cfg is not None
            else qat_store.FQuantConfig().priority)

    if hashed_cfg is not None:
        if mesh is not None:
            from repro.dist.hashed import sharded_hashed_lookup_train

            def gather(tbl, gidx):
                return sharded_hashed_lookup_train(
                    tbl, gidx, num_chunks=hashed_cfg.num_chunks,
                    num_hashes=hashed_cfg.num_hashes,
                    num_slots=hashed_cfg.num_slots,
                    seed=hashed_cfg.seed, mesh=mesh, axis=axis,
                    use_pallas=use_pallas)
        else:
            from repro.kernels.hashed_gather.autodiff import \
                hashed_lookup_train

            def gather(tbl, gidx):
                return hashed_lookup_train(
                    tbl, gidx, num_chunks=hashed_cfg.num_chunks,
                    num_hashes=hashed_cfg.num_hashes,
                    seed=hashed_cfg.seed, use_pallas=use_pallas)
    elif mesh is not None:
        from repro.dist.packed import sharded_lookup_train

        def gather(tbl, gidx):
            return sharded_lookup_train(tbl, gidx, mesh=mesh, axis=axis,
                                        use_pallas=use_pallas)
    else:
        def gather(tbl, gidx):
            return lookup_train(tbl, gidx, use_pallas=use_pallas)

    def init_compressed_state(params) -> TrainState:
        dense = {k: v for k, v in params.items() if k != table_path}
        if hashed_cfg is not None:
            vocab, dim = hashed_cfg.vocab, hashed_cfg.dim
        else:
            vocab, dim = params[table_path].shape
        # adagrad accumulator: one cell per trained row (pool slots for
        # the hashed form, vocab rows otherwise)
        opt = (dense_optimizer.init(dense),
               jnp.full((params[table_path].shape[0],), 0.1,
                        jnp.float32))
        pri = (jnp.zeros((vocab,), jnp.float32)
               if (fq_cfg or hashed_cfg is not None) else None)
        acc = (accum_lib.init_accum(vocab, num_fields, dim)
               if with_accum else None)
        return TrainState(params=params, opt=opt,
                          step=jnp.zeros((), jnp.int32), priority=pri,
                          rng=jax.random.PRNGKey(0), accum=acc)

    def step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        params = state.params
        table = params[table_path]
        dense = {k: v for k, v in params.items() if k != table_path}
        gidx = indices_fn(batch)                       # (B, F) global

        # forward gather through the fused kernel; emb_vjp is the
        # registered custom_vjp -> the Pallas scatter-add backward
        emb, emb_vjp = jax.vjp(lambda t: gather(t, gidx), table)

        def head_loss(dense_params, e):
            if field_mask is not None:
                e = e * jnp.asarray(field_mask,
                                    jnp.float32)[None, :, None]
            p = dict(dense_params)
            p[table_path] = table       # heads must not touch the table
            return loss_from_emb(p, e, batch).mean()

        loss, (g_dense, g_emb) = jax.value_and_grad(
            head_loss, argnums=(0, 1))(dense, emb)
        with jax.named_scope("table_grad"):            # dense (V, D)
            (g_table,) = emb_vjp(g_emb)                # scatter kernel

        # ---- row-wise adagrad on the table (touched rows only: the
        # scatter emits exact zeros for untouched rows) ---------------
        dense_opt_state, accum_sq = state.opt
        with jax.named_scope("adagrad"):
            table, accum_sq = opt_lib.rowwise_adagrad_table_update(
                table, accum_sq, g_table, lr, step=state.step, eps=eps)

        # ---- dense params -------------------------------------------
        upd, dense_opt_state = dense_optimizer.update(
            g_dense, dense_opt_state, dense)
        dense = apply_updates(dense, upd)

        # ---- F-Quant fold: Eq. 7 priority + Eq. 5-6 sparse snap -----
        priority = state.priority
        if hashed_cfg is not None:
            # shared pool slots cannot snap per row; Eq. 7 still folds
            # per VIRTUAL row (serving cache + field-prune ranking)
            priority = priority_lib.priority_update_from_batch(
                priority, gidx, labels_fn(batch), pcfg)
        elif fq_cfg is not None:
            store = qat_store.QATStore(table=table, priority=priority)
            with jax.named_scope("snap"):
                store = qat_store.post_step_sparse(
                    store, gidx, labels_fn(batch), fq_cfg,
                    seed=state.step.astype(jnp.uint32))
            table, priority = store.table, store.priority

        # ---- in-training Taylor + access accumulation ---------------
        acc = state.accum
        if acc is not None:
            acc = accum_lib.update_accum(acc, gidx, emb, g_emb, pcfg)

        params = dict(dense)
        params[table_path] = table
        new_state = TrainState(params=params,
                               opt=(dense_opt_state, accum_sq),
                               step=state.step + 1, priority=priority,
                               rng=state.rng, accum=acc)
        return new_state, {"loss": loss,
                           "grad_norm": global_norm(g_dense)}

    step.init_state = init_compressed_state
    return step


def make_eval_step(loss_fn: Callable) -> Callable:
    def eval_step(params, batch):
        return loss_fn(params, batch)
    return eval_step
