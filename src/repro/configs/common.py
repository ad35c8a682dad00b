"""Arch framework: per-family cell builders for smoke tests and dry-runs.

An Arch owns:
  * the exact model config from the assignment table,
  * ``cells()``: supported shape names (documented skips excluded),
  * ``lowerable(shape, mesh_axis_names)`` -> Cell(fn, args, in_specs):
    everything dryrun.py needs — args are ShapeDtypeStruct trees (no
    allocation), in_specs are PartitionSpec trees aligned with args,
  * ``smoke()``: a REDUCED config of the same family running one real
    train/forward step on CPU (used by per-arch smoke tests).

Training cells lower the full SHARK train step (grad + optimizer +
F-Quantization priority/snap where applicable); serving cells lower
prefill / decode / packed-store forward / retrieval scoring.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.qat_store import FQuantConfig
from repro.dist import sharding as sh
from repro.optim import optimizers as opt_lib
from repro.train import steps as steps_lib

Array = jax.Array


def sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


@dataclasses.dataclass
class Cell:
    fn: Callable
    args: tuple            # ShapeDtypeStruct pytrees
    in_specs: tuple        # PartitionSpec pytrees, aligned with args
    kind: str              # "train" | "prefill" | "decode" | "serve"
    donate: tuple = ()     # argnums to donate
    out_specs: Any = None  # PartitionSpec tree for outputs (None = auto)


TRAIN_METRIC_SPECS = {"loss": P(), "grad_norm": P()}


def data_axes_of(mesh_axis_names) -> Any:
    axes = tuple(a for a in ("pod", "data") if a in mesh_axis_names)
    return axes if len(axes) != 1 else axes[0]


def opt_state_specs(opt_abs, params_abs, pspecs):
    """Spec tree for optimizer state: moments shaped like params inherit
    the param spec; row-wise accumulators keep the row axis; scalars
    replicate."""

    def match(leaf, param, spec):
        if tuple(leaf.shape) == tuple(param.shape):
            return spec
        if tuple(leaf.shape) == tuple(param.shape[:1]):
            return P(spec[0]) if len(spec) else P()
        return P()

    fields = {}
    for f in opt_abs._fields:
        val = getattr(opt_abs, f)
        if f == "step":
            fields[f] = P()
        else:
            fields[f] = jax.tree_util.tree_map(match, val, params_abs,
                                               pspecs)
    return type(opt_abs)(**fields)


def train_state_specs(state_abs: steps_lib.TrainState, pspecs,
                      table_path: str | None = None):
    pri_spec = None
    if state_abs.priority is not None:
        row_axis = None
        if table_path is not None:
            tspec = pspecs[table_path]
            row_axis = tspec[0] if len(tspec) else None
        pri_spec = P(row_axis)
    return steps_lib.TrainState(
        params=pspecs,
        opt=opt_state_specs(state_abs.opt, state_abs.params, pspecs),
        step=P(), priority=pri_spec, rng=P())


class Arch:
    name: str = ""
    family: str = ""
    ruleset: str = ""

    def cells(self) -> list[str]:
        raise NotImplementedError

    def lowerable(self, shape: str,
                  mesh_axis_names=("data", "model"),
                  variant: str = "baseline") -> Cell:
        """variant: "baseline" = paper-faithful; "optimized" = §Perf
        beyond-paper levers (sparse snap, bf16 params, ...)."""
        raise NotImplementedError

    def smoke(self) -> dict:
        raise NotImplementedError


# ======================================================================
# LM family
# ======================================================================

LM_SHAPES = {
    "train_4k": dict(seq=4096, batch=256),
    "prefill_32k": dict(seq=32768, batch=32),
    "decode_32k": dict(seq=32768, batch=128),
    "long_500k": dict(seq=524288, batch=1),
}


@dataclasses.dataclass
class LMArch(Arch):
    lm_cfg: Any                      # transformer.LMConfig (full size)
    smoke_cfg: Any                   # reduced same-family config
    supports_long: bool = False     # sub-quadratic path exists
    rolling_window: int | None = None  # SWA serving cache (mixtral)
    lr: float = 3e-4
    fquant: bool = True             # SHARK F-Quant on the token table
    name: str = ""
    family: str = "lm"
    ruleset: str = "lm"

    def cells(self) -> list[str]:
        out = ["train_4k", "prefill_32k", "decode_32k"]
        if self.supports_long:
            out.append("long_500k")
        return out

    # -- shared builders ---------------------------------------------------

    def _params_abs(self, cfg):
        from repro.models import transformer as T
        return jax.eval_shape(lambda k: T.init_params(k, cfg),
                              jax.random.PRNGKey(0))

    def _fquant_hook(self, sparse: bool = False):
        if not self.fquant:
            return None
        return steps_lib.FQuantHook(
            cfg=FQuantConfig(),
            table_path="embed",
            indices_fn=lambda b: b["tokens"],
            labels_fn=lambda b: jnp.ones(b["tokens"].shape[0], jnp.float32),
            sparse_snap=sparse)

    def _train_cell(self, cfg, batch, seq, mesh_axis_names,
                    variant: str = "baseline") -> Cell:
        from repro.models import transformer as T
        d = data_axes_of(mesh_axis_names)
        optimizer = opt_lib.adam(self.lr)
        hook = self._fquant_hook(sparse=variant == "optimized")
        if variant == "optimized" and cfg.moe is not None:
            # block-local MoE dispatch: per-data-shard capacity buffers
            # eliminate the (E, C_global, D) dispatch all-reduces (4.5 TB
            # per device per step at the mixtral train_4k shape).
            # (Two REFUTED attempts recorded in EXPERIMENTS.md §Perf:
            # remat="dots" blew up activation all-gathers 78x; bf16
            # params shifted the partitioner to 1.5 TB of all-gathers.)
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, dispatch_blocks=32))

        def loss(p, b):
            return T.lm_loss(p, cfg, b["tokens"])

        step = steps_lib.make_train_step(loss, optimizer, hook)
        params_abs = self._params_abs(cfg)
        state_abs = jax.eval_shape(
            lambda p: steps_lib.init_state(p, optimizer, hook), params_abs)
        batch_abs = {"tokens": sds((batch, seq), jnp.int32)}
        pspecs = sh.param_specs(params_abs, self.ruleset, mesh_axis_names)
        sspecs = train_state_specs(state_abs, pspecs, "embed")
        bspecs = {"tokens": P(d, None)}
        return Cell(step, (state_abs, batch_abs), (sspecs, bspecs),
                    kind="train", donate=(0,),
                    out_specs=(sspecs, TRAIN_METRIC_SPECS))

    def _cache_specs(self, cache_abs, mesh_axis_names, shard_batch: bool,
                     model_size: int = 16, data_size: int = 16):
        d = data_axes_of(mesh_axis_names) if shard_batch else None

        def fits(dim: int) -> bool:
            return dim % model_size == 0

        def assign(path, leaf):
            key = jax.tree_util.keystr(path)
            if "pos" in key:
                return P()
            db = d if (d is not None
                       and leaf.shape[1] % data_size == 0) else None
            if leaf.ndim == 5:    # (L, B, S, Hkv, Dh)
                # kv heads rarely divide 16 (3/8); fall back to head_dim
                if fits(leaf.shape[3]):
                    return P(None, db, None, "model", None)
                if fits(leaf.shape[4]):
                    return P(None, db, None, None, "model")
                return P(None, db, None, None, None)
            if leaf.ndim == 4:    # (L, B, S, R) MLA latent
                if fits(leaf.shape[3]):
                    return P(None, db, None, "model")
                return P(None, db, None, None)
            return P()

        return jax.tree_util.tree_map_with_path(assign, cache_abs)

    def _serve_out_specs(self, fn, args, mesh_axis_names,
                         shard_batch: bool):
        """(logits, caches...) output specs: vocab-sharded logits, cache
        dims sharded like the input cache rules."""
        out_abs = jax.eval_shape(fn, *args)
        d = data_axes_of(mesh_axis_names) if shard_batch else None
        vocab_ok = self.lm_cfg.vocab % 16 == 0

        def assign(path, leaf):
            nonlocal_first = jax.tree_util.keystr(path).startswith("[0]")
            if nonlocal_first:   # logits (B, 1|T, V)
                return P(d, None, "model" if vocab_ok else None)
            if "pos" in jax.tree_util.keystr(path):
                return P()
            shp = leaf.shape
            if leaf.ndim == 5:
                if shp[3] % 16 == 0:
                    return P(None, d, None, "model", None)
                if shp[4] % 16 == 0:
                    return P(None, d, None, None, "model")
                return P(None, d, None, None, None)
            if leaf.ndim == 4:   # (L,B,T,R) stacked latent
                return P(None, d, None,
                         "model" if shp[3] % 16 == 0 else None)
            if leaf.ndim == 3:   # (B,T,R) unstacked (first-dense cache)
                return P(d, None, "model" if shp[2] % 16 == 0 else None)
            return P()

        return jax.tree_util.tree_map_with_path(assign, out_abs)

    def lowerable(self, shape: str,
                  mesh_axis_names=("data", "model"),
                  variant: str = "baseline") -> Cell:
        from repro.models import transformer as T
        cfg = self.lm_cfg
        d = data_axes_of(mesh_axis_names)
        info = LM_SHAPES[shape]
        pspecs_cfg = cfg

        if shape == "train_4k":
            return self._train_cell(cfg, info["batch"], info["seq"],
                                    mesh_axis_names, variant)

        params_abs = self._params_abs(pspecs_cfg)
        pspecs = sh.param_specs(params_abs, self.ruleset, mesh_axis_names)

        if shape == "prefill_32k":
            def fn(p, toks):
                return T.prefill(p, cfg, toks)
            toks = sds((info["batch"], info["seq"]), jnp.int32)
            outs = self._serve_out_specs(fn, (params_abs, toks),
                                         mesh_axis_names, True)
            return Cell(fn, (params_abs, toks), (pspecs, P(d, None)),
                        kind="prefill", out_specs=outs)

        # decode shapes
        batch = info["batch"]
        if shape == "long_500k" and self.rolling_window:
            cache_len_max = self.rolling_window
            rolling = True
        else:
            cache_len_max = info["seq"]
            rolling = False
        cache_abs = jax.eval_shape(
            lambda: T.init_cache(cfg, batch, cache_len_max, jnp.bfloat16,
                                 rolling=rolling))
        cspecs = self._cache_specs(cache_abs, mesh_axis_names,
                                   shard_batch=batch > 1)

        def fn(p, tok, cache, cache_len):
            return T.decode_step(p, cfg, tok, cache, cache_len)

        tok = sds((batch, 1), jnp.int32)
        tok_spec = P(d, None) if batch > 1 else P()
        args = (params_abs, tok, cache_abs, sds((), jnp.int32))
        outs = self._serve_out_specs(fn, args, mesh_axis_names, batch > 1)
        return Cell(fn, args, (pspecs, tok_spec, cspecs, P()),
                    kind="decode", donate=(2,), out_specs=outs)

    def smoke(self) -> dict:
        from repro.models import transformer as T
        cfg = self.smoke_cfg
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab)
        optimizer = opt_lib.adam(1e-3)
        hook = steps_lib.FQuantHook(
            cfg=FQuantConfig(),
            table_path="embed",
            indices_fn=lambda b: b["tokens"],
            labels_fn=lambda b: jnp.ones(b["tokens"].shape[0], jnp.float32)
        ) if self.fquant else None
        step = jax.jit(steps_lib.make_train_step(
            lambda p, b: T.lm_loss(p, cfg, b["tokens"]), optimizer, hook))
        state = steps_lib.init_state(params, optimizer, hook)
        l0 = None
        for i in range(3):
            state, m = step(state, {"tokens": toks})
            l0 = l0 if l0 is not None else float(m["loss"])
        # decode smoke
        cache = T.init_cache(cfg, 2, 32)
        logits, _ = jax.jit(
            lambda p, t, c, l: T.decode_step(p, cfg, t, c, l)
        )(state.params, toks[:, :1], cache, jnp.asarray(3))
        return {"loss_first": l0, "loss_last": float(m["loss"]),
                "decode_logits_shape": tuple(logits.shape),
                "finite": bool(jnp.isfinite(logits).all()
                               & jnp.isfinite(m["loss"]))}


# ======================================================================
# Recsys family
# ======================================================================

RECSYS_SHAPES = {
    "train_batch": dict(batch=65536),
    "serve_p99": dict(batch=512),
    "serve_bulk": dict(batch=262144),
    "retrieval_cand": dict(batch=1, n_candidates=1_000_000),
}

# steady-state tier fractions for abstract PackedStore shapes (zipf access
# under the paper's t8/t16 thresholds; exact numbers only set array sizes)
TIER_FRACTIONS = (0.70, 0.25, 0.05)


def packed_abs(total_rows: int, dim: int):
    from repro.core.packed_store import PackedStore
    v8 = (int(total_rows * TIER_FRACTIONS[0]) // 512) * 512
    v16 = (int(total_rows * TIER_FRACTIONS[1]) // 512) * 512
    v32 = total_rows - v8 - v16   # total_rows is 512-padded upstream
    return PackedStore(
        payload8=sds((v8, dim), jnp.int8), scale8=sds((v8,), jnp.float32),
        payload16=sds((v16, dim), jnp.bfloat16),
        scale16=sds((v16,), jnp.float32),
        payload32=sds((v32, dim), jnp.float32),
        indirect=sds((total_rows,), jnp.int32))


def packed_specs(rows_axis):
    from repro.core.packed_store import PackedStore
    return PackedStore(
        payload8=P(rows_axis, None), scale8=P(rows_axis),
        payload16=P(rows_axis, None), scale16=P(rows_axis),
        payload32=P(rows_axis, None), indirect=P(rows_axis))


class DriverModel(NamedTuple):
    model: Any
    num_dense: int          # dense features per example (0: none)
    lr: float               # the drivers' default training rate


@dataclasses.dataclass
class RecsysArch(Arch):
    model: Any                       # models.recsys.Model (full size)
    smoke_model: Any                 # reduced
    has_dense: bool = False          # DLRM dense features
    num_dense: int = 13
    smoke_num_dense: int = 5         # reduced config's dense width
    seq_model: bool = False          # BERT4Rec batch format
    seq_len: int = 200
    lr: float = 0.01
    chip_model: Any = None           # published widths, one chip's vocab
    reduced: dict = dataclasses.field(default_factory=dict)  # chip cuts
    # compressed-step learning rate (head Adam + table adagrad) the
    # drivers train each model at: the smoke config's tuned rate, and
    # one for the published widths, where Adam at 0.05 diverges
    smoke_lr: float = 0.05
    chip_lr: float = 1e-3
    name: str = ""
    family: str = "recsys"
    ruleset: str = "recsys"

    def driver_model(self, chip: bool = True) -> DriverModel:
        """(model, dense width, lr) the launch drivers run:
        ``chip_model`` on a TPU backend where the arch has one, the
        smoke model elsewhere (CPU tests and rehearsals) or with
        ``chip=False``."""
        if (chip and self.chip_model is not None
                and jax.default_backend() == "tpu"):
            return DriverModel(self.chip_model,
                               self.num_dense if self.has_dense else 0,
                               self.chip_lr)
        return DriverModel(self.smoke_model,
                           self.smoke_num_dense if self.has_dense else 0,
                           self.smoke_lr)

    def cells(self) -> list[str]:
        return list(RECSYS_SHAPES)

    # -- batch builders ------------------------------------------------

    def _batch_abs(self, batch: int):
        if self.seq_model:
            return {"inputs": sds((batch, self.seq_len), jnp.int32),
                    "targets": sds((batch, self.seq_len), jnp.int32),
                    "mask": sds((batch, self.seq_len), jnp.float32)}
        b = {"indices": sds((batch, self.model.spec.num_fields),
                            jnp.int32),
             "labels": sds((batch,), jnp.float32)}
        if self.has_dense:
            b["dense"] = sds((batch, self.num_dense), jnp.float32)
        return b

    def _batch_specs(self, batch_abs, mesh_axis_names):
        d = data_axes_of(mesh_axis_names)
        return jax.tree_util.tree_map(
            lambda leaf: P(d, *([None] * (leaf.ndim - 1))), batch_abs)

    def _loss_fn(self):
        model = self.model
        if self.seq_model:
            return lambda p, b: model.extras["seq_loss"](p, b)
        return lambda p, b: model.loss_from_emb(
            p, model.embed(p, b), b).mean()

    def _fquant_hook(self, model, sparse: bool = False):
        from repro.models import embedding as E
        if self.seq_model:
            return steps_lib.FQuantHook(
                cfg=FQuantConfig(), table_path="embed_table",
                indices_fn=lambda b: b["inputs"],
                labels_fn=lambda b: jnp.ones(b["inputs"].shape[0],
                                             jnp.float32),
                sparse_snap=sparse)
        spec = model.spec
        return steps_lib.FQuantHook(
            cfg=FQuantConfig(), table_path="embed_table",
            indices_fn=lambda b: E.globalize(b["indices"], spec),
            labels_fn=lambda b: b["labels"], sparse_snap=sparse)

    def lowerable(self, shape: str,
                  mesh_axis_names=("data", "model"),
                  variant: str = "baseline") -> Cell:
        from repro.core.packed_store import lookup as packed_lookup
        from repro.core.packed_store import unpack as packed_unpack
        from repro.models import embedding as E
        model = self.model
        d = data_axes_of(mesh_axis_names)
        info = RECSYS_SHAPES[shape]
        params_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs = sh.param_specs(params_abs, self.ruleset, mesh_axis_names)

        if shape == "train_batch":
            batch_abs = self._batch_abs(info["batch"])
            bspecs = self._batch_specs(batch_abs, mesh_axis_names)
            if variant == "optimized" and not self.seq_model:
                # sparse-table path: grads w.r.t. gathered rows only;
                # adagrad accum + table writes touch <=B*F rows, not V
                hook = self._fquant_hook(model, sparse=True)
                step = steps_lib.make_sparse_table_train_step(
                    model.embed, model.loss_from_emb,
                    hook.indices_fn, hook.labels_fn,
                    "embed_table", self.lr, fq_cfg=hook.cfg)
                state_abs = jax.eval_shape(step.init_state, params_abs)
                table_spec = pspecs["embed_table"]
                row_axis = table_spec[0] if len(table_spec) else None
                opt_specs = (opt_state_specs(
                    state_abs.opt[0],
                    {k: v for k, v in params_abs.items()
                     if k != "embed_table"},
                    {k: v for k, v in pspecs.items()
                     if k != "embed_table"}), P(row_axis))
                sspecs = steps_lib.TrainState(
                    params=pspecs, opt=opt_specs, step=P(),
                    priority=P(row_axis), rng=P())
                return Cell(step, (state_abs, batch_abs),
                            (sspecs, bspecs), kind="train", donate=(0,),
                            out_specs=(sspecs, TRAIN_METRIC_SPECS))
            optimizer = opt_lib.rowwise_adagrad(self.lr)
            hook = self._fquant_hook(model,
                                     sparse=variant == "optimized")
            step = steps_lib.make_train_step(self._loss_fn(), optimizer,
                                             hook)
            state_abs = jax.eval_shape(
                lambda p: steps_lib.init_state(p, optimizer, hook),
                params_abs)
            sspecs = train_state_specs(state_abs, pspecs, "embed_table")
            return Cell(step, (state_abs, batch_abs), (sspecs, bspecs),
                        kind="train", donate=(0,),
                        out_specs=(sspecs, TRAIN_METRIC_SPECS))

        if shape in ("serve_p99", "serve_bulk"):
            spec = model.spec
            packed = packed_abs(spec.total_rows, spec.dim)
            pk_specs = packed_specs("model")
            batch_abs = self._batch_abs(info["batch"])
            bspecs = self._batch_specs(batch_abs, mesh_axis_names)
            # dense-side params only (embedding served from PackedStore)
            net_abs = {k: v for k, v in params_abs.items()
                       if k != "embed_table"}
            net_specs = {k: v for k, v in pspecs.items()
                         if k != "embed_table"}

            if self.seq_model:
                def fn(net, packed, batch):
                    # small vocab: dequantize the table once per batch
                    table = packed_unpack(packed)
                    p = dict(net)
                    p["embed_table"] = table
                    return model.forward(p, batch)
            else:
                def fn(net, packed, batch):
                    gidx = E.globalize(batch["indices"], spec)
                    emb = packed_lookup(packed, gidx)     # (B, F, D) fp32
                    p = dict(net)
                    p["embed_table"] = packed.payload32   # unused by head
                    return model.head(p, emb.astype(jnp.float32), batch)

            d = data_axes_of(mesh_axis_names)
            return Cell(fn, (net_abs, packed, batch_abs),
                        (net_specs, pk_specs, bspecs), kind="serve",
                        out_specs=P(d))

        if shape == "retrieval_cand":
            n = info["n_candidates"]
            dim = model.spec.dim
            cand_axes = tuple(a for a in ("pod", "model")
                              if a in mesh_axis_names)
            batch_abs = self._batch_abs(info["batch"])

            def fn(params, cand_payload, cand_scales, batch):
                if self.seq_model:
                    user = model.extras["encode"](
                        params, batch["inputs"])[:, -1]   # (1, D)
                else:
                    emb = model.embed(params, batch)
                    user = emb.mean(axis=1)               # (1, D)
                scores = jnp.einsum(
                    "nd,bd->bn", cand_payload.astype(jnp.float32),
                    user.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
                scores = scores * cand_scales[None, :]
                vals, idx = jax.lax.top_k(scores, 100)
                return vals, idx

            return Cell(
                fn,
                (params_abs, sds((n, dim), jnp.int8),
                 sds((n,), jnp.float32), batch_abs),
                (pspecs, P(cand_axes, None), P(cand_axes),
                 jax.tree_util.tree_map(lambda _: P(), batch_abs)),
                kind="serve", out_specs=(P(), P()))

        raise KeyError(shape)

    def smoke(self) -> dict:
        from repro.core import FQuantConfig as FQ
        from repro.core import pack
        from repro.core.qat_store import QATStore
        model = self.smoke_model
        params = model.init(jax.random.PRNGKey(0))
        batch = self._smoke_batch(model)
        loss_fn = (model.extras["seq_loss"] if self.seq_model else
                   lambda p, b: model.loss_from_emb(
                       p, model.embed(p, b), b).mean())
        optimizer = opt_lib.rowwise_adagrad(0.05)
        hook = self._fquant_hook(model)
        step = jax.jit(steps_lib.make_train_step(loss_fn, optimizer, hook))
        state = steps_lib.init_state(params, optimizer, hook)
        losses = []
        for i in range(3):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        # serve smoke through the packed store
        store = QATStore(table=state.params["embed_table"],
                         priority=state.priority)
        packed = pack(store, FQ())
        from repro.core.packed_store import unpack
        table = unpack(packed)
        p2 = dict(state.params)
        p2["embed_table"] = table
        out = model.forward(p2, batch)
        return {"loss_first": losses[0], "loss_last": losses[-1],
                "serve_shape": tuple(out.shape),
                "finite": bool(jnp.isfinite(out).all())}

    def _smoke_batch(self, model):
        if self.seq_model:
            t = model.spec.cardinalities[1]   # position table = seq_len
            rng = jax.random.PRNGKey(7)
            items = model.spec.cardinalities[0]
            return {"inputs": jax.random.randint(rng, (4, t), 0, items),
                    "targets": jax.random.randint(rng, (4, t), 0,
                                                  items - 2),
                    "mask": jnp.ones((4, t), jnp.float32)}
        f = model.spec.num_fields
        rng = jax.random.PRNGKey(7)
        idx = jax.random.randint(rng, (8, f), 0,
                                 min(model.spec.cardinalities))
        b = {"indices": idx,
             "labels": jnp.asarray([0., 1., 0., 1., 1., 0., 0., 1.])}
        if self.has_dense:
            b["dense"] = jax.random.normal(rng, (8, self.smoke_num_dense))
        return b


# ======================================================================
# GNN family (PNA)
# ======================================================================

GNN_SHAPES = {
    "full_graph_sm": dict(n_nodes=2708, n_edges=10556, d_feat=1433),
    "minibatch_lg": dict(n_nodes=232965, n_edges=114615892,
                         batch_nodes=1024, fanout=(15, 10), d_feat=602),
    "ogb_products": dict(n_nodes=2449029, n_edges=61859140, d_feat=100),
    "molecule": dict(n_nodes=30, n_edges=64, batch=128, d_feat=16),
}


@dataclasses.dataclass
class GNNArch(Arch):
    d_hidden: int = 75
    n_layers: int = 4
    lr: float = 0.01
    name: str = "pna"
    family: str = "gnn"
    ruleset: str = "gnn"

    def cells(self) -> list[str]:
        return list(GNN_SHAPES)

    def _cfg(self, shape: str):
        from repro.models.gnn import PNAConfig
        info = GNN_SHAPES[shape]
        if shape == "minibatch_lg":
            vocab = -(-info["n_nodes"] // 512) * 512   # mesh-divisible
            return PNAConfig(d_in=info["d_feat"], d_hidden=self.d_hidden,
                             n_layers=self.n_layers, node_vocab=vocab)
        if shape == "molecule":
            return PNAConfig(d_in=info["d_feat"], d_hidden=self.d_hidden,
                             n_layers=self.n_layers, graph_readout=True)
        return PNAConfig(d_in=info["d_feat"], d_hidden=self.d_hidden,
                         n_layers=self.n_layers)

    def _block_shape(self, shape: str):
        """Static (n_block_nodes, n_block_edges, n_seeds) per cell."""
        info = GNN_SHAPES[shape]
        if shape == "minibatch_lg":
            s = info["batch_nodes"]
            f1, f2 = info["fanout"]
            l1 = s * f1
            l2 = s * f1 * f2
            return s + l1 + l2, l1 + l2, s
        if shape == "molecule":
            return (info["batch"] * info["n_nodes"],
                    info["batch"] * info["n_edges"], info["batch"])
        return info["n_nodes"], info["n_edges"], info["n_nodes"]

    def lowerable(self, shape: str,
                  mesh_axis_names=("data", "model"),
                  variant: str = "baseline") -> Cell:
        from repro.models import gnn as G
        cfg = self._cfg(shape)
        info = GNN_SHAPES[shape]
        d = data_axes_of(mesh_axis_names)
        n_nodes, n_edges, n_seeds = self._block_shape(shape)
        # pad ragged graph arrays to mesh-divisible sizes (padding edges
        # point at a dummy node / carry zero weight in the real pipeline)
        pad = lambda n: -(-n // 512) * 512  # noqa: E731
        n_nodes, n_edges, n_seeds = pad(n_nodes), pad(n_edges), pad(n_seeds)

        batch_abs = {
            "features": sds((n_nodes, info["d_feat"]), jnp.float32),
            "src": sds((n_edges,), jnp.int32),
            "dst": sds((n_edges,), jnp.int32),
        }
        bspecs = {"features": P(d, None), "src": P(d), "dst": P(d)}
        if shape == "molecule":
            batch_abs["graph_ids"] = sds((n_nodes,), jnp.int32)
            batch_abs["labels"] = sds((n_seeds,), jnp.float32)
            bspecs["graph_ids"] = P(d)
            bspecs["labels"] = P(d)
            loss_fn = lambda p, b: G.graph_loss(p, cfg, b)  # noqa: E731
        else:
            batch_abs["labels"] = sds((n_seeds,), jnp.int32)
            bspecs["labels"] = P(d)
            if shape == "minibatch_lg":
                batch_abs["node_ids"] = sds((n_nodes,), jnp.int32)
                batch_abs["seed_local"] = sds((n_seeds,), jnp.int32)
                bspecs["node_ids"] = P(d)
                bspecs["seed_local"] = P(d)
            loss_fn = lambda p, b: G.node_loss(p, cfg, b)  # noqa: E731

        params_abs = jax.eval_shape(
            lambda k: G.init_params(k, cfg), jax.random.PRNGKey(0))
        pspecs = sh.param_specs(params_abs, self.ruleset, mesh_axis_names)
        optimizer = opt_lib.adam(self.lr)
        hook = None
        if cfg.node_vocab:
            hook = steps_lib.FQuantHook(
                cfg=FQuantConfig(), table_path="embed_table",
                indices_fn=lambda b: b["node_ids"],
                labels_fn=lambda b: jnp.ones(b["node_ids"].shape[0],
                                             jnp.float32),
                sparse_snap=variant == "optimized")
        step = steps_lib.make_train_step(loss_fn, optimizer, hook)
        state_abs = jax.eval_shape(
            lambda p: steps_lib.init_state(p, optimizer, hook), params_abs)
        sspecs = train_state_specs(state_abs, pspecs, "embed_table")
        return Cell(step, (state_abs, batch_abs), (sspecs, bspecs),
                    kind="train", donate=(0,),
                    out_specs=(sspecs, TRAIN_METRIC_SPECS))

    def smoke(self) -> dict:
        import numpy as np

        from repro.data.graphs import padded_subgraph, random_graph
        from repro.models import gnn as G
        from repro.models.gnn import PNAConfig
        g = random_graph(400, 6, 12, seed=3)
        blk = padded_subgraph(g, np.arange(16), (4, 3), seed=1)
        batch = {k: jnp.asarray(v) for k, v in blk.items()}
        cfg = PNAConfig(d_in=12, d_hidden=16, n_layers=2, node_vocab=400)
        params = G.init_params(jax.random.PRNGKey(0), cfg)
        optimizer = opt_lib.adam(0.01)
        hook = steps_lib.FQuantHook(
            cfg=FQuantConfig(), table_path="embed_table",
            indices_fn=lambda b: b["node_ids"],
            labels_fn=lambda b: jnp.ones(b["node_ids"].shape[0],
                                         jnp.float32))
        step = jax.jit(steps_lib.make_train_step(
            lambda p, b: G.node_loss(p, cfg, b), optimizer, hook))
        state = steps_lib.init_state(params, optimizer, hook)
        losses = []
        for _ in range(3):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        logits = G.forward(state.params, cfg, batch)
        return {"loss_first": losses[0], "loss_last": losses[-1],
                "serve_shape": tuple(logits.shape),
                "finite": bool(jnp.isfinite(logits).all())}
