"""Recsys model zoo: DLRM / Wide&Deep / xDeepFM / BERT4Rec.

All four expose the SHARK interface (see core/taylor.py):

    model.init(key)                        -> params
    model.embed(params, batch, field_mask) -> (B, F, D) field embeddings
    model.loss_from_emb(params, emb, batch)-> (B,) per-sample BCE
    model.forward(params, batch, mask)     -> (B,) logits
    model.spec                             -> FieldSpec (stacked table)

The stacked embedding table lives at params["embed_table"] — a single
(sum_f V_f, D) array.  F-Quantization state (priority scores) attaches to
it globally; F-Permutation masks field slices of it.  Dense-side params
live under params["net"].

BERT4Rec is the odd one out (single item vocab, sequence model); its
"fields" for the SHARK interface are {item-embedding, position-embedding}
tables, with field-importance pruning documented as degenerate in
DESIGN.md §Arch-applicability.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics
from repro.models import embedding as E
from repro.models import layers as L

Array = jax.Array


class Model(NamedTuple):
    """Bound model API (callables close over the config)."""
    name: str
    spec: E.FieldSpec
    init: Callable
    embed: Callable          # (params, batch, field_mask=None) -> (B, F, D)
    head: Callable           # (params, emb, batch) -> (B,) logits
    forward: Callable        # (params, batch, field_mask=None) -> (B,)
    loss_from_emb: Callable  # (params, emb, batch) -> (B,) per-sample loss
    extras: dict = {}        # model-specific extra entry points


def _bce_from_emb(head):
    def loss_from_emb(params, emb, batch):
        logits = head(params, emb, batch)
        return metrics.bce_with_logits(logits, batch["labels"])
    return loss_from_emb


# ======================================================================
# DLRM (Naumov et al. 2019) — the paper's public-dataset baseline model
# ======================================================================

@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    cardinalities: tuple
    embed_dim: int = 64
    num_dense: int = 13
    bot_mlp: tuple = (512, 256, 64)
    top_mlp: tuple = (512, 512, 256, 1)
    param_dtype: object = jnp.float32


def make_dlrm(cfg: DLRMConfig) -> Model:
    spec = E.FieldSpec(tuple(int(c) for c in cfg.cardinalities),
                       cfg.embed_dim)
    f = spec.num_fields
    assert cfg.bot_mlp[-1] == cfg.embed_dim, \
        "bottom MLP must project dense features to embed_dim"
    n_inter = (f + 1) * f // 2  # pairwise dots incl. dense-vs-sparse
    top_in = cfg.embed_dim + n_inter

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "embed_table": E.init_table(k1, spec, dtype=cfg.param_dtype),
            "net": {
                "bot": L.mlp_init(k2, (cfg.num_dense,) + cfg.bot_mlp,
                                  cfg.param_dtype),
                "top": L.mlp_init(k3, (top_in,) + cfg.top_mlp,
                                  cfg.param_dtype),
            },
        }

    def embed(params, batch, field_mask=None):
        return E.field_lookup(params["embed_table"], batch["indices"], spec,
                              field_mask)

    @jax.named_scope("head")
    def head(params, emb, batch):
        dense = L.mlp(params["net"]["bot"], batch["dense"],
                      final_act=True)                      # (B, D)
        feats = jnp.concatenate([dense[:, None, :], emb], axis=1)
        inter = jnp.einsum("bfd,bgd->bfg", feats, feats,
                           preferred_element_type=jnp.float32)
        iu, ju = jnp.triu_indices(feats.shape[1], k=1)
        flat = inter[:, iu, ju]                            # (B, n_inter)
        z = jnp.concatenate([dense, flat.astype(dense.dtype)], axis=-1)
        return L.mlp(params["net"]["top"], z)[:, 0]

    def forward(params, batch, field_mask=None):
        return head(params, embed(params, batch, field_mask), batch)

    # no fused_head: DLRM's first consumer of emb is the Gram
    # interaction (bfd,bgd->bfg), not a linear layer over the flattened
    # bag — the fused lookup (packed_lookup_fused) is the fusion
    # ceiling for this head (docs/kernels.md)
    return Model("dlrm", spec, init, embed, head, forward,
                 _bce_from_emb(head))


# ======================================================================
# Wide & Deep (Cheng et al. 2016)
# ======================================================================

@dataclasses.dataclass(frozen=True)
class WideDeepConfig:
    cardinalities: tuple
    embed_dim: int = 32
    mlp: tuple = (1024, 512, 256)
    param_dtype: object = jnp.float32


def make_wide_deep(cfg: WideDeepConfig) -> Model:
    spec = E.FieldSpec(tuple(int(c) for c in cfg.cardinalities),
                       cfg.embed_dim)
    f = spec.num_fields

    def init(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "embed_table": E.init_table(k1, spec, dtype=cfg.param_dtype),
            # wide part: per-row scalar weights (an embed_dim=1 table)
            "wide_table": E.init_table(
                k2, E.FieldSpec(spec.cardinalities, 1), scale=0.0,
                dtype=cfg.param_dtype),
            "net": {
                "deep": L.mlp_init(k3, (f * cfg.embed_dim,) + cfg.mlp
                                   + (1,), cfg.param_dtype),
                "bias": jnp.zeros((1,), cfg.param_dtype),
            },
        }

    def embed(params, batch, field_mask=None):
        return E.field_lookup(params["embed_table"], batch["indices"], spec,
                              field_mask)

    @jax.named_scope("head")
    def head(params, emb, batch):
        b = emb.shape[0]
        wide_spec = E.FieldSpec(spec.cardinalities, 1)
        wide = E.field_lookup(params["wide_table"], batch["indices"],
                              wide_spec)
        deep = L.mlp(params["net"]["deep"], emb.reshape(b, -1))[:, 0]
        return deep + wide.sum(axis=(1, 2)) + params["net"]["bias"][0]

    def fused_head(params, batch, bag_matmul):
        """``head`` with the deep branch's first matmul fused into the
        embedding gather: ``bag_matmul(w)`` must compute
        ``emb.reshape(B, F*D) @ w`` (e.g. ``packed_store.bag_matmul``
        closed over the packed table and the batch's global indices) —
        the (B, F*D) activations never materialise.  The wide branch is
        an embed_dim=1 table lookup and stays as-is.
        """
        y0 = bag_matmul(params["net"]["deep"]["l0"]["w"])
        deep = L.mlp_tail(params["net"]["deep"], y0)[:, 0]
        wide_spec = E.FieldSpec(spec.cardinalities, 1)
        wide = E.field_lookup(params["wide_table"], batch["indices"],
                              wide_spec)
        return deep + wide.sum(axis=(1, 2)) + params["net"]["bias"][0]

    def forward(params, batch, field_mask=None):
        return head(params, embed(params, batch, field_mask), batch)

    return Model("wide_deep", spec, init, embed, head, forward,
                 _bce_from_emb(head),
                 extras={"fused_head": fused_head,
                         "fused_needs_emb": False})


# ======================================================================
# xDeepFM (Lian et al. 2018) — CIN feature interaction
# ======================================================================

@dataclasses.dataclass(frozen=True)
class XDeepFMConfig:
    cardinalities: tuple
    embed_dim: int = 10
    cin_layers: tuple = (200, 200, 200)
    mlp: tuple = (400, 400)
    param_dtype: object = jnp.float32


def cin_layer(w: Array, x_k: Array, x_0: Array) -> Array:
    """One CIN layer: (B,H,D),(B,M,D),(O,H,M) -> (B,O,D).

    X^{k+1}_o = sum_{h,m} W_{o,h,m} * (X^k_h ∘ X^0_m)   (Hadamard over D)
    The (B,H,M,D) outer product is the hot spot — fused in
    repro/kernels/cin for TPU; this jnp version is the oracle.
    """
    outer = jnp.einsum("bhd,bmd->bhmd", x_k, x_0,
                       preferred_element_type=jnp.float32)
    return jnp.einsum("bhmd,ohm->bod", outer, w,
                      preferred_element_type=jnp.float32).astype(x_k.dtype)


def make_xdeepfm(cfg: XDeepFMConfig) -> Model:
    spec = E.FieldSpec(tuple(int(c) for c in cfg.cardinalities),
                       cfg.embed_dim)
    f = spec.num_fields

    def init(key):
        keys = jax.random.split(key, 4 + len(cfg.cin_layers))
        cin = {}
        h = f
        for i, o in enumerate(cfg.cin_layers):
            cin[f"w{i}"] = (jax.random.normal(keys[4 + i], (o, h, f),
                                              jnp.float32)
                            * (1.0 / np.sqrt(h * f))).astype(cfg.param_dtype)
            h = o
        return {
            "embed_table": E.init_table(keys[0], spec,
                                        dtype=cfg.param_dtype),
            "wide_table": E.init_table(
                keys[1], E.FieldSpec(spec.cardinalities, 1), scale=0.0,
                dtype=cfg.param_dtype),
            "net": {
                "cin": cin,
                "cin_out": L.dense_bias_init(
                    keys[2], sum(cfg.cin_layers), 1, cfg.param_dtype),
                "deep": L.mlp_init(keys[3], (f * cfg.embed_dim,) + cfg.mlp
                                   + (1,), cfg.param_dtype),
            },
        }

    def embed(params, batch, field_mask=None):
        return E.field_lookup(params["embed_table"], batch["indices"], spec,
                              field_mask)

    @jax.named_scope("head")
    def head(params, emb, batch):
        b = emb.shape[0]
        x0 = emb
        xk = emb
        pooled = []
        for i in range(len(cfg.cin_layers)):
            xk = cin_layer(params["net"]["cin"][f"w{i}"], xk, x0)
            pooled.append(xk.sum(axis=-1))        # (B, O_i)
        cin_feat = jnp.concatenate(pooled, axis=-1)
        cin_logit = L.dense_bias(params["net"]["cin_out"], cin_feat)[:, 0]
        deep_logit = L.mlp(params["net"]["deep"], emb.reshape(b, -1))[:, 0]
        wide_spec = E.FieldSpec(spec.cardinalities, 1)
        wide = E.field_lookup(params["wide_table"], batch["indices"],
                              wide_spec).sum(axis=(1, 2))
        return cin_logit + deep_logit + wide

    def fused_head(params, batch, bag_matmul, emb):
        """``head`` with the deep branch's first matmul fused into the
        embedding gather (``bag_matmul(w)`` as in wide&deep).  The CIN
        consumes the (B, F, D) field embeddings directly, so ``emb``
        is still required — only the deep MLP's (B, F*D) reshape +
        first matmul round-trip is eliminated.
        """
        b = emb.shape[0]
        x0 = emb
        xk = emb
        pooled = []
        for i in range(len(cfg.cin_layers)):
            xk = cin_layer(params["net"]["cin"][f"w{i}"], xk, x0)
            pooled.append(xk.sum(axis=-1))
        cin_feat = jnp.concatenate(pooled, axis=-1)
        cin_logit = L.dense_bias(params["net"]["cin_out"], cin_feat)[:, 0]
        y0 = bag_matmul(params["net"]["deep"]["l0"]["w"])
        deep_logit = L.mlp_tail(params["net"]["deep"], y0)[:, 0]
        wide_spec = E.FieldSpec(spec.cardinalities, 1)
        wide = E.field_lookup(params["wide_table"], batch["indices"],
                              wide_spec).sum(axis=(1, 2))
        return cin_logit + deep_logit + wide

    def forward(params, batch, field_mask=None):
        return head(params, embed(params, batch, field_mask), batch)

    return Model("xdeepfm", spec, init, embed, head, forward,
                 _bce_from_emb(head),
                 extras={"fused_head": fused_head,
                         "fused_needs_emb": True})


# ======================================================================
# BERT4Rec (Sun et al. 2019) — bidirectional sequence recommendation
# ======================================================================

@dataclasses.dataclass(frozen=True)
class Bert4RecConfig:
    num_items: int = 50002        # incl. [MASK]/[PAD]
    embed_dim: int = 64
    n_blocks: int = 2
    n_heads: int = 2
    seq_len: int = 200
    d_ff_mult: int = 4
    param_dtype: object = jnp.float32


def make_bert4rec(cfg: Bert4RecConfig) -> Model:
    # SHARK fields: {item table, position table} — see module docstring
    spec = E.FieldSpec((cfg.num_items, cfg.seq_len), cfg.embed_dim)
    d = cfg.embed_dim
    hd = d // cfg.n_heads

    def init(key):
        keys = jax.random.split(key, 2 + cfg.n_blocks)
        item = (jax.random.normal(keys[0], (cfg.num_items, d), jnp.float32)
                * 0.02).astype(cfg.param_dtype)
        pos = (jax.random.normal(keys[1], (cfg.seq_len, d), jnp.float32)
               * 0.02).astype(cfg.param_dtype)
        pad = spec.total_rows - (cfg.num_items + cfg.seq_len)
        blocks = []
        for i in range(cfg.n_blocks):
            ka, kf = jax.random.split(keys[2 + i])
            k1, k2, k3, k4 = jax.random.split(ka, 4)
            blocks.append({
                "wq": L.dense_bias_init(k1, d, d, cfg.param_dtype),
                "wk": L.dense_bias_init(k2, d, d, cfg.param_dtype),
                "wv": L.dense_bias_init(k3, d, d, cfg.param_dtype),
                "wo": L.dense_bias_init(k4, d, d, cfg.param_dtype),
                "ln1": L.layernorm_init(d, cfg.param_dtype),
                "ln2": L.layernorm_init(d, cfg.param_dtype),
                "ffn": L.mlp_init(kf, (d, d * cfg.d_ff_mult, d),
                                  cfg.param_dtype),
            })
        padding = jnp.zeros((pad, d), cfg.param_dtype)
        return {"embed_table": jnp.concatenate([item, pos, padding],
                                               axis=0),
                "net": {"blocks": blocks,
                        "ln_f": L.layernorm_init(d, cfg.param_dtype)}}

    def _tables(params):
        item = params["embed_table"][:cfg.num_items]
        pos = params["embed_table"][cfg.num_items:cfg.num_items
                                    + cfg.seq_len]
        return item, pos

    def encode(params, inputs: Array) -> Array:
        item, pos = _tables(params)
        b, t = inputs.shape
        x = jnp.take(item, inputs, axis=0) + pos[None, :t]
        for blk in params["net"]["blocks"]:
            h = L.layernorm(blk["ln1"], x)
            q = L.dense_bias(blk["wq"], h).reshape(b, t, cfg.n_heads, hd)
            k = L.dense_bias(blk["wk"], h).reshape(b, t, cfg.n_heads, hd)
            v = L.dense_bias(blk["wv"], h).reshape(b, t, cfg.n_heads, hd)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32) / np.sqrt(hd)
            a = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", a, v.astype(jnp.float32))
            x = x + L.dense_bias(blk["wo"],
                                 o.reshape(b, t, d).astype(x.dtype))
            h = L.layernorm(blk["ln2"], x)
            x = x + L.mlp(blk["ffn"], h, act=jax.nn.gelu)
        return L.layernorm(params["net"]["ln_f"], x)

    def item_logits(params, inputs: Array) -> Array:
        """(B, T, num_items) cloze logits (tied item embedding head)."""
        hidden = encode(params, inputs)
        item, _ = _tables(params)
        return jnp.einsum("btd,vd->btv", hidden, item,
                          preferred_element_type=jnp.float32)

    # -- SHARK interface (fields = {item, position} tables) ---------------

    def embed(params, batch, field_mask=None):
        item, pos = _tables(params)
        inputs = batch["inputs"]
        b, t = inputs.shape
        e_item = jnp.take(item, inputs, axis=0).mean(axis=1)   # (B, D)
        e_pos = jnp.broadcast_to(pos[:t].mean(axis=0), (b, d))
        emb = jnp.stack([e_item, e_pos], axis=1)               # (B, 2, D)
        if field_mask is not None:
            emb = emb * field_mask.astype(emb.dtype)[None, :, None]
        return emb

    def head(params, emb, batch):
        raise NotImplementedError(
            "bert4rec uses sequence loss; see seq_loss/forward")

    def seq_loss(params, batch) -> Array:
        """Masked-position cross entropy (the training objective)."""
        logits = item_logits(params, batch["inputs"])
        ce = metrics.softmax_xent(logits, batch["targets"])
        m = batch["mask"]
        return (ce * m).sum() / jnp.maximum(m.sum(), 1.0)

    def forward(params, batch, field_mask=None):
        """Score of the true last item (serving: next-item score)."""
        logits = item_logits(params, batch["inputs"])
        last = logits[:, -1]
        return jnp.take_along_axis(
            last, batch["targets"][:, -1:], axis=-1)[:, 0]

    def loss_from_emb(params, emb, batch):
        del emb
        return seq_loss(params, batch)[None]

    return Model("bert4rec", spec, init, embed, head, forward,
                 loss_from_emb,
                 extras={"encode": encode, "item_logits": item_logits,
                         "seq_loss": seq_loss})


# ======================================================================
# retrieval scoring (the retrieval_cand shape): one query vs 1M candidates
# ======================================================================

def retrieval_scores(user_vec: Array, cand_table: Array) -> Array:
    """(D,) x (N, D) -> (N,) dot-product scores — batched, not a loop."""
    return cand_table @ user_vec
