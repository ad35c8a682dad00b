"""Plain reference of DLRM [arXiv:1906.00091] as served and trained:
bottom MLP (ReLU after every layer) over the dense features, pairwise
dot interactions of the dense vector and the field embeddings (upper
triangle), top MLP (ReLU after all but the last layer) over
[dense vector, interactions].  ``dot`` sets each matmul's precision.

Also the model's counts per example, for the per-layer metrics.
"""

import jax.numpy as jnp
import numpy as np


def _mlp(layers, x, dot, final_act):
    n = len(layers)
    for i in range(n):
        x = dot("bi,io->bo", x, layers[f"l{i}"]["w"]) + layers[f"l{i}"]["b"]
        if i < n - 1 or final_act:
            x = jnp.maximum(x, 0.0)
    return x


def logits(params, emb, batch, dot):
    """(B, F, D) served rows + the batch -> (B,) logits."""
    net = params["net"]
    x = _mlp(net["bot"], batch["dense"], dot, True)
    feats = jnp.concatenate([x[:, None, :], emb], axis=1)
    inter = dot("bfd,bgd->bfg", feats, feats)
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    z = jnp.concatenate([x, inter[:, iu, ju]], axis=-1)
    return _mlp(net["top"], z, dot, False)[:, 0]


def _mlp_dims(sizes):
    f = len(sizes["cardinalities"])
    d = sizes["embed_dim"]
    bot = [sizes["num_dense"]] + list(sizes["bot_mlp"])
    top = [d + (f + 1) * f // 2] + list(sizes["top_mlp"])
    return bot, top


def head_flops(sizes) -> int:
    """Forward FLOPs per example: both MLPs and the upper-triangle
    interactions (a multiply-add is 2)."""
    bot, top = _mlp_dims(sizes)
    f = len(sizes["cardinalities"])
    mm = sum(a * b for a, b in zip(bot, bot[1:]))
    mm += sum(a * b for a, b in zip(top, top[1:]))
    inter = (f + 1) * f // 2 * sizes["embed_dim"]
    return 2 * (mm + inter)


def head_params(sizes) -> int:
    bot, top = _mlp_dims(sizes)
    return sum(a * b + b for dims in (bot, top)
               for a, b in zip(dims, dims[1:]))


def input_bytes(sizes) -> int:
    """Ids, dense features and the logit of one example."""
    return 4 * (len(sizes["cardinalities"]) + sizes["num_dense"] + 1)
