"""Plain reference of Wide & Deep [arXiv:1606.07792 sections 3-4] as
served: deep MLP (ReLU after all but the last layer) over the
concatenated field embeddings, plus the wide part, one scalar weight
per row summed over the fields, plus a bias.  ``dot`` sets each
matmul's precision.

Also the model's counts per example, for the per-layer metrics.
"""

import jax.numpy as jnp


def logits(params, emb, batch, dot):
    net = params["net"]["deep"]
    b = emb.shape[0]
    x = emb.reshape(b, -1)
    n = len(net)
    for i in range(n):
        x = dot("bi,io->bo", x, net[f"l{i}"]["w"]) + net[f"l{i}"]["b"]
        if i < n - 1:
            x = jnp.maximum(x, 0.0)
    wide = jnp.take(params["wide_table"][:, 0], batch["gidx"], axis=0)
    return x[:, 0] + wide.sum(axis=1) + params["net"]["bias"][0]


def _dims(sizes):
    f = len(sizes["cardinalities"])
    return [f * sizes["embed_dim"]] + list(sizes["mlp"]) + [1]


def head_flops(sizes) -> int:
    dims = _dims(sizes)
    f = len(sizes["cardinalities"])
    return 2 * sum(a * b for a, b in zip(dims, dims[1:])) + f + 1


def head_params(sizes) -> int:
    dims = _dims(sizes)
    return sum(a * b + b for a, b in zip(dims, dims[1:])) + 1


def input_bytes(sizes) -> int:
    """Ids, the wide weights they read, and the logit of one example."""
    return 4 * (2 * len(sizes["cardinalities"]) + 1)
