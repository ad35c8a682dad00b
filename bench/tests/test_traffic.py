"""Inputs and weights come from ``--seed`` alone: the same seed gives
the same inputs, seeds beyond 32 bits work, and every seed gets the
same sizes."""

import numpy as np

from bench.lib import registry, traffic, weights

CARDS = [1000, 37, 5000, 3, 2_000_000]
BIG = 2 ** 33 + 17


def _mix(name):
    return registry.traffic(name)


def test_serve_pool_from_seed_alone():
    mix = dict(_mix("serve-zipf.sat"), pool_requests=4096)
    a = traffic.serve_pool(mix, CARDS, 13, BIG)
    b = traffic.serve_pool(mix, CARDS, 13, BIG)
    c = traffic.serve_pool(mix, CARDS, 13, BIG + 1)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["indices"], c["indices"])
    assert a["indices"].shape == c["indices"].shape == (4096, len(CARDS))
    assert (a["indices"] >= 0).all()
    assert (a["indices"] < np.asarray(CARDS)[None, :]).all()


def test_zipf_ids_are_skewed_and_scattered():
    mix = dict(_mix("serve-zipf.sat"), pool_requests=20000)
    ids = traffic.serve_pool(mix, CARDS, 0, 5)["indices"][:, 4]
    values, counts = np.unique(ids, return_counts=True)
    hot = values[np.argmax(counts)]
    assert counts.max() / ids.size > 0.03        # a hot id exists
    assert hot > 1000                            # not row 0..k


def test_train_pool_from_seed_alone():
    mix = dict(_mix("train-b8192"), batch=512, pool_batches=2)
    a = traffic.train_pool(mix, CARDS, 13, BIG)
    b = traffic.train_pool(mix, CARDS, 13, BIG)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["indices"], a[1]["indices"])
    assert 0.05 < a[0]["labels"].mean() < 0.95


def test_poisson_due_times():
    t = traffic.due_times({"rate_rps": 1000.0}, BIG, 2.0)
    assert (np.diff(t) > 0).all() and t[-1] < 2.0
    assert 1800 < t.size < 2200


def test_weights_from_seed_alone():
    import jax
    import jax.numpy as jnp
    shapes = {"embed_table": jax.ShapeDtypeStruct((64, 8), jnp.float32),
              "net": {"l0": {"w": jax.ShapeDtypeStruct((8, 4),
                                                       jnp.float32),
                             "b": jax.ShapeDtypeStruct((4,),
                                                       jnp.float32)}}}
    init = {"table_scale": 0.1, "bias_scale": 0.01}
    a = weights.make(shapes, init, BIG)
    b = weights.make(shapes, init, BIG)
    c = weights.make(shapes, init, BIG - 2 ** 32)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["embed_table"], c["embed_table"])
    assert abs(float(jnp.std(a["net"]["l0"]["w"])) - 8 ** -0.5) < 0.15
