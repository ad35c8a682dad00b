"""The one traffic generator.  A mix is a data file under
``bench/traffic/``; everything here is drawn from ``--seed`` alone,
before the measured window, so generating costs the window nothing.

Serve mixes (``"kind": "serve"``):
  ids      per field, Zipf(exponent) over ranks, rank wrapped to the
           field's cardinality, then scattered over the field's rows by
           a seeded affine bijection (the hot ids are not rows 0..k)
  dense    standard normal
  arrivals "closed": micro-batches back to back; "poisson": single
           requests at ``rate_rps``, due times from the seed

Train mixes (``"kind": "train"``): a Criteo-like click log with a
planted signal (copied from the program's ``data.criteo`` generator so
the yardstick does not move with it): per-field bounded Zipf ids,
per-value latent signals, planted field weights, labels drawn from the
resulting logit.
"""

from __future__ import annotations

import math

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def _scatter_params(rng, cards):
    """Per-field (mult, add) of the affine bijection r -> (m r + a) % V."""
    out = []
    for v in cards:
        v = int(v)
        m = int(rng.integers(1, max(2, v))) | 1
        while math.gcd(m, v) != 1:
            m += 2
        out.append((m % max(v, 1), int(rng.integers(0, max(v, 1)))))
    return out


def serve_ids(mix: dict, cards, n: int, seed: int) -> np.ndarray:
    """(n, F) int32 field-local ids."""
    cards = np.asarray(cards, np.int64)
    rng = rng_for(seed, 1)
    law = mix["ids"]
    if law["law"] != "zipf":
        raise ValueError(f"unknown id law {law['law']!r}")
    ranks = rng.zipf(law["exponent"], size=(n, cards.size)) - 1
    ranks %= cards[None, :]
    out = np.empty((n, cards.size), np.int64)
    for f, (m, a) in enumerate(_scatter_params(rng_for(seed, 2), cards)):
        out[:, f] = (ranks[:, f] * m + a) % cards[f]
    return out.astype(np.int32)


def serve_pool(mix: dict, cards, num_dense: int, seed: int) -> dict:
    """The requests a serve run draws from: ``pool_requests`` requests
    (ids, dense features), served in order and cycled."""
    n = int(mix["pool_requests"])
    pool = {"indices": serve_ids(mix, cards, n, seed)}
    if num_dense:
        pool["dense"] = rng_for(seed, 3).standard_normal(
            (n, num_dense)).astype(np.float32)
    return pool


def due_times(mix: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Open-loop due times in seconds from the window's start."""
    rate = float(mix["rate_rps"])
    rng = rng_for(seed, 4)
    n = int(rate * horizon_s * 1.2) + 16
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    return t[t < horizon_s]


# ---------------------------------------------------------------- train


class ClickLog:
    """Criteo-like click log with a planted signal (see module doc)."""

    def __init__(self, mix: dict, cards, num_dense: int, seed: int):
        self.mix = mix
        self.cards = np.asarray(cards, np.int64)
        self.num_dense = max(int(num_dense), 1)
        f = self.cards.size
        rng = rng_for(seed, 5)
        important = max(1, int(f * mix["important_share"]))
        w = np.zeros(f, np.float32)
        mags = 2.0 * 0.8 ** np.arange(important)
        w[:important] = mags * rng.choice([-1.0, 1.0], important)
        self.field_weight = w[rng.permutation(f)]
        self._sig = np.minimum(self.cards, 1 << 14)
        self.signals = [rng.standard_normal(int(s)).astype(np.float32)
                        for s in self._sig]
        self.seed = seed

    def _zipf(self, rng, n, card):
        u = np.maximum(rng.random(n), 1e-9)
        a = self.mix["zipf_a"]
        k = np.floor(u ** (-1.0 / (a - 1.0)) - 1.0)
        return np.clip(k, 0, card - 1).astype(np.int64)

    def batch(self, step: int) -> dict:
        b = int(self.mix["batch"])
        rng = rng_for(self.seed, 6, step)
        f = self.cards.size
        idx = np.empty((b, f), np.int64)
        logit = np.full(b, self.mix["bias"], np.float32)
        for j in range(f):
            idx[:, j] = self._zipf(rng, b, int(self.cards[j]))
            logit += self.field_weight[j] * self.signals[j][
                idx[:, j] % self._sig[j]]
        dense = rng.standard_normal((b, self.num_dense)).astype(np.float32)
        logit += 0.1 * dense.sum(axis=1)
        logit += rng.standard_normal(b).astype(np.float32) \
            * self.mix["noise"]
        labels = (rng.random(b) < 1.0 / (1.0 + np.exp(-logit))
                  ).astype(np.float32)
        return {"indices": idx.astype(np.int32), "dense": dense,
                "labels": labels}


def train_pool(mix: dict, cards, num_dense: int, seed: int) -> list:
    log = ClickLog(mix, cards, num_dense, seed)
    return [log.batch(s) for s in range(int(mix["pool_batches"]))]
