"""Seeded corpus and query pool of a configuration, made on the device.

The generator is copied from `src/repro/data/vectors.py::make_manifold`
(the repository's `glove_like` dataset) so that no later change to the
program can change the benchmark's data: a random two-layer MLP embeds a
low-dimensional Gaussian, x = normalize(W2 tanh(2 W1 z)), giving unit-norm
vectors on a continuous manifold of `intrinsic_dim` dimensions. Unlike the
original, the base vectors and the query pool come from separate keys: the
base vectors and the embedding (W1, W2) from the configuration's
`data_seed`, the query pool from the run's `--seed`. Every run therefore
serves the same index, whose compiled shapes depend on its partition
sizes, to queries drawn afresh from the same distribution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A JAX PRNG key from any whole number (negative or above 32 bits)."""
    words = np.random.SeedSequence([seed % 2**64, stream]).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32),
                                    impl="threefry2x32")


def rng(seed: int, stream: int) -> np.random.Generator:
    """A host NumPy generator for one named use of `seed`."""
    return np.random.default_rng([seed % 2**64, stream])


@functools.partial(jax.jit, static_argnames=("n", "nq", "d", "p", "hidden"))
def _manifold(data_key, query_key, *, n, nq, d, p, hidden):
    k1, k2, k3 = jax.random.split(data_key, 3)
    w1 = jax.random.normal(k1, (p, hidden)) / np.sqrt(p)
    w2 = jax.random.normal(k2, (hidden, d)) / np.sqrt(hidden)

    def embed(z):
        x = jnp.tanh(2.0 * (z @ w1)) @ w2
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (embed(jax.random.normal(k3, (n, p))),
            embed(jax.random.normal(query_key, (nq, p))))


def make(cfg: dict, seed: int):
    """(base vectors (n, d), query pool (n_queries, d)), host float32."""
    X, Q = _manifold(seed_key(cfg["data_seed"], 0), seed_key(seed, 1),
                     n=cfg["n"], nq=cfg["n_queries"], d=cfg["d"],
                     p=cfg["intrinsic_dim"], hidden=cfg["hidden"])
    return np.asarray(X, np.float32), np.asarray(Q, np.float32)
