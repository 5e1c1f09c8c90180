"""Shared helpers: chunked linear algebra, padding, pytree utilities."""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np


# checkout root: src/repro/utils.py → src/repro → src → root
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    For entry points only — importing `repro` never turns it on, so tests
    that compile for a described chip stay free of cache warnings. JAX
    reads JAX_COMPILATION_CACHE_DIR itself when it is set; otherwise the
    cache lives at <checkout>/.jax_cache, a fixed path, so a later run in
    the same checkout finds what an earlier one stored.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def pad_to_multiple(x: jax.Array, multiple: int, axis: int = 0, value=0):
    """Pad `axis` of x up to a multiple; returns (padded, original_len)."""
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value), n


def chunked_map(fn, x: jax.Array, chunk: int):
    """Apply fn over chunks of x's leading axis via lax.map (bounded memory).

    fn must be shape-polymorphic only in outputs' leading axis == chunk.
    Returns outputs with padding stripped.
    """
    xp, n = pad_to_multiple(x, chunk, axis=0)
    xc = xp.reshape((-1, chunk) + xp.shape[1:])
    out = jax.lax.map(fn, xc)
    out = jax.tree.map(lambda o: o.reshape((-1,) + o.shape[2:])[:n], out)
    return out


@functools.partial(jax.jit, static_argnames=("chunk",))
def pairwise_neg_sqdist_argmin(X, C, chunk: int = 16384):
    """argmin_j ||x_i - c_j||^2 and the min value, chunked over rows of X."""
    Cn = jnp.sum(C * C, axis=-1)

    def f(xb):
        s = xb @ C.T
        d = Cn[None, :] - 2.0 * s  # ||x||^2 dropped (const per row)
        idx = jnp.argmin(d, axis=-1)
        xn = jnp.sum(xb * xb, axis=-1)
        return idx.astype(jnp.int32), jnp.take_along_axis(d, idx[:, None], axis=-1)[:, 0] + xn

    return chunked_map(f, X, chunk)


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def topk_inner_product(Q, X, k: int, chunk: int = 8192):
    """Exact MIPS top-k of each query in Q against X, chunked over X.

    Returns (values (nq,k), indices (nq,k)). Memory bounded by nq*chunk.
    """
    nq = Q.shape[0]
    n = X.shape[0]
    Xp, _ = pad_to_multiple(X, chunk, axis=0)
    nchunks = Xp.shape[0] // chunk

    def body(carry, i):
        bv, bi = carry
        xb = jax.lax.dynamic_slice_in_dim(Xp, i * chunk, chunk, axis=0)
        s = Q @ xb.T  # (nq, chunk)
        base = i * chunk
        idx = base + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where(idx[None, :] < n, s, -jnp.inf)
        cv = jnp.concatenate([bv, s], axis=1)
        ci = jnp.concatenate([bi, jnp.broadcast_to(idx[None, :], (nq, chunk))], axis=1)
        v, pos = jax.lax.top_k(cv, k)
        return (v, jnp.take_along_axis(ci, pos, axis=1)), None

    init = (jnp.full((nq, k), -jnp.inf, Q.dtype), jnp.full((nq, k), -1, jnp.int32))
    (v, i), _ = jax.lax.scan(body, init, jnp.arange(nchunks))
    return v, i


def tree_bytes(tree) -> int:
    return sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "shape"))


def tree_param_count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree) if hasattr(x, "shape"))
