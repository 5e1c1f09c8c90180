"""The plain reference and the comparison that decides `correct`.

Independent of the code under test: it imports nothing of `repro` and
takes nothing the program made, only the base vectors and queries the
benchmark generated.

- `exact_topk_device`: brute-force top-k inner product, in blocks of
  queries and of base vectors, at `Precision.HIGHEST` (float32 products).
- `exact_topk` and `recall_at_k`: the host NumPy float32 brute force and
  recall, copied from `chip_smoke.py`; the tests hold the device reference
  and the vectorised recall to them.
- `control_topk_device`: the same brute force one precision step below
  the configuration's float32-at-HIGHEST rerank, the three-pass bfloat16
  product of `Precision.HIGH`, written out so that it computes the same on
  every backend.
- `check`: the numbers compared, each against its limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

K = 10


# ------------------------------------------- copied from chip_smoke.py
def exact_topk(X, Q, k: int = K, keep=None, chunk: int = 65_536):
    """Brute-force top-k inner product in host NumPy f32: row ids of X,
    best first, restricted to rows where `keep` is True. Independent of
    the code under test."""
    X = np.asarray(X, np.float32)
    Q = np.asarray(Q, np.float32)
    nq = Q.shape[0]
    best_v = np.full((nq, k), -np.inf, np.float32)
    best_i = np.full((nq, k), -1, np.int64)
    for s in range(0, X.shape[0], chunk):
        S = Q @ X[s:s + chunk].T
        if keep is not None:
            S[:, ~keep[s:s + chunk]] = -np.inf
        kk = min(k, S.shape[1])
        part = np.argpartition(-S, kk - 1, axis=1)[:, :kk]
        v = np.concatenate([best_v, np.take_along_axis(S, part, 1)], 1)
        i = np.concatenate([best_i, part + s], 1)
        top = np.argsort(-v, axis=1, kind="stable")[:, :k]
        best_v = np.take_along_axis(v, top, 1)
        best_i = np.take_along_axis(i, top, 1)
    return np.where(np.isfinite(best_v), best_i, -1)


def recall_at_k(got, ref) -> float:
    got = np.asarray(got)[:, :K]
    hits = [len(set(g[g >= 0]) & set(r[r >= 0])) / max((r >= 0).sum(), 1)
            for g, r in zip(got, ref)]
    return float(np.mean(hits))
# ----------------------------------------------------------------------


def _dot_highest(q, x):
    return jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)


def _dot_high3(q, x):
    """Three-pass bfloat16 product (what `Precision.HIGH` computes on a
    TPU): each float32 operand splits into a bfloat16 high part and a
    bfloat16 low part, and the low-by-low product is dropped. The parts
    are rounded with `reduce_precision`, which the compiler keeps even
    where it may otherwise compute in excess precision, and multiplied
    exactly at HIGHEST, so the control computes the same on every
    backend."""
    def bf16(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def split(a):
        hi = bf16(a)
        return hi, bf16(a - hi)

    (qh, ql), (xh, xl) = split(q), split(x)
    return _dot_highest(qh, xh) + _dot_highest(qh, xl) + _dot_highest(ql, xh)


_DOTS = {"highest": _dot_highest, "high3": _dot_high3}


@functools.partial(jax.jit, static_argnames=("k", "chunk", "dot"))
def _topk_blocks(Q, X, n, *, k, chunk, dot):
    """Top-k of Q against the first n rows of X (padded to a multiple of
    `chunk`), one base-vector block at a time."""
    blocks = X.reshape(-1, chunk, X.shape[1])

    def body(carry, xb_i):
        bv, bi = carry
        xb, i = xb_i
        s = _DOTS[dot](Q, xb)
        idx = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where(idx[None, :] < n, s, -jnp.inf)
        v, pos = jax.lax.top_k(jnp.concatenate([bv, s], 1), k)
        cand = jnp.concatenate([bi, jnp.broadcast_to(idx, s.shape)], 1)
        return (v, jnp.take_along_axis(cand, pos, 1)), None

    init = (jnp.full((Q.shape[0], k), -jnp.inf, jnp.float32),
            jnp.full((Q.shape[0], k), -1, jnp.int32))
    (v, i), _ = jax.lax.scan(body, init,
                             (blocks, jnp.arange(blocks.shape[0])))
    return i, v


def _topk_device(X, Q, k, dot, q_block, chunk):
    n = X.shape[0]
    chunk = min(chunk, n)
    Xd = jnp.asarray(np.pad(X, ((0, (-n) % chunk), (0, 0))))
    ids, vals = [], []
    for s in range(0, Q.shape[0], q_block):
        qb = Q[s:s + q_block]
        qp = np.pad(qb, ((0, q_block - qb.shape[0]), (0, 0)))
        i, v = _topk_blocks(jnp.asarray(qp), Xd, n, k=k, chunk=chunk,
                            dot=dot)
        ids.append(np.asarray(i)[:qb.shape[0]])
        vals.append(np.asarray(v)[:qb.shape[0]])
    del Xd
    return np.concatenate(ids), np.concatenate(vals)


def exact_topk_device(X, Q, k: int = K, q_block: int = 2048,
                      chunk: int = 65_536):
    """(ids, scores) of the exact top-k of every row of Q, float32 products
    at HIGHEST, on the default device."""
    return _topk_device(X, Q, k, "highest", min(q_block, len(Q)), chunk)


def control_topk_device(X, Q, k: int = K, q_block: int = 2048,
                        chunk: int = 65_536):
    """The reference one precision step down: three-pass bfloat16."""
    return _topk_device(X, Q, k, "high3", min(q_block, len(Q)), chunk)


def score_gap(X, pool, qidx, ids, scores, chunk: int = 8192) -> float:
    """Widest gap between a served score and the float64 inner product of
    its query and the id served beside it; inf for an id that is not a
    base vector."""
    n = X.shape[0]
    worst = 0.0
    for s in range(0, len(qidx), chunk):
        i = ids[s:s + chunk]
        ok = (i >= 0) & (i < n)
        if not ok.all():
            return float("inf")
        x = X[i].astype(np.float64)
        q = pool[qidx[s:s + chunk]].astype(np.float64)
        ref = np.einsum("ckd,cd->ck", x, q)
        gap = np.abs(scores[s:s + chunk].astype(np.float64) - ref)
        if not np.isfinite(gap).all():
            return float("inf")
        worst = max(worst, float(gap.max()))
    return worst


def duplicate_rows(ids) -> int:
    """Answers that name one id twice."""
    s = np.sort(ids, axis=1)
    return int((s[:, 1:] == s[:, :-1]).any(axis=1).sum())


def recall_rows(ids, ref_ids) -> np.ndarray:
    """Per answer: share of the exact top-k found (ref_ids row-aligned)."""
    k = ref_ids.shape[1]
    hits = (ids[:, :k, None] == ref_ids[:, None, :]) & (ref_ids[:, None, :] >= 0)
    return hits.any(axis=2).sum(axis=1) / np.maximum((ref_ids >= 0).sum(1), 1)


def readings(X, pool, qidx, ids, scores, exact_ids) -> dict:
    """Every number the comparison looks at, for answers (qidx, ids,
    scores): qidx[i] is the pool row answer i was asked for."""
    rec = np.concatenate([recall_rows(ids[s:s + 65536],
                                      exact_ids[qidx[s:s + 65536]])
                          for s in range(0, len(qidx), 65536)]) \
        if len(qidx) else np.zeros(0)
    return {"score_gap": score_gap(X, pool, qidx, ids, scores),
            "recall_at_10": float(rec.mean()) if rec.size else 0.0,
            "duplicate_ids": duplicate_rows(ids) if len(ids) else 0}


def check(read: dict, limits: dict, counters: dict) -> tuple:
    """(correct, checks): each compared number beside its limit.

    `limits` holds the configuration's: `score_gap` (upper), and
    `recall_at_10` (lower, the recall the deployment guarantees).
    `counters` are exact comparisons against 0: answers that never came,
    frontend failures, and duplicated ids."""
    checks = {"score_gap": {"value": read["score_gap"],
                            "limit": limits["score_gap"], "at_most": True},
              "recall_at_10": {"value": read["recall_at_10"],
                               "limit": limits["recall_at_10"],
                               "at_most": False}}
    for name, v in counters.items():
        checks[name] = {"value": v, "limit": 0, "at_most": True}
    ok = all((c["value"] <= c["limit"]) if c["at_most"]
             else (c["value"] >= c["limit"]) for c in checks.values())
    return ok, checks
