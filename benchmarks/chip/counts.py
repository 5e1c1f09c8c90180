"""Work the window-scoring stage must do, counted from the algorithm.

Per query, PQ scoring of the candidate window has to read the code of
every live candidate in the probed partitions once, read the query's
lookup table once, and write one score per live candidate. The counts
below take the live candidates from the partition sizes (never from the
padded capacity `pmax`), codes at log2(centres) bits each (4 bits for 16
centres: below the byte a code is stored in today, so packing nibbles
cannot push a share past 100%), and float32 tables and scores.

The operations are one table lookup and one add per candidate and
subspace. Against a chip's peaks they take far less time than the bytes
do, so the window kernel's roofline is bound by HBM bytes.
"""
from __future__ import annotations

import math

import numpy as np


def live_sizes(part_ids: np.ndarray) -> np.ndarray:
    """(c,) live slots per partition of a padded (c, cap) id table, where
    -1 marks an empty or deleted slot."""
    return (np.asarray(part_ids) >= 0).sum(axis=1)


def probed_live(Q, centroids, sizes, top_t: int) -> np.ndarray:
    """(nq,) live candidates each query's window holds: the live sizes of
    its `top_t` best partitions by inner product with the centroids."""
    Q = np.asarray(Q, np.float32)
    C = np.asarray(centroids, np.float32)
    out = np.empty(Q.shape[0], np.int64)
    for s in range(0, Q.shape[0], 4096):
        sc = Q[s:s + 4096] @ C.T
        top = np.argpartition(-sc, top_t - 1, axis=1)[:, :top_t]
        out[s:s + 4096] = np.asarray(sizes)[top].sum(axis=1)
    return out


def window_bytes(live, m: int, centres: int = 16) -> np.ndarray:
    """HBM bytes per query: codes at log2(centres) bits, the float32
    (m, centres) lookup table once, one float32 score per candidate."""
    code_bytes = m * math.ceil(math.log2(centres)) / 8
    return np.asarray(live) * (code_bytes + 4) + m * centres * 4


def window_ops(live, m: int) -> np.ndarray:
    """Lookups-and-adds per query: one per live candidate and subspace."""
    return np.asarray(live) * m


def roofline_share(bytes_, ops, seconds: float, peak: dict) -> float:
    """Share (%) of the chip's roofline: the least time the work could
    take, the larger of bytes over HBM bandwidth and operations over the
    peak rate, divided by the time it took."""
    least = max(bytes_ / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
    return 100.0 * least / seconds
