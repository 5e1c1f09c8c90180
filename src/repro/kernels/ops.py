"""Public jit'd wrappers for the Pallas kernels.

Off-TPU the kernels execute in interpret mode — the kernel body runs as
traced jnp ops, validating block logic exactly. On a TPU backend they
compile to Mosaic (`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

from repro import kernels
from repro.kernels.pq_score import pq_score_pallas, pq_score_window_pallas
from repro.kernels.vq_assign import vq_assign_pallas
from repro.kernels.soar_assign import soar_assign_pallas


def pq_score(luts, codes, **kw):
    """Batched PQ LUT scoring: (nq, m, 16) × (n, m) → (nq, n)."""
    return pq_score_pallas(luts, codes, interpret=kernels.interpret_mode(),
                           **kw)


def pq_score_window(luts, table, parts, **kw):
    """Per-query candidate-window scoring from the packed code table in
    place: (nq, m, 16) × table (c, R, W·128) × probes (nq, t) →
    (nq, t, R·128) — the candidate-local search_jit hot path."""
    return pq_score_window_pallas(luts, table, parts,
                                  interpret=kernels.interpret_mode(), **kw)


def vq_assign(X, C, **kw):
    """Fused nearest-centroid: (n, d) × (c, d) → (idx (n,), sqdist (n,))."""
    return vq_assign_pallas(X, C, interpret=kernels.interpret_mode(), **kw)


def soar_assign(X, rhat, primary, C, lam: float = 1.0, **kw):
    """Fused SOAR spilled assignment → (idx (n,), loss (n,))."""
    return soar_assign_pallas(X, rhat, primary, C, lam=lam,
                              interpret=kernels.interpret_mode(), **kw)
