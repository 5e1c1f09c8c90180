"""Shared benchmark fixtures: dataset + the three index variants, built once
per process and cached."""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

from repro.core import build_ivf, true_neighbors
from repro.data.vectors import glove_like
from repro.utils import enable_compile_cache

enable_compile_cache()

# benchmark scale (1-core CPU container): see DESIGN.md §7 — relative claims
# at 100k–200k scale; the paper's billion-scale gains extrapolate per Fig 10.
N = 100_000
D = 100
NQ = 400
K = 100
C = 500          # 200 points/partition
LAM = 1.0


@functools.lru_cache(maxsize=None)
def dataset():
    return glove_like(n=N, d=D, nq=NQ)


@functools.lru_cache(maxsize=None)
def neighbors():
    ds = dataset()
    return true_neighbors(ds.X, ds.Q, k=K)


@functools.lru_cache(maxsize=None)
def index(mode: str, lam: float = LAM, pq: int = 0, n: int = N, c: int = C):
    ds = dataset() if n == N else glove_like(n=n, d=D, nq=NQ)
    return build_ivf(jax.random.PRNGKey(1), ds.X[:n], c, spill_mode=mode,
                     lam=lam, pq_subspaces=pq, train_iters=8)


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.us = (time.perf_counter() - self.t0) * 1e6


# every emit()ed row is also collected here so run.py can write the
# consolidated BENCH_search.json artifact (perf trajectory across PRs)
ROWS = []


def emit(name: str, us: float, derived):
    ROWS.append({"name": name, "us_per_call": round(float(us), 1),
                 "derived": str(derived)})
    print(f"{name},{us:.1f},{derived}")


def write_rows(path: str, rows, **meta):
    """Write a standalone bench artifact (the per-bench JSON files the CI
    regression gate consumes; run.py separately writes the consolidated
    artifact from ROWS)."""
    import json
    import platform

    import jax

    payload = {"unit": "us_per_call", "backend": jax.default_backend(),
               "platform": platform.platform(), **meta, "rows": list(rows)}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
