"""Candidate-local `search_jit` vs the seed dense-dedup implementation.

ISSUE 2 acceptance microbench: the rewrite replaces the seed's per-query
dense (n,)-scatter dedup + full-database top_k with sort-based dedup over
the t·pmax candidate window. This bench times both pipelines on the same
packed index (n=100k, nq=256, CPU) and reports the speedup and recall@10 —
the win must be ≥ 3x with recall unchanged (±0.002).

ISSUE 5 adds filtered-serving rows (DESIGN.md §3.9): subset search at
selectivities {0.9, 0.5, 0.1, 0.01} on both engines, with recall measured
against FILTERED exact search (the only honest comparator — unfiltered
ground truth is unreachable by definition once a filter applies).

ISSUE 6 adds router rows (DESIGN.md §3.10): flat vs two-level tree probe
(`--routers` sweeps c ∈ {1k, 8k, 32k} at n=100k and asserts the acceptance
bar — tree ≥ 0.95x flat recall@10 at ≤ 1/4 probe FLOPs at c=32k).

    PYTHONPATH=src python -m benchmarks.bench_search_jit [--smoke|--routers]

`--smoke` runs a scaled-down shape (n=10k, nq=32) as a CI sanity check.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, emit
from repro.core import build_ivf, pack_ivf, search_jit, true_neighbors
from repro.core.search import PackedIVF, search_jit_batched
from repro.data.vectors import glove_like
from repro.kernels.pq_score import code_table_codes
from repro.quant.pq import pq_lut


@functools.partial(jax.jit, static_argnames=("top_t", "final_k", "rerank_budget"))
def seed_search_jit(packed: PackedIVF, Q, top_t: int, final_k: int,
                    rerank_budget: int = 256):
    """The seed implementation, kept verbatim as the baseline: per-query
    closure, dense (n,)-scatter dedup, top_k over the whole database.
    `packed.rerank` holds the (n, d) rows and `packed.part_codes` the
    (c, pmax, m) codes, as the seed's layout had them."""
    C, ids_all, codes_all = packed.centroids, packed.part_ids, packed.part_codes
    n = packed.rerank.shape[0]

    def one(q):
        sc = C @ q
        psc, parts = jax.lax.top_k(sc, top_t)
        ids = ids_all[parts].reshape(-1)
        valid = ids >= 0
        if codes_all is not None:
            lut = pq_lut(packed.pq, q)
            codes = codes_all[parts].reshape(ids.shape[0], -1)
            approx = jnp.sum(
                jnp.take_along_axis(lut[None], codes[:, :, None].astype(jnp.int32),
                                    axis=2)[:, :, 0], axis=-1)
            approx = approx + jnp.repeat(psc, ids_all.shape[1])
        else:
            approx = jnp.repeat(psc, ids_all.shape[1])
        approx = jnp.where(valid, approx, -jnp.inf)
        dense = jnp.full((n,), -jnp.inf, approx.dtype)
        dense = dense.at[jnp.where(valid, ids, n - 1)].max(
            jnp.where(valid, approx, -jnp.inf))
        bv, bi = jax.lax.top_k(dense, rerank_budget)
        exact = packed.rerank[bi] @ q
        exact = jnp.where(jnp.isfinite(bv), exact, -jnp.inf)
        fv, fpos = jax.lax.top_k(exact, final_k)
        return bi[fpos].astype(jnp.int32), fv

    return jax.vmap(one)(Q)


def _time(fn, reps: int = 5) -> float:
    """Best-of-reps wall time in µs (post-warmup; blocks on device results)."""
    best = float("inf")
    for _ in range(reps):
        with Timer() as t:
            r = fn()
            jax.block_until_ready(r)
        best = min(best, t.us)
    return best


def recall_at(ids: np.ndarray, tn: np.ndarray, k: int = 10) -> float:
    return float((ids[:, :k, None] == tn[:, None, :k]).any(-1).mean())


def _setup(n: int, nq: int, c: int, train_iters: int):
    """Shared dataset+index build for run()/run_filtered() — the same
    (seeded) build, so main() pays the multi-minute 100k Lloyd+PQ pass
    once, not per section."""
    ds = glove_like(n=n, d=100, nq=nq)
    idx = build_ivf(jax.random.PRNGKey(1), ds.X, c, spill_mode="soar",
                    pq_subspaces=25, train_iters=train_iters)
    return ds, idx, pack_ivf(idx)


def run(n: int, nq: int, c: int, top_t: int, rerank_budget: int,
        train_iters: int, label: str, prebuilt=None):
    ds, idx, packed = prebuilt or _setup(n, nq, c, train_iters)
    tn = true_neighbors(ds.X, ds.Q, k=10)
    Q = jnp.asarray(ds.Q)
    kw = dict(top_t=top_t, final_k=10, rerank_budget=rerank_budget)

    seed_packed = packed._replace(
        rerank=jnp.asarray(idx.rerank_f32),
        part_codes=jnp.asarray(code_table_codes(
            np.asarray(packed.part_codes), idx.codes.shape[1],
            packed.part_ids.shape[1]).astype(np.uint8)))
    new_ids, _ = search_jit(packed, Q, **kw)              # compile + warmup
    seed_ids, _ = seed_search_jit(seed_packed, Q, **kw)
    tiled_ids, _ = search_jit_batched(packed, Q, bq=64, **kw)
    t_new = _time(lambda: search_jit(packed, Q, **kw))
    t_seed = _time(lambda: seed_search_jit(seed_packed, Q, **kw))
    t_tiled = _time(lambda: search_jit_batched(packed, Q, bq=64, **kw))

    r_new = recall_at(np.asarray(new_ids), tn)
    r_seed = recall_at(np.asarray(seed_ids), tn)
    speedup = t_seed / t_new
    emit(f"search_jit_seed_{label}", t_seed / nq,
         f"recall@10={r_seed:.3f} n={n} nq={nq}")
    emit(f"search_jit_new_{label}", t_new / nq,
         f"recall@10={r_new:.3f} speedup={speedup:.2f}x "
         f"d_recall={r_new - r_seed:+.4f}")
    emit(f"search_jit_tiled_{label}", t_tiled / nq,
         f"recall@10={recall_at(np.asarray(tiled_ids), tn):.3f} bq=64")
    return speedup, r_new, r_seed


def run_filtered(n: int, nq: int, c: int, top_t: int, rerank_budget: int,
                 train_iters: int, label: str,
                 sels=(0.9, 0.5, 0.1, 0.01), prebuilt=None):
    """Filtered-serving rows: per selectivity, time the filtered jit path
    (with its fixed escalation pass) and the host engine (with its
    host-driven escalation loop); recall is vs FILTERED exact search."""
    from repro.core import search_numpy
    ds, idx, packed = prebuilt or _setup(n, nq, c, train_iters)
    Q = jnp.asarray(ds.Q)
    rng = np.random.default_rng(0)
    kw = dict(top_t=top_t, final_k=10, rerank_budget=rerank_budget)
    for sel in sels:
        mask = rng.random(n) < sel
        alive = np.flatnonzero(mask)
        tn = alive[np.asarray(true_neighbors(ds.X[alive], ds.Q, k=10))]
        f = jnp.asarray(mask.astype(np.uint8))
        jids, _ = search_jit(packed, Q, filter=f, **kw)      # compile+warm
        t_jit = _time(lambda: search_jit(packed, Q, filter=f, **kw))
        np_res = {}                 # ids from a TIMED call — a 4th untimed
                                    # run can be a near-full scan at s=0.01
        t_np = _time(lambda: np_res.setdefault(
            "ids", search_numpy(idx, ds.Q, filter_mask=mask, **kw)[0]),
            reps=3)
        nids = np_res["ids"]
        emit(f"search_jit_filtered_s{sel}_{label}", t_jit / nq,
             f"recall@10={recall_at(np.asarray(jids), tn):.3f} "
             f"selectivity={sel} (vs filtered exact)")
        emit(f"search_numpy_filtered_s{sel}_{label}", t_np / nq,
             f"recall@10={recall_at(nids, tn):.3f} "
             f"selectivity={sel} (vs filtered exact)")


def run_routers(n: int, nq: int, cs, rerank_budget: int, train_iters: int,
                label: str, check_acceptance_c: int = 0):
    """Router rows (ISSUE 6 / DESIGN.md §3.10): flat vs two-level tree
    probe at growing partition counts. top_t scales with c (a roughly
    constant candidate budget), so the probe stage's share of the work
    grows with c — the regime the TreeRouter exists for. Each tree row's
    derived string carries recall@10, the probe-FLOPs ratio vs flat, and
    the relative recall; the README recall-vs-probe-cost table and the CI
    regression gate read these rows."""
    from repro.core.router import FlatRouter, train_tree_router
    ds = glove_like(n=n, d=100, nq=nq)
    tn = true_neighbors(ds.X, ds.Q, k=10)
    Q = jnp.asarray(ds.Q)
    for c in cs:
        top_t = max(6, round(c / 200))
        idx = build_ivf(jax.random.PRNGKey(1), ds.X, c, spill_mode="soar",
                        pq_subspaces=25, train_iters=train_iters,
                        # exact k-means++ is c sequential picks — at 8k+
                        # centroids the k-means|| init is the only sane one
                        init="pp" if c <= 1024 else "parallel")
        packed = pack_ivf(idx)
        flat = FlatRouter(packed.centroids)
        S = max(2, int(round(c ** 0.5)))
        tree = train_tree_router(jax.random.PRNGKey(2), idx.centroids,
                                 n_super=S, t_route=max(2, -(-S // 8)))
        treed = tree.device()
        kw = dict(top_t=top_t, final_k=10, rerank_budget=rerank_budget)
        fids, _ = search_jit(packed, Q, router=flat, **kw)   # compile+warm
        tids, _ = search_jit(packed, Q, router=treed, **kw)
        t_flat = _time(lambda: search_jit(packed, Q, router=flat, **kw))
        t_tree = _time(lambda: search_jit(packed, Q, router=treed, **kw))
        rf = recall_at(np.asarray(fids), tn)
        rt = recall_at(np.asarray(tids), tn)
        ratio = tree.probe_flops(top_t) / flat.probe_flops(top_t)
        rel = rt / max(rf, 1e-9)
        emit(f"search_router_flat_c{c}_{label}", t_flat / nq,
             f"recall@10={rf:.3f} top_t={top_t} "
             f"probe_flops={flat.probe_flops(top_t)}")
        emit(f"search_router_tree_c{c}_{label}", t_tree / nq,
             f"recall@10={rt:.3f} top_t={top_t} n_super={S} "
             f"t_route={tree.t_route} flops_ratio={ratio:.3f} "
             f"rel_recall={rel:.3f}")
        if c == check_acceptance_c:
            assert rel >= 0.95, (
                f"tree recall {rt:.3f} < 0.95x flat {rf:.3f} at c={c}")
            assert ratio <= 0.25, (
                f"tree probe FLOPs {ratio:.2f}x flat exceeds 1/4 at c={c}")


def main(smoke: bool = False, routers: bool = False, out: str = ""):
    from benchmarks import common
    mark = len(common.ROWS)
    if smoke:
        pre = _setup(n=10_000, nq=32, c=64, train_iters=3)
        run(n=10_000, nq=32, c=64, top_t=6, rerank_budget=256,
            train_iters=3, label="smoke", prebuilt=pre)
        run_filtered(n=10_000, nq=32, c=64, top_t=6, rerank_budget=256,
                     train_iters=3, label="smoke", prebuilt=pre)
        run_routers(n=10_000, nq=32, cs=(256,), rerank_budget=256,
                    train_iters=3, label="smoke")
    elif routers:
        run_routers(n=100_000, nq=64, cs=(1024, 8192, 32768),
                    rerank_budget=300, train_iters=5, label="100k",
                    check_acceptance_c=32768)
    else:
        pre = _setup(n=100_000, nq=256, c=500, train_iters=8)
        speedup, r_new, r_seed = run(n=100_000, nq=256, c=500, top_t=10,
                                     rerank_budget=300, train_iters=8,
                                     label="100k", prebuilt=pre)
        assert speedup >= 3.0, f"speedup {speedup:.2f}x < 3x acceptance bar"
        assert abs(r_new - r_seed) <= 0.002, (r_new, r_seed)
        run_filtered(n=100_000, nq=256, c=500, top_t=10, rerank_budget=300,
                     train_iters=8, label="100k", prebuilt=pre)
    if out:
        from benchmarks.common import write_rows
        write_rows(out, common.ROWS[mark:], smoke=smoke)
        print(f"# wrote {len(common.ROWS) - mark} rows to {out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down CI shape (n=10k, nq=32)")
    ap.add_argument("--routers", action="store_true",
                    help="flat-vs-tree router sweep at c in {1k, 8k, 32k} "
                         "(n=100k; the ISSUE 6 acceptance run)")
    ap.add_argument("--out", default="",
                    help="standalone JSON artifact path (for the CI "
                         "regression gate)")
    main(**vars(ap.parse_args()))
