"""Chip smoke of the SOAR build-and-serve path.

    python chip_smoke.py              # one chip: the per-shard deployment
    python chip_smoke.py --chips 4    # four chips: replica fan-out and the
                                      # 4-shard PQ search, nothing else

One process drives the system through the entry points a user calls. A
corpus is made from `--seed` (`repro.data.vectors.glove_like`), the index
is built on the chip with `AnnEngine.build`, and queries are served through
`ServingFrontend`: single-query requests from client threads, one bulk
batch, one tenant-filtered request, and an `add` and a `remove` barrier
with searches after each. Every answer is compared with a brute-force
inner-product reference in host NumPy (f32) over the same vectors, filter
and tombstones, and must reach the recall@10 floor stated below.

The default size is the chip's share of a big-ann-benchmarks d=100 corpus:
1M vectors in 2,500 partitions (about 400 points each, as in the paper),
25 PQ subspaces, 1,024 queries.

Earlier lines of stdout report build and compile seconds, recall, peak
device bytes, the route each kernel dispatcher took and whether each
Pallas kernel is in the compiled programs as a `tpu_custom_call`. The
last line is one JSON object, {"ok": true, "device": {...}}. Any failed
check, or a backend other than TPU, exits non-zero without that line:
the script never falls back to the CPU.

The persistent compile cache is JAX_COMPILATION_CACHE_DIR when set, else
<checkout>/.jax_cache, so a second run in the same checkout compiles less.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import logging
import os
import re
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

RECALL_FLOOR = 0.9
K = 10
KERNELS = ("pq_score_window_pallas", "pq_score_pallas", "lloyd_sweep_pallas",
           "vq_assign_pallas", "soar_assign_pallas", "tree_route_pallas")


@dataclasses.dataclass(frozen=True)
class Size:
    n: int               # base vectors (all shards together)
    d: int
    c: int               # partitions of the one-chip index
    m: int               # PQ subspaces
    nq: int              # queries of the bulk batch
    top_t: int           # partitions probed per query
    n_single: int        # single-query frontend requests
    clients: int         # client threads sending them
    n_sub: int           # queries of the tenant / add / remove phases
    n_add: int           # vectors of the add barrier
    train_sample: int = 131_072
    shard_size: int = 65_536


FULL = Size(n=1_000_000, d=100, c=2500, m=25, nq=1024, top_t=16,
            n_single=256, clients=8, n_sub=256, n_add=1024)


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    log(f"check {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------- exact reference
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


# ----------------------------------------------------------- introspection
def kernels_in(compiled_text: str) -> set:
    """Pallas kernels present as tpu_custom_call in a compiled program."""
    found = set()
    for line in compiled_text.splitlines():
        if "tpu_custom_call" in line:
            found |= {k for k in KERNELS
                      if re.search(rf"\b{k}\b", line)}
    return found


class Routes(logging.Handler):
    """Counts the route records the kernel dispatchers log
    (`repro.kernels.note_route`) while the context is open."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.counts = collections.Counter()
        self._logger = logging.getLogger("repro.kernels")

    def emit(self, record):
        self.counts[(record.kernel, record.route)] += 1

    def __enter__(self):
        self._level = self._logger.level
        self._logger.setLevel(logging.INFO)
        self._logger.addHandler(self)
        return self

    def __exit__(self, *exc):
        self._logger.removeHandler(self)
        self._logger.setLevel(self._level)

    def report(self) -> None:
        for (kernel, route), n in sorted(self.counts.items()):
            log(f"route {kernel}: {route} x{n}")


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def corpus(size: Size, seed: int):
    from repro.data.vectors import glove_like
    t0 = time.perf_counter()
    ds = glove_like(n=size.n + size.n_add, d=size.d, nq=size.nq, seed=seed)
    X, X_add, Q = ds.X[:size.n], ds.X[size.n:], ds.Q
    log(f"corpus n={size.n} d={size.d} nq={size.nq} add={size.n_add} "
        f"seconds={time.perf_counter() - t0:.3f}")
    return X, X_add, Q


def build_engine(size: Size, X, seed: int):
    import jax
    from repro.serve.engine import AnnEngine
    t0 = time.perf_counter()
    engine = AnnEngine.build(jax.random.PRNGKey(seed), X, size.c,
                             spill_mode="soar", lam=1.0,
                             pq_subspaces=size.m, top_t=size.top_t,
                             train_sample=size.train_sample,
                             shard_size=size.shard_size)
    engine.index.pack()
    build_s = time.perf_counter() - t0
    log(f"build_seconds {build_s:.3f} (c={size.c} m={size.m} "
        f"cap={engine.index.part_ids.shape[1]} top_t={size.top_t})")
    return engine, build_s


def compile_programs(engine, size: Size) -> dict:
    """AOT-compile the serving step (one bq-query tile) and the build
    kernels at the shapes the build used; report seconds and kernels."""
    import jax
    import jax.numpy as jnp
    from repro.core.search import search_jit_batched
    from repro.kernels.lloyd import lloyd_sweep_pallas
    from repro.kernels.soar_assign import soar_assign_pallas
    from repro.kernels.vq_assign import vq_assign_pallas
    from repro import kernels

    f32 = jnp.float32
    idx = engine.index
    packed = idx.pack()
    t0 = time.perf_counter()
    serve = search_jit_batched.lower(
        packed, jax.ShapeDtypeStruct((engine.bq, size.d), f32),
        top_t=size.top_t, final_k=K, rerank_budget=engine.rerank_budget,
        bq=engine.bq, multiplicity=1 + max(idx.n_spills, 1),
        filter=None, escalate=True).compile()
    compile_s = time.perf_counter() - t0
    found = {"serve": kernels_in(serve.as_text())}
    interp = kernels.interpret_mode()
    ns = min(size.n, size.train_sample)
    nb = min(size.n, size.shard_size)
    S = jax.ShapeDtypeStruct
    build_progs = {
        "lloyd": lloyd_sweep_pallas.lower(
            S((ns, size.d), f32), S((size.c, size.d), f32), c=size.c,
            interpret=interp),
        "vq_assign": vq_assign_pallas.lower(
            S((nb, size.d), f32), S((size.c, size.d), f32),
            interpret=interp),
        "soar_assign": soar_assign_pallas.lower(
            S((nb, size.d), f32), S((nb, size.d), f32),
            S((nb,), jnp.int32), S((size.c, size.d), f32),
            interpret=interp),
    }
    for name, low in build_progs.items():
        found[name] = kernels_in(low.compile().as_text())
    log(f"compile_seconds serve_step {compile_s:.3f}")
    for prog, ks in found.items():
        for k in KERNELS:
            if k in ks:
                log(f"kernel {k}: tpu_custom_call in {prog}")
    return {"compile_s": compile_s, "kernels": found}


def frontend_phases(engine, size: Size, X, X_add, Q) -> dict:
    """Serve through ServingFrontend and check every answer."""
    from repro.serve.api import SearchParams
    from repro.serve.frontend import ServingFrontend

    out = {}
    fe = ServingFrontend(engine)
    try:
        # --- single-query requests from client threads
        ref = exact_topk(X, Q[:size.n_single])
        results = [None] * size.n_single
        errors = []

        def client(ci):
            try:
                for i in range(ci, size.n_single, size.clients):
                    results[i] = fe.search(Q[i:i + 1])
            except Exception as e:          # surfaced below, never hidden
                errors.append(repr(e))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(size.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        single_s = time.perf_counter() - t0
        check(not errors, f"single-query clients raised {errors[:3]}")
        ids = np.concatenate([r.ids for r in results])
        rec = recall_at_k(ids, ref)
        batches = [r.batch_size for r in results]
        log(f"single_requests {size.n_single} clients={size.clients} "
            f"seconds={single_s:.3f} max_batch={max(batches)} "
            f"coalesced={fe.stats['coalesced']}")
        log(f"recall@10 single {rec:.4f}")
        check(rec >= RECALL_FLOOR, f"single-query recall@10 {rec:.4f} "
              f">= {RECALL_FLOOR}")
        out["recall_single"] = rec

        # coalesced == solo at the same epoch (bitwise)
        epoch = engine.index._alive_epoch
        same = 0
        for i, r in enumerate(results):
            s = engine.search_request(Q[i:i + 1], SearchParams())
            same += (r.epoch == epoch and s.epoch == epoch
                     and np.array_equal(r.ids, s.ids)
                     and np.array_equal(r.scores, s.scores))
        log(f"coalesced_equals_solo {same}/{size.n_single}")
        check(same == size.n_single, "coalesced results bitwise equal to "
              "solo engine calls at the same epoch")

        # --- one bulk batch straight through the engine
        ref_bulk = exact_topk(X, Q)
        t0 = time.perf_counter()
        b_ids, _ = engine.search(Q)
        bulk_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        b_ids2, _ = engine.search(Q)
        bulk_warm = time.perf_counter() - t0
        rec = recall_at_k(b_ids, ref_bulk)
        log(f"bulk nq={Q.shape[0]} first_call_seconds={bulk_first:.3f} "
            f"warm_seconds={bulk_warm:.3f}")
        log(f"recall@10 bulk {rec:.4f}")
        check(rec >= RECALL_FLOOR, f"bulk recall@10 {rec:.4f} >= "
              f"{RECALL_FLOOR}")
        check(np.array_equal(b_ids, b_ids2), "bulk search is deterministic")
        out["recall_bulk"] = rec

        # --- one filtered (tenant) request
        nsub = size.n_sub
        rng = np.random.default_rng(0)
        tenant = rng.random(size.n) < 0.5
        fe.register_tenant("t0", mask=tenant)
        r = fe.search(Q[:nsub], SearchParams(tenant="t0"))
        got = r.ids
        check(bool(np.all(tenant[got[got >= 0]])),
              "tenant results hold only tenant ids")
        rec = recall_at_k(got, exact_topk(X, Q[:nsub], keep=tenant))
        log(f"recall@10 tenant {rec:.4f} (tenant holds {tenant.mean():.3f}"
            f" of ids, escalated={r.escalated})")
        check(rec >= RECALL_FLOOR, f"tenant recall@10 {rec:.4f} >= "
              f"{RECALL_FLOOR}")
        out["recall_tenant"] = rec

        # --- add barrier, then search
        new_ids = fe.add(X_add)
        check(np.array_equal(new_ids, np.arange(size.n, size.n + size.n_add)),
              "add returned the next ids")
        X_all = np.concatenate([X, X_add])
        self_hit = fe.search(X_add[:nsub]).ids[:, 0]
        hit = float(np.mean(self_hit == new_ids[:nsub]))
        log(f"added {size.n_add} vectors; self top-1 hit rate {hit:.4f}")
        check(hit >= 0.99, "added vectors are found as their own top-1")
        r = fe.search(Q[:nsub])
        rec = recall_at_k(r.ids, exact_topk(X_all, Q[:nsub]))
        log(f"recall@10 after_add {rec:.4f} (epoch {r.epoch})")
        check(rec >= RECALL_FLOOR, f"post-add recall@10 {rec:.4f} >= "
              f"{RECALL_FLOOR}")
        out["recall_after_add"] = rec

        # --- remove barrier: each query's exact top-1 and some added ids
        top1 = exact_topk(X_all, Q[:nsub], k=1)[:, 0]
        gone = np.unique(np.concatenate([top1, new_ids[:nsub // 4]]))
        n_rm = fe.remove(gone)
        check(n_rm == gone.size, f"remove tombstoned {n_rm}/{gone.size}")
        alive = np.ones(X_all.shape[0], bool)
        alive[gone] = False
        r = fe.search(Q[:nsub])
        check(not np.isin(r.ids, gone).any(), "no removed id is served")
        rec = recall_at_k(r.ids, exact_topk(X_all, Q[:nsub], keep=alive))
        log(f"removed {n_rm}; recall@10 after_remove {rec:.4f} "
            f"(epoch {r.epoch})")
        check(rec >= RECALL_FLOOR, f"post-remove recall@10 {rec:.4f} >= "
              f"{RECALL_FLOOR}")
        out["recall_after_remove"] = rec
    finally:
        fe.close()
    st = fe.stats
    log("frontend_stats " + json.dumps(st, sort_keys=True))
    for key in ("failures", "retries", "degraded", "rejected", "shed",
                "expired"):
        check(st[key] == 0, f"frontend {key} == 0")
    return out


def one_chip(size: Size, seed: int, require_kernels: bool) -> dict:
    """The one-chip deployment: build, compile, serve, check."""
    import jax
    from repro import kernels
    from repro.kernels.pq_score import _resolve_interpret

    interp = kernels.interpret_mode()
    log(f"pallas interpret_mode={interp} "
        f"pq_score_resolve={_resolve_interpret(None)}")
    X, X_add, Q = corpus(size, seed)
    with Routes() as routes:
        engine, build_s = build_engine(size, X, seed)
        routes.report()
        comp = compile_programs(engine, size)
        out = frontend_phases(engine, size, X, X_add, Q)
    routes.report()
    dev = jax.devices()[0]
    log(f"peak_bytes_in_use {peak_bytes(dev)}")
    if require_kernels:
        check(not interp and not _resolve_interpret(None),
              "Pallas kernels resolve to compiled Mosaic")
        check("pq_score_window_pallas" in comp["kernels"]["serve"],
              "window-scoring kernel is a tpu_custom_call in the serving "
              "step")
        check(("pq_score_window", "xla") not in routes.counts,
              "no serving call took the XLA window-scoring route")
    return {"build_s": build_s, "routes": dict(routes.counts), **comp,
            **out}


# ------------------------------------------------------------ four chips
def four_chips(size: Size, seed: int, n_dev: int = 4) -> dict:
    """Replica fan-out through the frontend, compared with the one-chip
    engine; and a 4-shard PQ search, one shard per chip, compared with
    the exact reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.core.distributed import (build_sharded_ivf_pq,
                                        make_distributed_search_pq,
                                        sharded_ivf_pq_pspecs)
    from repro.launch.mesh import make_mesh, to_shardings
    from repro.serve.api import SearchParams
    from repro.serve.frontend import ServingFrontend
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    check(len(devs) >= n_dev, f"{n_dev} devices visible (have {len(devs)})")
    X, _, Q = corpus(dataclasses.replace(size, n_add=0), seed)
    ref = exact_topk(X, Q)

    # --- replica fan-out vs the one-chip engine
    engine, _ = build_engine(size, X, seed)
    local = [engine.search_request(Q[i:i + engine.bq], SearchParams())
             for i in range(0, Q.shape[0], engine.bq)]
    fe = ServingFrontend(engine, policy="auto")
    try:
        futs = [fe.submit(Q[i:i + engine.bq])
                for i in range(0, Q.shape[0], engine.bq)]
        rep = [f.result() for f in futs]
    finally:
        fe.close()
    st = fe.stats
    log("frontend_stats " + json.dumps(st, sort_keys=True))
    check(st["replica_dispatches"] > 0, "frontend fanned out over replicas")
    for key in ("failures", "retries", "degraded"):
        check(st[key] == 0, f"frontend {key} == 0")
    rep_ids = np.concatenate([r.ids for r in rep])
    loc_ids = np.concatenate([r.ids for r in local])
    same = sum(np.array_equal(a.ids, b.ids)
               and np.array_equal(a.scores, b.scores)
               for a, b in zip(rep, local))
    rec_rep = recall_at_k(rep_ids, ref)
    rec_loc = recall_at_k(loc_ids, ref)
    log(f"replica over {len(devs)} devices: recall@10 {rec_rep:.4f}; "
        f"one-chip engine recall@10 {rec_loc:.4f}; bitwise-equal "
        f"dispatches {same}/{len(rep)}; equal id rows "
        f"{int((rep_ids == loc_ids).all(1).sum())}/{rep_ids.shape[0]}")
    check(same == len(rep), "replica results bitwise equal to the one-chip "
          "engine")
    check(rec_rep >= RECALL_FLOOR, f"replica recall@10 {rec_rep:.4f}")
    del engine

    # --- 4-shard PQ search, one shard per chip
    t0 = time.perf_counter()
    shards = build_sharded_ivf_pq(jax.random.PRNGKey(seed), X, n_dev,
                                  size.c // n_dev, size.m)
    log(f"shard_build_seconds {time.perf_counter() - t0:.3f} "
        f"(shards={n_dev} c_per_shard={size.c // n_dev} "
        f"pmax={shards.part_ids.shape[2]})")
    mesh = make_mesh((n_dev,), ("data",), devices=devs[:n_dev])
    spec = sharded_ivf_pq_pspecs(("data",))
    shards = jax.device_put(shards, to_shardings(mesh, spec))
    search = jax.jit(make_distributed_search_pq(
        mesh, ("data",), top_t=size.top_t, final_k=K, rerank_k=256,
        q_chunk=min(128, Q.shape[0])))
    Qd = jax.device_put(jnp.asarray(Q), NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    ids, _ = search(shards, Qd)
    ids = np.asarray(ids)
    log(f"sharded_search first_call_seconds {time.perf_counter() - t0:.3f}")
    rec = recall_at_k(ids, ref)
    log(f"recall@10 sharded {rec:.4f}")
    check(rec >= RECALL_FLOOR, f"4-shard recall@10 {rec:.4f} >= "
          f"{RECALL_FLOOR}")
    for i, d in enumerate(devs[:n_dev]):
        log(f"peak_bytes_in_use device{i} {peak_bytes(d)}")
    return {"recall_replica": rec_rep, "recall_sharded": rec}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    from repro.utils import enable_compile_cache
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    log(f"compile_cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(FULL, args.seed)
        else:
            one_chip(FULL, args.seed, require_kernels=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total_seconds {time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
