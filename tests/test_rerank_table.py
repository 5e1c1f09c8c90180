"""The lane-row rerank table (`PackedIVF.rerank`, DESIGN.md §3.6).

Every search path reads the table through `rerank_rows` /
`rerank_scores`. Each case here runs one path on the table and the same
path with every read replaced by a gather from the (n, d) rows, the
layout the table replaced: the ids must be equal and the scores equal to
float32 rounding. d covers vectors that never straddle a row boundary,
at multiples of 128 (128 in one lane-row, 256 in two), and three that do
(37, 100, 200, spanning 2, 2 and 3 lane-rows). A snapshot written with (n, d) row storage, as
format v1 always stored it, must load and serve the same answers.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import index_store
from repro.core import MutableIVF, build_ivf, pack_ivf
from repro.core import search
from repro.core.distributed import make_replicated_search
from repro.core.search import (LANES, rerank_rows, rerank_table,
                               rerank_table_rows, search_jit_batched)

N, NQ, C_PARTS = 1_001, 24, 12
DIMS = (37, 100, 128, 200, 256)
PQ_M = {37: 37, 100: 25, 128: 16, 200: 25, 256: 32}  # subspaces dividing d
KW = dict(top_t=4, final_k=10, rerank_budget=64, multiplicity=2)
BQ = 8
PATHS = ("accessor", "pq", "exact", "filtered", "delta", "replica")


@functools.lru_cache(maxsize=None)
def _data(d):
    rng = np.random.default_rng(d)
    X = rng.standard_normal((N + 431, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    Q = rng.standard_normal((NQ, d)).astype(np.float32)
    return X, Q


@functools.lru_cache(maxsize=None)
def _index(d, pq: bool):
    X, _ = _data(d)
    return build_ivf(jax.random.PRNGKey(d), X[:N], C_PARTS,
                     spill_mode="soar", pq_subspaces=PQ_M[d] if pq else 0,
                     train_iters=3)


def _row_search(packed, rows, Q, **kw):
    """`packed`'s search with every table read a gather of (n, d) rows.
    Run eagerly, so that no jit cache keeps the patched trace."""
    def row_scores(table, ids, q):
        return jnp.einsum("qwd,qd->qw", table[ids], q,
                          precision=search.EXACT)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(search, "rerank_scores", row_scores)
        return search._search_block(
            packed._replace(rerank=jnp.asarray(rows)), jnp.asarray(Q),
            KW["top_t"], KW["final_k"], KW["rerank_budget"],
            KW["multiplicity"], **kw)


def _assert_same(got, ref):
    gi, gv = map(np.asarray, got)
    ri, rv = map(np.asarray, ref)
    assert np.array_equal(gi, ri)
    np.testing.assert_allclose(gv, rv, rtol=0, atol=1e-6)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("d", DIMS)
def test_lane_row_table_matches_row_gather(d, path):
    X, Q = _data(d)
    jQ = jnp.asarray(Q)
    if path == "accessor":
        table = rerank_table(X)
        ids = np.random.default_rng(0).integers(0, X.shape[0], (7, 33))
        got, lane = map(np.asarray, rerank_rows(jnp.asarray(table),
                                                jnp.asarray(ids), d))
        vecs = np.take_along_axis(
            got, lane[..., None] + np.arange(d), axis=-1)
        assert np.array_equal(vecs, X[ids])
        back = rerank_table_rows(table, d)
        assert np.array_equal(back[:X.shape[0]], X)
        assert not back[X.shape[0]:].any()
        assert np.array_equal(rerank_table(back), table)
        return
    if path in ("pq", "exact", "filtered", "replica"):
        idx = _index(d, path != "exact")
        packed = pack_ivf(idx)
        rows = idx.rerank_f32
        kw = {}
        if path == "filtered":
            bits = np.random.default_rng(1).random(N) < 0.08
            kw = dict(filter=jnp.asarray(bits.astype(np.uint8)),
                      escalate=True)
        got = search_jit_batched(packed, jQ, bq=BQ, **KW, **kw)
        _assert_same(got, _row_search(packed, rows, Q, **kw))
        if path == "replica":
            mesh = jax.make_mesh((1,), ("r",))
            fan = make_replicated_search(mesh, ("r",), **KW)
            rep = jax.jit(fan)(packed, jQ)
            # one-replica fan-out IS the local pipeline, bitwise
            assert np.array_equal(np.asarray(rep[0]), np.asarray(got[0]))
            assert np.array_equal(np.asarray(rep[1]), np.asarray(got[1]))
        return
    # delta: vectors appended to a packed snapshot land at flat offsets,
    # the first of them mid-row whenever d is not a multiple of 128
    mut = MutableIVF.build(jax.random.PRNGKey(d), X[:N], C_PARTS,
                           spill_mode="soar", pq_subspaces=PQ_M[d],
                           train_iters=3)
    mut.pack()
    mut.add(X[N:N + 400])                 # grows capacity: a full pack
    mut.pack()
    start = mut.n_total
    assert d % LANES == 0 or start * d % LANES
    calls = []
    apply = MutableIVF._apply_pack_delta
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(MutableIVF, "_apply_pack_delta",
                   lambda self, p: calls.append(1) or apply(self, p))
        mut.add(X[N + 400:])
        packed = mut.pack()
    assert calls, "the second add must take the delta pack"
    assert np.array_equal(np.asarray(packed.rerank),
                          rerank_table(mut.rerank))
    got = search_jit_batched(packed, jQ, bq=BQ, **KW)
    _assert_same(got, _row_search(packed, mut.rerank, Q))


@pytest.mark.parametrize("d", DIMS)
def test_row_storage_snapshot_loads_and_serves(d, tmp_path):
    """A PackedIVF snapshot as format v1 stored it before the lane-row
    table, (n, d) rows under "rerank", loads into the table and serves
    the answers of a fresh pack; saving it again keeps that storage."""
    idx = _index(d, True)
    packed = pack_ivf(idx)
    kind, meta, arrays = index_store._state_of(packed, None)
    arrays["rerank"] = idx.rerank_f32               # exactly (n, d)
    index_store._write_state(str(tmp_path / "old"), kind, meta, arrays)
    loaded, _ = index_store.load_snapshot(str(tmp_path / "old"))
    assert np.array_equal(np.asarray(loaded.rerank),
                          np.asarray(packed.rerank))
    jQ = jnp.asarray(_data(d)[1])
    want = search_jit_batched(packed, jQ, bq=BQ, **KW)
    got = search_jit_batched(loaded, jQ, bq=BQ, **KW)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    index_store.save_snapshot(str(tmp_path / "new"), loaded)
    man = index_store.read_manifest(str(tmp_path / "new"))
    assert man["format_version"] == 1
    stored = next(e for e in man["arrays"] if e["name"] == "rerank")
    assert stored["shape"][1] == d and stored["shape"][0] >= N
    again, _ = index_store.load_snapshot(str(tmp_path / "new"))
    assert np.array_equal(np.asarray(again.rerank),
                          np.asarray(packed.rerank))
