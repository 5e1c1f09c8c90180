"""Build-path overhaul pins (ISSUE 4).

Exactness matrix (DESIGN.md §3.8): the fused fast paths must be
bitwise-identical to their unfused references wherever the arithmetic is
merely reassociated —

- fused `lloyd_sweep` == two-pass `lloyd_step` at matched reduction order
  (single chunk); chunked sweeps change only f32 accumulation grouping
  (assignments/counts stay exact);
- hand-batched `lloyd_sweep_batched` == per-slice `lloyd_sweep`;
- batched `train_pq` == sequential per-subspace `train_pq_sequential` at
  the same keys (including the per-subspace early-stop schedule);
- fused one-pass residual encode == chunked host-loop reference;
- counting-sort CSR == stable argsort;
- delta `pack()` == full re-pack.

The flagged approximations (k-means|| init, mini-batch Lloyd) are
recall-parity tested, not bitwise.

Structural pin: no Lloyd iteration materializes an (n, c) or (n,)
intermediate outside a chunk tile (jaxpr-level, like the search-side pin
in test_search_pipeline.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_ivf, pack_ivf, search_jit, true_neighbors
from repro.core.ivf import _csr_from_assignments, _stable_counting_sort, finalize_ivf
from repro.core.kmeans import lloyd_step, train_kmeans
from repro.core.mutable import MutableIVF
from repro.data.vectors import make_manifold
from repro.kernels.lloyd import (_grouped_argmin, lloyd_sweep,
                                 lloyd_sweep_batched, lloyd_sweep_pallas)
from repro.kernels.pq_score import code_table_codes
from repro.quant.pq import train_pq, train_pq_sequential


@pytest.fixture(scope="module")
def ds():
    return make_manifold(jax.random.PRNGKey(0), n=6000, d=24, nq=32,
                         intrinsic_dim=6)


# --------------------------------------------------------------- argmin
def test_grouped_argmin_exact_with_ties():
    k = jax.random.PRNGKey(3)
    dm = jax.random.normal(k, (257, 48))
    # inject exact duplicates of row minima at random other columns so the
    # first-tie rule is actually exercised
    rows = jnp.arange(257)
    mins = jnp.min(dm, -1)
    dup_col = jax.random.randint(jax.random.PRNGKey(4), (257,), 0, 48)
    dm = dm.at[rows, dup_col].set(mins)
    idx, mv = _grouped_argmin(dm)
    assert np.array_equal(np.asarray(idx), np.asarray(jnp.argmin(dm, -1)))
    assert np.array_equal(np.asarray(mv), np.asarray(jnp.min(dm, -1)))


# ---------------------------------------------------------- fused Lloyd
def test_lloyd_sweep_single_chunk_bitwise_vs_lloyd_step():
    """At chunk == n the fused sweep reduces in exactly the reference
    order: new centroids AND distortion must match lloyd_step bitwise."""
    n, d, c = 4096, 32, 37           # c deliberately NOT a multiple of 8
    X = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    C = jax.random.normal(jax.random.PRNGKey(2), (c, d))
    ref_C, ref_assign, ref_dist = lloyd_step(X, C, c, chunk=n)
    new_C, counts, dist = lloyd_sweep(X, C, c, chunk=n)
    assert np.array_equal(np.asarray(ref_C), np.asarray(new_C))
    assert float(ref_dist) == float(dist)
    ref_counts = np.bincount(np.asarray(ref_assign), minlength=c)
    assert np.array_equal(ref_counts, np.asarray(counts).astype(np.int64))


def test_lloyd_sweep_chunked_counts_exact_sums_close():
    """Chunk boundaries change only the f32 accumulation grouping: the
    assignments (hence counts) stay exact, centroids agree to rounding."""
    n, d, c = 5000, 16, 24
    X = jax.random.normal(jax.random.PRNGKey(5), (n, d))
    C = jax.random.normal(jax.random.PRNGKey(6), (c, d))
    C1, counts1, d1 = lloyd_sweep(X, C, c, chunk=n)
    C2, counts2, d2 = lloyd_sweep(X, C, c, chunk=512)
    assert np.array_equal(np.asarray(counts1), np.asarray(counts2))
    np.testing.assert_allclose(np.asarray(C1), np.asarray(C2),
                               rtol=1e-5, atol=1e-5)
    assert abs(float(d1) - float(d2)) < 1e-4


def test_lloyd_sweep_batched_bitwise_per_slice():
    m, n, s, k = 7, 3000, 4, 16
    Xb = jax.random.normal(jax.random.PRNGKey(7), (m, n, s))
    Cb = jax.random.normal(jax.random.PRNGKey(8), (m, k, s))
    newC, counts, loss = lloyd_sweep_batched(Xb, Cb, k, chunk=1024)
    for j in range(m):
        c1, n1, l1 = lloyd_sweep(Xb[j], Cb[j], k, chunk=1024)
        assert np.array_equal(np.asarray(c1), np.asarray(newC[j]))
        assert np.array_equal(np.asarray(n1), np.asarray(counts[j]))
        assert float(l1) == float(loss[j])


def test_lloyd_sweep_pallas_matches_scan():
    """Interpret-mode Pallas route vs the scan route: identical counts,
    centroids to accumulation-order rounding (MXU one-hot vs scatter)."""
    n, d, c = 2048, 16, 32
    X = jax.random.normal(jax.random.PRNGKey(9), (n, d))
    C = jax.random.normal(jax.random.PRNGKey(10), (c, d))
    C1, counts1, d1 = lloyd_sweep(X, C, c, chunk=n)
    C2, counts2, d2 = lloyd_sweep_pallas(X, C, c, bn=512, interpret=True)
    assert np.array_equal(np.asarray(counts1), np.asarray(counts2))
    np.testing.assert_allclose(np.asarray(C1), np.asarray(C2),
                               rtol=1e-5, atol=1e-5)
    assert abs(float(d1) - float(d2)) < 1e-3


def test_no_lloyd_iteration_n_sized_intermediates():
    """ISSUE 4 acceptance: a Lloyd iteration materializes nothing
    (n, c)-shaped and no second-pass (n,) vector — every per-point
    intermediate lives inside a chunk tile."""
    from repro.analysis import jaxpr_shapes as _jaxpr_shapes
    n, d, c, chunk = 40_000, 32, 64, 4096
    X = jnp.zeros((n, d))
    C = jnp.zeros((c, d))
    closed = jax.make_jaxpr(
        lambda X, C: lloyd_sweep(X, C, c, chunk=chunk))(X, C)
    shapes = _jaxpr_shapes(closed.jaxpr)
    bad = [s for s in shapes
           if (len(s) == 1 and s[0] >= n)                 # (n,) second pass
           or (len(s) >= 2 and s[0] >= n and s[1] >= c)   # (n, c) dense
           or int(np.prod(s, dtype=np.int64)) >= n * c]
    assert not bad, f"n-sized Lloyd intermediates: {bad}"


def test_train_kmeans_final_assign_skip():
    X = jax.random.normal(jax.random.PRNGKey(11), (2000, 8))
    full = train_kmeans(jax.random.PRNGKey(12), X, 16, iters=4)
    skip = train_kmeans(jax.random.PRNGKey(12), X, 16, iters=4,
                        final_assign=False)
    assert np.array_equal(np.asarray(full.centroids),
                          np.asarray(skip.centroids))
    assert skip.assignments is None


# ------------------------------------------------------------ batched PQ
def test_batched_pq_bitwise_equals_sequential():
    """All m subspaces trained jointly == m sequential train_kmeans calls
    at the same keys, including per-subspace early-stop decisions."""
    X = jax.random.normal(jax.random.PRNGKey(13), (6000, 32))
    for iters in (3, 12):            # 12 iters: early stop kicks in per-m
        b = train_pq(jax.random.PRNGKey(14), X, 8, iters=iters)
        s = train_pq_sequential(jax.random.PRNGKey(14), X, 8, iters=iters)
        assert np.array_equal(np.asarray(b.centers), np.asarray(s.centers)), \
            f"batched != sequential at iters={iters}"


def test_batched_pq_bitwise_with_sampling():
    """The internal row-subsample paths (n > sample, and the per-subspace
    init subsample) must also coincide batched vs sequential."""
    X = jax.random.normal(jax.random.PRNGKey(15), (4000, 16))
    b = train_pq(jax.random.PRNGKey(16), X, 4, iters=4, sample=2048)
    s = train_pq_sequential(jax.random.PRNGKey(16), X, 4, iters=4,
                            sample=2048)
    assert np.array_equal(np.asarray(b.centers), np.asarray(s.centers))
    # init_sample < sample: exercises the vmapped per-subspace choice()
    b2 = train_pq(jax.random.PRNGKey(16), X, 4, iters=4, sample=2048,
                  init_sample=512)
    s2 = train_pq_sequential(jax.random.PRNGKey(16), X, 4, iters=4,
                             sample=2048, init_sample=512)
    assert np.array_equal(np.asarray(b2.centers), np.asarray(s2.centers))


def test_pq_encode_non_multiple_of_group_centers():
    """n_centers that doesn't divide the argmin group width must still
    encode (padded with never-chosen +inf) and match plain jnp.argmin."""
    from repro.quant.pq import PQCodebook, pq_encode
    X = jax.random.normal(jax.random.PRNGKey(30), (500, 16))
    cb = train_pq(jax.random.PRNGKey(31), X, 4, n_centers=12, iters=3)
    codes = np.asarray(pq_encode(cb, X))
    assert codes.max() < 12
    Xs = X.reshape(500, 4, 4)
    cn = jnp.sum(cb.centers * cb.centers, -1)
    dm = cn[None] - 2.0 * jnp.einsum("bms,mks->bmk", Xs, cb.centers)
    assert np.array_equal(codes, np.asarray(jnp.argmin(dm, -1)))


# --------------------------------------------------- fused residual encode
def test_fused_encode_bitwise_equals_chunked(ds):
    X = np.asarray(ds.X[:3000], np.float32)
    C = np.asarray(train_kmeans(jax.random.PRNGKey(17), X, 16, iters=3,
                                final_assign=False).centroids)
    rng = np.random.default_rng(0)
    assignments = np.stack([rng.integers(0, 16, 3000),
                            rng.integers(0, 16, 3000)], axis=1).astype(np.int32)
    kf = jax.random.PRNGKey(18)
    fused = finalize_ivf(kf, X, C, assignments, pq_subspaces=8,
                         encode_chunk=512, fused_encode=True)
    ref = finalize_ivf(kf, X, C, assignments, pq_subspaces=8,
                       encode_chunk=512, fused_encode=False)
    assert np.array_equal(fused.codes, ref.codes)
    assert np.array_equal(fused.point_ids, ref.point_ids)
    assert np.array_equal(fused.starts, ref.starts)
    np.testing.assert_array_equal(np.asarray(fused.pq.centers),
                                  np.asarray(ref.pq.centers))
    # encode_chunk is a pure tiling knob: codes are per-row exact
    other = finalize_ivf(kf, X, C, assignments, pq_subspaces=8,
                         encode_chunk=4096, fused_encode=True)
    assert np.array_equal(fused.codes, other.codes)


# ------------------------------------------------------------ CSR sort
def test_counting_sort_equals_stable_argsort():
    # without scipy the fallback IS argsort and this pin is vacuous —
    # scipy ships in requirements-dev.txt precisely so CI tests the
    # counting-sort branch; fail loudly if the environment lost it
    pytest.importorskip("scipy", reason="counting-sort fast path needs "
                        "scipy (requirements-dev.txt)")
    rng = np.random.default_rng(1)
    for n, c in ((1, 1), (100, 7), (50_000, 513)):
        keys = rng.integers(0, c, n).astype(np.int32)
        assert np.array_equal(_stable_counting_sort(keys, c),
                              np.argsort(keys, kind="stable"))
    assert _stable_counting_sort(np.empty(0, np.int32), 5).size == 0


def test_csr_from_assignments_order():
    A = np.array([[2, 0], [1, 2], [2, 1], [0, 1]], np.int32)
    starts, point_ids, order = _csr_from_assignments(A, 3)
    assert starts.tolist() == [0, 2, 5, 8]
    # partition 2 receives rows 0, 1, 2 in stable flat order
    assert point_ids[5:8].tolist() == [0, 1, 2]
    assert point_ids[0:2].tolist() == [0, 3]
    assert point_ids[2:5].tolist() == [1, 2, 3]


# ------------------------------------------------- flagged approximations
def _recall_of(idx, Q, tn):
    ids, _ = search_jit(pack_ivf(idx), jnp.asarray(Q), top_t=8, final_k=10,
                        rerank_budget=128)
    return float((np.asarray(ids)[:, :, None] == tn[:, None, :10])
                 .any(-1).mean())


def test_kmeans_parallel_and_minibatch_recall_parity(ds):
    tn = true_neighbors(ds.X, ds.Q, k=10)
    base = _recall_of(build_ivf(jax.random.PRNGKey(20), ds.X, 24,
                                pq_subspaces=8, train_iters=6), ds.Q, tn)
    par = _recall_of(build_ivf(jax.random.PRNGKey(20), ds.X, 24,
                               pq_subspaces=8, train_iters=6,
                               init="parallel"), ds.Q, tn)
    mb = _recall_of(build_ivf(jax.random.PRNGKey(20), ds.X, 24,
                              pq_subspaces=8, train_iters=12,
                              batch_size=1024), ds.Q, tn)
    assert par >= base - 0.03, (par, base)
    assert mb >= base - 0.05, (mb, base)


# ------------------------------------------------------------ delta pack
def test_delta_pack_identical_to_full_repack(ds):
    mut = MutableIVF.build(jax.random.PRNGKey(21), ds.X[:4000], 16,
                           spill_mode="soar", pq_subspaces=8, train_iters=3)
    mut.pack()                                   # seed cached snapshot
    mut.add(ds.X[4000:4800])
    mut.remove(np.arange(100, 300))
    delta = mut.pack()                           # delta-updated snapshot
    assert mut._dirty_parts is not None and not mut._dirty_parts.any()
    mut._invalidate()
    full = mut.pack()                            # full re-pack, same state
    assert np.array_equal(np.asarray(delta.part_ids),
                          np.asarray(full.part_ids))
    assert np.array_equal(np.asarray(delta.part_codes),
                          np.asarray(full.part_codes))
    # both hold the code table of the host codes, at capacity width
    assert np.array_equal(
        code_table_codes(np.asarray(delta.part_codes), mut.pq.centers.shape[0],
                         mut.part_ids.shape[1]), mut.part_codes)
    if delta.part_codes2 is not None:
        assert np.array_equal(np.asarray(delta.part_codes2),
                              np.asarray(full.part_codes2))
    assert np.array_equal(np.asarray(delta.sizes), np.asarray(full.sizes))
    assert np.array_equal(np.asarray(delta.rerank)[:mut.n_total],
                          np.asarray(full.rerank)[:mut.n_total])


def test_delta_pack_search_matches_after_mutation_burst(ds):
    """Serving loop shape: interleaved add/remove/pack/search must equal a
    from-scratch pack at every step (the cadence the bench times)."""
    mut = MutableIVF.build(jax.random.PRNGKey(22), ds.X[:3000], 16,
                           spill_mode="soar", pq_subspaces=8, train_iters=3)
    Q = jnp.asarray(ds.Q[:8])
    kw = dict(top_t=6, final_k=5, rerank_budget=64)
    for step in range(4):
        lo = 3000 + step * 200
        ids_new = mut.add(ds.X[lo:lo + 200])
        mut.remove(ids_new[::3])
        di, dv = search_jit(mut.pack(), Q, **kw)
        mut._invalidate()
        fi, fv = search_jit(mut.pack(), Q, **kw)
        assert np.array_equal(np.asarray(di), np.asarray(fi))
        np.testing.assert_allclose(np.asarray(dv), np.asarray(fv),
                                   rtol=1e-6, atol=1e-6)
