"""Per-kernel allclose validation vs pure-jnp oracles (interpret mode on CPU).

Sweeps shapes/dtypes per the deliverable spec; hypothesis property tests on
the assignment kernels' invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ---------------------------------------------------------------- pq_score
@pytest.mark.parametrize("nq,n,m", [
    (1, 64, 8), (7, 300, 16), (128, 512, 16), (33, 1000, 4), (2, 2048, 32),
])
def test_pq_score_matches_ref(nq, n, m):
    luts = _rand(0, nq, m, 16)
    codes = jax.random.randint(jax.random.PRNGKey(1), (n, m), 0, 16)
    got = ops.pq_score(luts, codes)
    want = ref.pq_score_ref(luts, codes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bq,bn", [(8, 128), (128, 512), (256, 256)])
def test_pq_score_block_shape_invariance(bq, bn):
    luts = _rand(2, 17, 8, 16)
    codes = jax.random.randint(jax.random.PRNGKey(3), (137, 8), 0, 16)
    got = ops.pq_score(luts, codes, bq=bq, bn=bn)
    want = ref.pq_score_ref(luts, codes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pq_score_dtype_bf16_lut():
    luts = _rand(4, 4, 16, 16).astype(jnp.bfloat16).astype(jnp.float32)
    codes = jax.random.randint(jax.random.PRNGKey(5), (256, 16), 0, 16)
    got = ops.pq_score(luts, codes)
    want = ref.pq_score_ref(luts, codes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- pq_score_window
def _window_case(nq, t, pmax, m, c=24, seed=0):
    """Random 4-bit codes of c partitions, their code table, and nq
    queries' t probed partitions (repeats allowed, as across queries)."""
    from repro.kernels.pq_score import code_table
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 16, (c, pmax, m), dtype=np.uint8)
    parts = rng.integers(0, c, (nq, t)).astype(np.int32)
    return codes, jnp.asarray(code_table(codes)), jnp.asarray(parts)


def _window_scores(luts, table, parts, pmax, **kw):
    nq, t = parts.shape
    out = ops.pq_score_window(luts, table, parts, **kw)
    assert out.shape == (nq, t, table.shape[1] * 128)
    return np.asarray(out)[:, :, :pmax].reshape(nq, t * pmax)


@pytest.mark.parametrize("m", [16, 25])
@pytest.mark.parametrize("t", [1, 16])
@pytest.mark.parametrize("nq", [1, 8, 13, 256])
def test_pq_score_window_matches_ref(nq, t, m):
    """The in-place window kernel against the reference on the window
    gathered from the raw codes: bitwise with integer-valued LUTs (every
    summation order is exact), to f32 rounding with random ones, and
    bitwise with random ones against the sum in subspace order. pmax
    1,678 is glove-100's capacity, not a multiple of 128; m = 25 leaves
    the last word one nibble."""
    pmax = 1678
    codes, table, parts = _window_case(nq, t, pmax, m, seed=nq * t + m)
    window = jnp.asarray(codes[np.asarray(parts)].reshape(nq, t * pmax, m))
    ints = jnp.round(_rand(6, nq, m, 16) * 100)
    got = _window_scores(ints, table, parts, pmax)
    assert np.array_equal(got, np.asarray(ref.pq_score_window_ref(ints,
                                                                  window)))
    luts = _rand(7, nq, m, 16)
    got = _window_scores(luts, table, parts, pmax)
    np.testing.assert_allclose(
        got, np.asarray(ref.pq_score_window_ref(luts, window)),
        rtol=1e-5, atol=1e-5)
    # subspaces add in order j = 0..m-1, as the int32-window kernel added
    # them: with random LUTs too the scores are that f32 sum, bitwise
    lut, win = np.asarray(luts), np.asarray(window)
    rows = np.arange(nq)[:, None]
    in_order = np.zeros(win.shape[:2], np.float32)
    for j in range(m):
        in_order = in_order + lut[rows, j, win[:, :, j]]
    assert np.array_equal(got, in_order)


# ---------------------------------------------------------------- vq_assign
@pytest.mark.parametrize("n,c,d", [
    (100, 16, 32), (513, 100, 64), (1000, 777, 128), (64, 2000, 100),
])
def test_vq_assign_matches_ref(n, c, d):
    X = _rand(10, n, d)
    C = _rand(11, c, d)
    idx, val = ops.vq_assign(X, C)
    ridx, rval = ref.vq_assign_ref(X, C)
    # compare chosen distances (ties could differ in index, never in value)
    np.testing.assert_allclose(np.asarray(val), np.asarray(rval),
                               rtol=1e-4, atol=1e-4)
    chosen = jnp.sum((X - C[idx]) ** 2, -1)
    ref_chosen = jnp.sum((X - C[ridx]) ** 2, -1)
    np.testing.assert_allclose(np.asarray(chosen), np.asarray(ref_chosen),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bn,bc", [(128, 128), (512, 256), (256, 1024)])
def test_vq_assign_block_invariance(bn, bc):
    X = _rand(12, 300, 48)
    C = _rand(13, 500, 48)
    idx, _ = ops.vq_assign(X, C, bn=bn, bc=bc)
    ridx, _ = ref.vq_assign_ref(X, C)
    assert (np.asarray(idx) == np.asarray(ridx)).mean() > 0.999


# -------------------------------------------------------------- soar_assign
@pytest.mark.parametrize("n,c,d,lam", [
    (200, 64, 32, 1.0), (513, 256, 64, 1.5), (100, 1000, 100, 0.5),
])
def test_soar_assign_matches_ref(n, c, d, lam):
    X = _rand(20, n, d)
    C = _rand(21, c, d)
    prim, _ = ref.vq_assign_ref(X, C)
    r = X - C[prim]
    rhat = r / jnp.maximum(jnp.linalg.norm(r, -1, keepdims=True), 1e-12)
    idx, val = ops.soar_assign(X, rhat, prim, C, lam=lam)
    ridx, rval = ref.soar_assign_ref(X, rhat, prim, C, lam)
    np.testing.assert_allclose(np.asarray(val), np.asarray(rval),
                               rtol=1e-4, atol=1e-4)
    assert not np.any(np.asarray(idx) == np.asarray(prim)), "spill == primary"


def test_soar_assign_lam0_is_second_closest():
    X = _rand(22, 128, 16)
    C = _rand(23, 64, 16)
    prim, _ = ref.vq_assign_ref(X, C)
    rhat = jnp.zeros_like(X).at[:, 0].set(1.0)
    idx, _ = ops.soar_assign(X, rhat, prim, C, lam=0.0)
    d2 = (jnp.sum(C * C, -1)[None] - 2 * X @ C.T)
    d2 = jnp.where(jax.nn.one_hot(prim, 64, dtype=bool), jnp.inf, d2)
    second = jnp.argmin(d2, -1)
    assert (np.asarray(idx) == np.asarray(second)).mean() > 0.999


# ------------------------------------------- fused batched assignment path
@pytest.mark.parametrize("n,c,d,lam", [(300, 64, 32, 1.0), (513, 130, 48, 0.7)])
def test_assign_fused_pallas_route_matches_gemm_route(n, c, d, lam):
    """The Pallas (vq_assign + soar_assign kernels, interpret mode here)
    route of the sharded-build assignment agrees with the chunked two-GEMM
    route — same argmins, loss computed by the fused kernel."""
    from repro.kernels.soar_assign import assign_fused

    X = _rand(30, n, d)
    C = _rand(31, c, d)
    gemm = np.asarray(assign_fused(X, C, lam=lam, n_spills=1, chunk=256,
                                   use_pallas=False))
    pall = np.asarray(assign_fused(X, C, lam=lam, n_spills=1,
                                   use_pallas=True, interpret=True))
    # tie-adjacent rows may flip under different GEMM tilings; require
    # near-total agreement rather than bitwise identity
    assert (gemm[:, 0] == pall[:, 0]).mean() > 0.999
    assert (gemm[:, 1] == pall[:, 1]).mean() > 0.999
    assert not np.any(pall[:, 1] == pall[:, 0])


@settings(max_examples=10, deadline=None)
@given(n=st.integers(8, 150), c=st.integers(4, 90), d=st.integers(2, 64),
       lam=st.floats(0.0, 3.0), spills=st.integers(1, 3),
       seed=st.integers(0, 2**30))
def test_assign_fused_property(n, c, d, lam, spills, seed):
    """Fused batched assignment invariants: column 0 is the Euclidean
    argmin, every row has distinct assignments, spills minimize the
    accumulated SOAR loss over the remaining centroids."""
    from repro.kernels.soar_assign import assign_fused

    spills = min(spills, c - 1)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    X = jax.random.normal(k1, (n, d))
    C = jax.random.normal(k2, (c, d))
    A = np.asarray(assign_fused(X, C, lam=float(lam), n_spills=spills,
                                chunk=64))
    assert A.shape == (n, 1 + spills)
    d_all = jnp.sum((X[:, None, :] - C[None, :, :]) ** 2, -1)
    np.testing.assert_array_equal(A[:, 0], np.asarray(jnp.argmin(d_all, -1)))
    for i in range(n):
        assert len(set(A[i].tolist())) == 1 + spills
    # spill 1 minimizes the single-spill SOAR loss over non-primary centroids
    r = X - C[A[:, 0]]
    rhat = r / jnp.maximum(jnp.linalg.norm(r, axis=-1, keepdims=True), 1e-12)
    rp = X[:, None, :] - C[None, :, :]
    loss = jnp.sum(rp * rp, -1) + lam * jnp.einsum("nd,ncd->nc", rhat, rp) ** 2
    loss = jnp.where(jax.nn.one_hot(A[:, 0], c, dtype=bool), jnp.inf, loss)
    chosen = np.asarray(loss)[np.arange(n), A[:, 1]]
    np.testing.assert_allclose(chosen, np.asarray(jnp.min(loss, -1)),
                               rtol=1e-3, atol=1e-3)


# ----------------------------------------------------- hypothesis properties
@settings(max_examples=20, deadline=None)
@given(n=st.integers(4, 200), c=st.integers(2, 120), d=st.integers(2, 96),
       seed=st.integers(0, 2**30))
def test_vq_assign_property(n, c, d, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    X = jax.random.normal(k1, (n, d))
    C = jax.random.normal(k2, (c, d))
    idx, val = ops.vq_assign(X, C)
    # invariant: reported min distance equals distance to reported centroid,
    # and is <= distance to every centroid
    d_all = jnp.sum((X[:, None, :] - C[None, :, :]) ** 2, -1)
    np.testing.assert_allclose(np.asarray(val),
                               np.asarray(jnp.min(d_all, -1)), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(jnp.sum((X - C[idx]) ** 2, -1)),
        np.asarray(jnp.min(d_all, -1)), rtol=1e-3, atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 100), c=st.integers(3, 80), d=st.integers(2, 64),
       lam=st.floats(0.0, 4.0), seed=st.integers(0, 2**30))
def test_soar_assign_property(n, c, d, lam, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    X = jax.random.normal(k1, (n, d))
    C = jax.random.normal(k2, (c, d))
    prim, _ = ref.vq_assign_ref(X, C)
    r = X - C[prim]
    rhat = r / jnp.maximum(jnp.linalg.norm(r, -1, keepdims=True), 1e-12)
    idx, val = ops.soar_assign(X, rhat, prim, C, lam=float(lam))
    # invariant: the kernel's loss is minimal over all non-primary centroids
    rp = X[:, None, :] - C[None, :, :]
    loss = jnp.sum(rp * rp, -1) + lam * jnp.einsum("nd,ncd->nc", rhat, rp) ** 2
    loss = jnp.where(jax.nn.one_hot(prim, c, dtype=bool), jnp.inf, loss)
    np.testing.assert_allclose(np.asarray(val), np.asarray(jnp.min(loss, -1)),
                               rtol=1e-3, atol=1e-3)
    assert not np.any(np.asarray(idx) == np.asarray(prim))


# --------------------------------------------------------------- tree_route
def _tree_tables(key, S, cmax, d, frac_pad=0.25):
    """Random router tables with ragged children (-1 pad like training)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(key), 3)
    SC = jax.random.normal(k1, (S, d), jnp.float32)
    CC = jax.random.normal(k2, (S, cmax, d), jnp.float32)
    ids = jnp.arange(S * cmax, dtype=jnp.int32).reshape(S, cmax)
    pad = jax.random.uniform(k3, (S, cmax)) < frac_pad
    pad = pad.at[:, 0].set(False)          # every super keeps >= 1 child
    CH = jnp.where(pad, -1, ids)
    CC = jnp.where(pad[:, :, None], 0.0, CC)
    return SC, CC, CH


@pytest.mark.parametrize("nq,S,cmax,d,tr", [
    (1, 4, 3, 8, 1), (7, 16, 9, 32, 3), (40, 8, 16, 16, 8),
    (130, 32, 5, 24, 4),   # nq > bq tile: multi-tile grid
])
def test_tree_route_pallas_matches_ref(nq, S, cmax, d, tr):
    from repro.kernels import tree_route as trk

    Q = _rand(40, nq, d)
    SC, CC, CH = _tree_tables(41, S, cmax, d)
    ws, wi = trk.tree_route_ref(Q, SC, CC, CH, t_route=tr)
    gs, gi = trk.tree_route_pallas(Q, SC, CC, CH, t_route=tr, bq=64,
                                   interpret=True)
    # same per-round supers (random normals: no super-score ties), so ids
    # must match exactly; -inf pad masks must coincide; finite scores are
    # the same dot products modulo accumulation order
    np.testing.assert_array_equal(np.asarray(wi), np.asarray(gi))
    wmask = np.isfinite(np.asarray(ws))
    gmask = np.isfinite(np.asarray(gs))
    np.testing.assert_array_equal(wmask, gmask)
    np.testing.assert_allclose(np.asarray(gs)[gmask], np.asarray(ws)[wmask],
                               rtol=1e-4, atol=1e-4)


def test_tree_route_pallas_tile_invariance():
    from repro.kernels import tree_route as trk

    Q = _rand(50, 37, 16)
    SC, CC, CH = _tree_tables(51, 8, 6, 16)
    a = trk.tree_route_pallas(Q, SC, CC, CH, t_route=2, bq=8, interpret=True)
    b = trk.tree_route_pallas(Q, SC, CC, CH, t_route=2, bq=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               rtol=1e-5, atol=1e-5)


def test_tree_route_dispatcher_cpu_uses_ref():
    """On CPU the dispatcher must take the jnp reference path and agree
    with an explicit ref call bitwise."""
    from repro.kernels import tree_route as trk

    Q = _rand(60, 9, 8)
    SC, CC, CH = _tree_tables(61, 4, 5, 8)
    ds_, di_ = trk.tree_route(Q, SC, CC, CH, t_route=2)
    rs_, ri_ = trk.tree_route_ref(Q, SC, CC, CH, t_route=2)
    np.testing.assert_array_equal(np.asarray(di_), np.asarray(ri_))
    np.testing.assert_array_equal(np.asarray(ds_), np.asarray(rs_))
