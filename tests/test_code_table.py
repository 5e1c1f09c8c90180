"""The packed code table (`PackedIVF.part_codes`, DESIGN.md §3.6).

The window kernel reads each probed partition's block of the table in
place, so the table is the index's only device copy of the PQ codes.
Every writer of the table must produce the layout `code_table` defines:
the full pack, the mutable index's dirty-row delta, the sharded stacker
(shards of unequal capacity) and a snapshot load — including a snapshot
written with (c, pmax, m) uint8 code storage, as format v1 stored it
before the table.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import index_store
from repro.core import build_ivf, pack_ivf
from repro.core.search import LANES, search_jit_batched
from repro.kernels.pq_score import (code_table, code_table_codes,
                                    code_table_shape)

KW = dict(top_t=3, final_k=10, rerank_budget=48, multiplicity=2)


@pytest.mark.parametrize("m", [1, 8, 25])
@pytest.mark.parametrize("pmax", [1, 130, 1678])
def test_code_table_layout_round_trip(pmax, m):
    """Word w of slot r·128 + l sits at [p, r, w·128 + l] and holds
    subspace 8w + s in bits 4s..4s+3; padding is zero; decoding gives the
    codes back, on numpy and on a device array alike."""
    c = 3
    codes = np.random.default_rng(pmax + m).integers(
        0, 16, (c, pmax, m), dtype=np.uint8)
    table = code_table(codes)
    assert table.shape == code_table_shape(c, pmax, m)
    assert table.dtype == np.int32
    assert table.shape[1] % 8 == 0 and table.shape[1] * LANES >= pmax
    words = table.view(np.uint32)
    for p, i, j in [(0, 0, 0), (c - 1, pmax - 1, m - 1),
                    (1, pmax // 2, m // 2)]:
        r, lane = divmod(i, LANES)
        w, s = divmod(j, 8)
        assert (words[p, r, w * LANES + lane] >> 4 * s) & 15 == codes[p, i, j]
    back = code_table_codes(table, m, pmax)
    assert isinstance(back, np.ndarray) and np.array_equal(back, codes)
    assert np.array_equal(np.asarray(code_table_codes(jnp.asarray(table), m,
                                                      pmax)), codes)
    full = code_table_codes(table, -(-m // 8) * 8, table.shape[1] * LANES)
    assert not full[:, pmax:].any() and not full[:, :, m:].any()


@functools.lru_cache(maxsize=None)
def _index(n, d, c, m, seed):
    X = np.random.default_rng(seed).standard_normal((n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    return X, build_ivf(jax.random.PRNGKey(seed), X, c, spill_mode="soar",
                        pq_subspaces=m, train_iters=3)


def _raw_codes(idx, pmax):
    """(c, pmax, m) uint8 codes of an IVFIndex, straight from its CSR."""
    c = idx.n_partitions
    out = np.zeros((c, pmax, idx.codes.shape[1]), np.uint8)
    for p in range(c):
        lo, hi = idx.starts[p], idx.starts[p + 1]
        out[p, :hi - lo] = idx.codes[lo:hi]
    return out


@pytest.mark.parametrize("d,m", [(32, 8), (100, 25)])
def test_code_storage_snapshot_loads_and_serves(d, m, tmp_path):
    """A PackedIVF snapshot as format v1 stored it before the table,
    (c, pmax, m) uint8 codes under "part_codes", loads into the table and
    serves the answers of a fresh pack; saving it again keeps that
    storage."""
    X, idx = _index(1_001, d, 12, m, d)
    packed = pack_ivf(idx)
    pmax = packed.part_ids.shape[1]
    kind, meta, arrays = index_store._state_of(packed, None)
    arrays["part_codes"] = _raw_codes(idx, pmax)
    index_store._write_state(str(tmp_path / "old"), kind, meta, arrays)
    loaded, _ = index_store.load_snapshot(str(tmp_path / "old"))
    assert np.array_equal(np.asarray(loaded.part_codes),
                          np.asarray(packed.part_codes))
    Q = jnp.asarray(X[:16] + 0.1)
    want = search_jit_batched(packed, Q, bq=8, **KW)
    got = search_jit_batched(loaded, Q, bq=8, **KW)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    index_store.save_snapshot(str(tmp_path / "new"), loaded)
    man = index_store.read_manifest(str(tmp_path / "new"))
    stored = next(e for e in man["arrays"] if e["name"] == "part_codes")
    assert stored["shape"] == [12, pmax, m] and stored["dtype"] == "uint8"
    again, _ = index_store.load_snapshot(str(tmp_path / "new"))
    assert np.array_equal(np.asarray(again.part_codes),
                          np.asarray(packed.part_codes))


def test_stacker_pads_shards_of_unequal_pmax():
    """Shards whose capacities need different numbers of slot rows stack
    into one table: each shard's codes decode back at the common pmax,
    zero past its own, and each shard of the stack answers as its own
    pack does."""
    from repro.core.distributed import sharded_from_indexes
    shards = [_index(3_000, 16, 4, 4, 1)[1], _index(400, 16, 4, 4, 2)[1]]
    own = [pack_ivf(idx, pair_codes=False) for idx in shards]
    rows = [pk.part_codes.shape[1] for pk in own]
    assert rows[0] > rows[1], rows
    stacked = sharded_from_indexes(shards).packed
    pmax = stacked.part_ids.shape[-1]
    assert stacked.part_codes.shape == (2, 4, rows[0], LANES)
    Q = jnp.asarray(np.random.default_rng(3).standard_normal(
        (16, 16)).astype(np.float32))
    for s, (idx, pk) in enumerate(zip(shards, own)):
        codes = code_table_codes(np.asarray(stacked.part_codes[s]), 4, pmax)
        p_s = pk.part_ids.shape[1]
        assert np.array_equal(codes[:, :p_s], _raw_codes(idx, p_s))
        assert not codes[:, p_s:].any()
        one = jax.tree_util.tree_map(lambda x: x[s], stacked)
        got = search_jit_batched(one, Q, bq=8, escalate=False, **KW)
        want = search_jit_batched(pk, Q, bq=8, escalate=False, **KW)
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b))
