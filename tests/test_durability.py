"""Durable index lifecycle (DESIGN.md §3.11): snapshot round trips are
bitwise, every injected crash point recovers to a committed state (never a
torn hybrid), corruption surfaces CorruptSnapshotError, and the serving
entry points reject malformed inputs at the edge.

The crash matrix runs in-process (mode="raise": the writer flushes+fsyncs
up to the injection point, so the on-disk state IS the crash state) —
plus a couple of true os._exit subprocess crashes validating end-to-end
that nothing depends on interpreter-side cleanup.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.ckpt import faults
from repro.ckpt.faults import InjectedCrash
from repro.ckpt.index_store import (CorruptSnapshotError, load_snapshot,
                                    save_snapshot)
from repro.ckpt.wal import REC_ADD, MutationWAL, read_records
from repro.serve.engine import AnnEngine

D = 16
K = 5


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.uninstall()


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module")
def queries(rng):
    return rng.normal(size=(12, D)).astype(np.float32)


@pytest.fixture(scope="module")
def built(rng):
    """One shared engine: PQ + tree router + hard and soft tombstones —
    every piece of state the snapshot must carry."""
    X = rng.normal(size=(500, D)).astype(np.float32)
    eng = AnnEngine.build(jax.random.PRNGKey(0), X, 16, pq_subspaces=4,
                          router="tree", router_kw={"n_super": 4})
    eng.add(rng.normal(size=(40, D)).astype(np.float32))
    eng.remove([3, 5, 7], hard=True)
    eng.remove([11, 13], hard=False)
    return eng


def _clone(eng, tmp_path, name):
    p = str(tmp_path / name)
    eng.save(p)
    return AnnEngine.open(p), p


# ------------------------------------------------------------ round trips
def test_engine_snapshot_roundtrip_bitwise(built, queries, tmp_path):
    i0, s0 = built.search(queries, k=K)
    e2, _ = _clone(built, tmp_path, "eng")
    i1, s1 = e2.search(queries, k=K)
    assert np.array_equal(i0, i1) and np.array_equal(s0, s1)
    assert (e2.top_t, e2.rerank_budget, e2.bq) == (
        built.top_t, built.rerank_budget, built.bq)
    # tombstone state survives: same soft-deleted population, same filter
    assert e2.index.n_soft_deleted == built.index.n_soft_deleted
    assert np.array_equal(e2.index.alive, built.index.alive)


def test_ivf_snapshot_roundtrip_numpy_engine(built, queries, tmp_path):
    from repro.core.search import search_numpy
    idx = built.index.to_ivf_index()
    i0, st0 = search_numpy(idx, queries, top_t=6, final_k=K,
                           rerank_budget=64)
    p = str(tmp_path / "ivf")
    save_snapshot(p, idx)
    idx2, _ = load_snapshot(p, expect_kind="IVFIndex")
    i1, st1 = search_numpy(idx2, queries, top_t=6, final_k=K,
                           rerank_budget=64)
    assert np.array_equal(i0, i1)
    assert np.array_equal(st0.points_read, st1.points_read)
    # the tree router rode along (probe order is part of the contract)
    assert type(idx2.router).__name__ == type(idx.router).__name__


def test_knn_memory_roundtrip_with_filters(rng, tmp_path):
    from repro.serve.knn_memory import KNNMemory
    Kv = rng.normal(size=(300, 8)).astype(np.float32)
    V = rng.normal(size=(300, 8)).astype(np.float32)
    mem = KNNMemory.build(Kv, V, n_partitions=8, engine="jit")
    mem.add(rng.normal(size=(16, 8)).astype(np.float32),
            rng.normal(size=(16, 8)).astype(np.float32), segment=2)
    mem.remove([1, 2], hard=False)
    q = rng.normal(size=(6, 8)).astype(np.float32)
    p = str(tmp_path / "mem")
    mem.save(p)
    m2 = KNNMemory.open(p)
    for kw in ({}, {"recency": 200}, {"segment": 2}):
        i0, k0, v0 = mem.retrieve(q, k=8, **kw)
        i1, k1, v1 = m2.retrieve(q, k=8, **kw)
        assert np.array_equal(i0, i1)
        assert np.array_equal(k0, k1) and np.array_equal(v0, v1)


def test_sharded_envelope_roundtrip(rng, tmp_path):
    from repro.core.build import build_ivf_sharded
    from repro.core.distributed import (load_sharded, save_sharded,
                                        sharded_from_indexes)
    from repro.core.mutable import MutableIVF
    X = rng.normal(size=(512, 8)).astype(np.float32)
    shards = [build_ivf_sharded(jax.random.PRNGKey(s),
                                X[s * 256:(s + 1) * 256], 8,
                                pq_subspaces=2) for s in range(2)]
    shards[0] = MutableIVF.from_index(shards[0])
    shards[0].add(rng.normal(size=(10, 8)).astype(np.float32))
    s0 = sharded_from_indexes(shards)
    p = str(tmp_path / "shards")
    save_sharded(p, shards, extra={"note": 1})
    loaded, extra = load_sharded(p)
    assert extra == {"note": 1}
    s1 = sharded_from_indexes(loaded)
    for a, b in zip(jax.tree_util.tree_leaves(s0),
                    jax.tree_util.tree_leaves(s1)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- corruption → error
def test_corruption_raises_not_garbage(built, tmp_path):
    cases = [
        ("arrays mid-file flip", "index/arrays.bin",
         lambda p: faults.flip_byte(p, 1000)),
        ("arrays tail flip", "index/arrays.bin",
         lambda p: faults.flip_byte(p, -1)),
        ("arrays truncated", "index/arrays.bin",
         lambda p: faults.truncate_tail(p, 7)),
        ("manifest flip", "index/manifest.json",
         lambda p: faults.flip_byte(p, -2)),
        ("manifest truncated", "index/manifest.json",
         lambda p: faults.truncate_tail(p, 30)),
    ]
    for i, (label, rel, inject) in enumerate(cases):
        p = str(tmp_path / f"c{i}")
        built.save(p)
        inject(os.path.join(p, rel))
        with pytest.raises(CorruptSnapshotError):
            AnnEngine.open(p)


def test_missing_snapshot_is_clear(tmp_path):
    with pytest.raises(CorruptSnapshotError, match="no snapshot"):
        load_snapshot(str(tmp_path / "nope"))


# --------------------------------------------------------------- WAL unit
def test_wal_roundtrip_and_torn_tail(tmp_path):
    p = str(tmp_path / "wal.log")
    with MutationWAL(p) as w:
        w.append(REC_ADD, {"i": 0}, {"x": np.arange(6, dtype=np.float32)})
        w.append(REC_ADD, {"i": 1}, {"x": np.ones((2, 3), np.int32)})
        last = w.append(REC_ADD, {"i": 2})
    assert last == 3
    recs = list(read_records(p))
    assert [m["i"] for _, _, m, _ in recs] == [0, 1, 2]
    assert np.array_equal(recs[1][3]["x"], np.ones((2, 3), np.int32))
    # tear the final record: committed prefix survives, tail dropped
    faults.truncate_tail(p, 5)
    assert [m["i"] for _, _, m, _ in read_records(p)] == [0, 1]
    # reopening truncates the torn bytes and continues the sequence
    with MutationWAL(p) as w:
        assert w.last_seq == 2
        assert w.append(REC_ADD, {"i": 9}) == 3
    assert [m["i"] for _, _, m, _ in read_records(p)] == [0, 1, 9]


def test_wal_midfile_corruption_raises(tmp_path):
    p = str(tmp_path / "wal.log")
    with MutationWAL(p) as w:
        w.append(REC_ADD, {"i": 0}, {"x": np.zeros(8, np.float32)})
        w.append(REC_ADD, {"i": 1})
    faults.flip_byte(p, 30)            # inside record 0's payload
    with pytest.raises(CorruptSnapshotError):
        list(read_records(p))
    with pytest.raises(CorruptSnapshotError):
        MutationWAL(p)                 # the opener validates too


def test_wal_guards(tmp_path):
    with pytest.raises(ValueError):
        MutationWAL(str(tmp_path / "w"), fsync="sometimes")
    with MutationWAL(str(tmp_path / "w2"), fsync="never") as w:
        w.append(REC_ADD, {"i": 0})
        with pytest.raises(ValueError):
            w.rotate(0)                # records past 0 are in the log
        w.rotate(w.last_seq)
    assert os.path.getsize(str(tmp_path / "w2")) == 0
    # start_seq floors the sequence after a rotation
    with MutationWAL(str(tmp_path / "w2"), start_seq=7) as w:
        assert w.append(REC_ADD) == 8


# ------------------------------------------------- in-process crash matrix
SNAPSHOT_FAULTS = [
    ("snapshot:arrays+0", "old"),
    ("snapshot:arrays+64", "old"),
    ("snapshot:arrays+4099", "old"),
    ("snapshot:manifest+0", "old"),
    ("snapshot:manifest+10", "old"),
    ("commit:between_renames", "old"),
    ("commit:before_cleanup", "new"),
]


def test_snapshot_crash_matrix(built, queries, tmp_path):
    """Every crash point during an overwriting save reopens to a committed
    state — the previous snapshot for crashes before the swap completes,
    the new one after — bitwise."""
    ra = built.search(queries, k=K)
    for i, (spec, expect) in enumerate(SNAPSHOT_FAULTS):
        engB, p = _clone(built, tmp_path, f"m{i}")
        engB.add(np.linspace(0, 1, 3 * D, dtype=np.float32).reshape(3, D))
        rb = engB.search(queries, k=K)
        faults.install(spec)
        with pytest.raises(InjectedCrash):
            engB.save(p)
        faults.uninstall()
        r2 = AnnEngine.open(p).search(queries, k=K)
        want = ra if expect == "old" else rb
        assert np.array_equal(r2[0], want[0]), (spec, expect)
        assert np.array_equal(r2[1], want[1]), (spec, expect)


def test_first_save_crash_leaves_no_committed_state(built, tmp_path):
    """Crash during the very first save: there is no previous snapshot to
    fall back to — open must refuse loudly, not serve a torn index."""
    p = str(tmp_path / "first")
    faults.install("snapshot:arrays+128")
    with pytest.raises(InjectedCrash):
        built.save(p)
    faults.uninstall()
    with pytest.raises(CorruptSnapshotError):
        AnnEngine.open(p)


WAL_FAULTS = [
    ("wal:append+0", "pre"),           # nothing of the record on disk
    ("wal:append+5", "pre"),           # torn header
    ("wal:append+23", "pre"),          # header complete less one byte
    ("wal:append+60", "pre"),          # torn payload
    ("wal:record", "post"),            # record durable, apply interrupted
]


def test_wal_crash_matrix(built, queries, tmp_path):
    """A crash anywhere inside a logged mutation recovers to exactly the
    pre-mutation state (torn record dropped) or the post-mutation state
    (record fully durable, replayed on open) — never between."""
    add = np.linspace(-1, 1, 4 * D, dtype=np.float32).reshape(4, D)
    for i, (spec, expect) in enumerate(WAL_FAULTS):
        _, p = _clone(built, tmp_path, f"w{i}")
        eng = AnnEngine.open(p, wal=True)
        r_pre = eng.search(queries, k=K)
        faults.install(spec)
        with pytest.raises(InjectedCrash):
            eng.add(add)
        faults.uninstall()
        eng2 = AnnEngine.open(p)
        r2 = eng2.search(queries, k=K)
        if expect == "pre":
            want = r_pre
        else:                          # replay applies the committed add
            # w0, not a string replace on p: the xdist worker's tmp dir
            # ("popen-gw<i>") would match too
            ref = AnnEngine.open(str(tmp_path / "w0"))
            ref.add(add)
            want = ref.search(queries, k=K)
        assert np.array_equal(r2[0], want[0]), (spec, expect)
        assert np.array_equal(r2[1], want[1]), (spec, expect)


def test_checkpoint_commit_crash_recovers_previous(tmp_path):
    """The ckpt/checkpoint.py satellite: the old rmtree-then-rename window
    lost the only copy; the rename-aside swap keeps one at every point."""
    import jax.numpy as jnp

    from repro.ckpt.checkpoint import restore, save
    p = str(tmp_path / "ck")
    save(p, {"x": jnp.zeros(4)}, step=1)
    faults.install("commit:between_renames")
    with pytest.raises(InjectedCrash):
        save(p, {"x": jnp.ones(4)}, step=2)
    faults.uninstall()
    back, step, _ = restore(p, {"x": jnp.zeros(4)})
    assert step == 1 and float(np.asarray(back["x"])[0]) == 0.0
    # and the interrupted swap was finished: a clean save works again
    save(p, {"x": jnp.ones(4)}, step=2)
    _, step, _ = restore(p, {"x": jnp.zeros(4)})
    assert step == 2


# ------------------------------------------------- true-crash subprocesses
_CHILD = r"""
import os, sys
import numpy as np
import jax
from repro.ckpt import faults
from repro.serve.engine import AnnEngine

d = sys.argv[1]
rng = np.random.default_rng(0)
X = rng.normal(size=(300, 8)).astype(np.float32)
Q = rng.normal(size=(6, 8)).astype(np.float32)
add = np.linspace(0, 1, 4 * 8, dtype=np.float32).reshape(4, 8)

eng = AnnEngine.build(jax.random.PRNGKey(0), X, 8, pq_subspaces=2)
p = os.path.join(d, "eng")
eng.save(p)
eng = AnnEngine.open(p, wal=True)
np.save(os.path.join(d, "q.npy"), Q)
i, s = eng.search(Q, k=4)
np.save(os.path.join(d, "pre.npy"), np.concatenate(
    [i.astype(np.float64), s.astype(np.float64)], axis=1))

stage = os.environ["CRASH_STAGE"]
faults.install()          # reads REPRO_FAULT / REPRO_FAULT_MODE=exit
if stage == "save":
    eng.add(add)          # committed through the WAL
    i, s = eng.search(Q, k=4)
    np.save(os.path.join(d, "post.npy"), np.concatenate(
        [i.astype(np.float64), s.astype(np.float64)], axis=1))
    eng.save(p)           # dies mid-commit (os._exit, no cleanup)
else:
    eng.add(add)          # dies mid-append
os._exit(0)
"""


@pytest.mark.parametrize("stage,fault,expect", [
    ("save", "commit:between_renames", "post"),
    ("mutate", "wal:append+30", "pre"),
])
def test_subprocess_crash_recovery(tmp_path, stage, fault, expect):
    """End-to-end with a REAL crash (os._exit: no atexit, no interpreter
    cleanup): reopen serves bitwise the last committed state."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", CRASH_STAGE=stage,
               REPRO_FAULT=fault, REPRO_FAULT_MODE="exit")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _CHILD, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 42, (r.returncode, r.stdout, r.stderr)
    eng = AnnEngine.open(str(tmp_path / "eng"))
    Q = np.load(tmp_path / "q.npy")
    i, s = eng.search(Q, k=4)
    got = np.concatenate([i.astype(np.float64), s.astype(np.float64)],
                         axis=1)
    want = np.load(tmp_path / f"{expect}.npy")
    assert np.array_equal(got, want)


# ------------------------------------------------------- hardened serving
def test_search_input_validation(built, queries):
    with pytest.raises(ValueError, match="top_t"):
        built.search(queries, top_t=0)       # was silently self.top_t
    with pytest.raises(ValueError, match="k must"):
        built.search(queries, k=0)
    with pytest.raises(ValueError, match="dim"):
        built.search(queries[:, :5])
    with pytest.raises(ValueError, match="numeric"):
        built.search(np.array(["a", "b"]))
    with pytest.raises(ValueError, match="shape"):
        built.search(np.zeros((2, 2, D), np.float32))
    bad = queries.copy()
    bad[0, 0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        built.search(bad)
    ids, _ = built.search(bad, k=K, sanitize=True)
    assert ids.shape == (queries.shape[0], K)
    # float64 values that overflow the float32 cast are caught too
    with pytest.raises(ValueError, match="non-finite"):
        built.search(np.full((1, D), 1e300))
    with pytest.raises(ValueError):
        AnnEngine(built.index, top_t=0)


def test_empty_batches(built):
    i, s = built.search(np.empty((0, D), np.float32), k=7)
    assert i.shape == (0, 7) and s.shape == (0, 7)
    from repro.core.search import search_numpy
    out, stats = search_numpy(built.index.to_ivf_index(),
                              np.empty((0, D), np.float32), top_t=4,
                              final_k=6)
    assert out.shape == (0, 6) and stats.points_read.shape == (0,)


def test_knn_retrieve_validation(rng):
    from repro.serve.knn_memory import KNNMemory
    Kv = rng.normal(size=(200, 8)).astype(np.float32)
    mem = KNNMemory.build(Kv, Kv, n_partitions=4)
    q = rng.normal(size=(3, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="top_t"):
        mem.retrieve(q, top_t=0)
    with pytest.raises(ValueError, match="k must"):
        mem.retrieve(q, k=0)
    with pytest.raises(ValueError, match="non-finite"):
        mem.retrieve(np.full((1, 8), np.inf, np.float32))
