"""The control of the comparison at a size a test run holds: the plain
reference one precision step down (three-pass bfloat16) fails the score
gap, while the reference at the configuration's precision passes."""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import control, corpus, reference  # noqa: E402

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny.json")


def tiny(n=20000, d=100):
    with open(TINY) as f:
        cfg = json.load(f)["config"]
    return dict(cfg, n=n, d=d, n_queries=300)


def test_control_fails_and_the_reference_passes():
    cfg = tiny()
    for seed in (1, 2, 3):
        got = control.readings(cfg, seed)
        assert not got["correct"], got
        assert got["score_gap"] > 3 * cfg["limits"]["score_gap"]
        assert got["recall_at_10"] > 0.9          # recall alone passes
    X, pool = corpus.make(cfg, 1)
    ids, scores = reference.exact_topk_device(X, pool, chunk=4096)
    read = reference.readings(X, pool, np.arange(len(pool)), ids, scores, ids)
    ok, _ = reference.check(read, cfg["limits"], {})
    assert ok and read["recall_at_10"] == 1.0


def test_device_reference_and_vectorised_recall_match_the_host_copies():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((5000, 8)).astype(np.float32)
    Q = rng.standard_normal((37, 8)).astype(np.float32)
    want = reference.exact_topk(X, Q, chunk=700)
    got, _ = reference.exact_topk_device(X, Q, q_block=16, chunk=512)
    assert np.array_equal(got, want)
    noisy = got.copy()
    noisy[::3, 4:] = -1
    assert np.isclose(reference.recall_rows(noisy, want).mean(),
                      reference.recall_at_k(noisy, want))


def test_duplicates_and_foreign_ids_are_caught():
    X = np.eye(4, dtype=np.float32)
    pool = np.eye(4, dtype=np.float32)
    ids = np.array([[0, 1], [2, 2]])
    assert reference.duplicate_rows(ids) == 1
    assert reference.score_gap(X, pool, np.array([0, 1]), np.array([[0, 9]]),
                               np.zeros((1, 2))) == float("inf")
