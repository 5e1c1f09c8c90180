"""Seeded traffic: open-loop schedules and closed-loop requests repeat
for a seed, and open-loop latency runs from each request's due time."""
import concurrent.futures
import contextlib
import os
import sys
import threading
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import corpus, harness  # noqa: E402

OPEN = {"kind": "open_loop", "rate_per_s": 500, "queries_per_request": 1}
BIG_SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def open_loop():
    return harness.plugin("loops", "open_loop")


@pytest.fixture(scope="module")
def closed_loop():
    return harness.plugin("loops", "closed_loop")


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, -3, 2**64 + 5])
def test_open_schedule_repeats_per_seed_with_a_fixed_count(open_loop, seed):
    due, rows = open_loop.schedule(OPEN, seed, 4.0, 1000)
    due2, rows2 = open_loop.schedule(OPEN, seed, 4.0, 1000)
    assert np.array_equal(due, due2) and np.array_equal(rows, rows2)
    assert len(due) == 2000 and rows.shape == (2000, 1)
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 4.0
    assert rows.min() >= 0 and rows.max() < 1000


def test_open_schedules_of_two_seeds_differ_in_order_not_in_load(open_loop):
    a, ra = open_loop.schedule(OPEN, 1, 4.0, 1000)
    b, rb = open_loop.schedule(OPEN, 2, 4.0, 1000)
    assert len(a) == len(b) and not np.array_equal(a, b)
    assert not np.array_equal(ra, rb)


def test_closed_loop_request_depends_on_seed_and_index_only(closed_loop):
    tr = {"queries_per_request": 16}
    r1 = closed_loop.request_rows(BIG_SEED, 3, tr, 100)
    assert np.array_equal(r1, closed_loop.request_rows(BIG_SEED, 3, tr, 100))
    assert not np.array_equal(r1, closed_loop.request_rows(BIG_SEED, 4, tr,
                                                           100))


def test_seed_key_takes_any_whole_number():
    import jax
    keys = [corpus.seed_key(s) for s in (0, BIG_SEED, -1, 2**40)]
    data = [tuple(np.asarray(jax.random.key_data(k))) for k in keys]
    assert len(set(data)) == 4
    assert data[1] == tuple(np.asarray(jax.random.key_data(
        corpus.seed_key(BIG_SEED))))


class StallingFrontend:
    """Answers each request 1 ms after submit, except that one call into
    `submit` blocks the caller for `stall_s`, as a frontend whose engine
    holds the interpreter lock would."""
    max_batch = 1

    def __init__(self, stall_at: int, stall_s: float):
        self.n = 0
        self.stall_at, self.stall_s = stall_at, stall_s
        self.pool = concurrent.futures.ThreadPoolExecutor(4)

    def submit(self, Q):
        self.n += 1
        if self.n == self.stall_at:
            time.sleep(self.stall_s)

        def answer():
            time.sleep(0.001)
            return types.SimpleNamespace(ids=np.zeros((len(Q), 10), np.int32),
                                         scores=np.zeros((len(Q), 10)))
        return self.pool.submit(answer)


def test_open_loop_latency_runs_from_the_due_time(open_loop):
    fe = StallingFrontend(stall_at=100, stall_s=0.2)
    tr = {"rate_per_s": 400, "queries_per_request": 1}
    started = threading.Event()
    w = open_loop.run(fe, np.zeros((50, 4), np.float32), tr, 5, 1.0,
                      lambda name: contextlib.nullcontext(),
                      lambda t0: started.set())
    fe.pool.shutdown()
    assert started.is_set() and w.attempted == 400
    lat = w.latency_s()
    assert np.isfinite(lat).all()
    # requests due during the stall were sent late, and their latency
    # counts the wait: a clock started at the send would hide it
    late = w.t_sent - w.t_due
    assert late.max() > 0.15
    assert np.percentile(lat, 99) > 0.1
    assert np.percentile(w.t_done - w.t_sent, 99) < 0.1
    assert open_loop.lateness_ms(w)["max"] > 150
