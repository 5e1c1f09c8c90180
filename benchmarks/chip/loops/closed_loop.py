"""Closed-loop traffic: a fixed number of clients, each sending its next
request as soon as the previous one returns, as batch retrieval jobs do.

Parameters (traffic file): `clients`, `queries_per_request`. Request j of
a run asks for `queries_per_request` pool rows drawn from the seed and j
alone, so the content of the j-th request does not depend on which
client sends it. A request counts as due when its client sends it.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchmarks.chip.corpus import rng
from benchmarks.chip.window import Window


def request_rows(seed: int, j: int, traffic: dict, pool_size: int):
    return rng(seed, 1000 + j).integers(0, pool_size,
                                        traffic["queries_per_request"])


def warm(fe, pool, traffic) -> None:
    """Serve one request of the traffic's size."""
    fe.search(pool[:traffic["queries_per_request"]])


def run(fe, pool, traffic, seed, seconds, span, on_start) -> Window:
    lock = threading.Lock()
    recs = {}
    nxt = [0]
    t0 = time.perf_counter()
    t1 = t0 + seconds
    on_start(t0)

    def client():
        while True:
            with lock:
                j = nxt[0]
                nxt[0] += 1
            if time.perf_counter() >= t1:
                return
            rows = request_rows(seed, j, traffic, pool.shape[0])
            Q = pool[rows]
            t_sent = time.perf_counter()
            try:
                with span("bench:submit"):
                    fut = fe.submit(Q)
                with span("bench:wait"):
                    res = fut.result(timeout=max(t1 - time.perf_counter(), 0)
                                     + 60)
            except Exception as e:      # refused, failed or never answered
                res = e
            recs[j] = (t_sent, time.perf_counter(), rows, res)
            if isinstance(res, BaseException):
                return

    threads = [threading.Thread(target=client, name=f"bench-client-{c}")
               for c in range(traffic["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(t1 - time.perf_counter(), 0) + 90)
    order = sorted(recs)
    sent = np.array([recs[j][0] for j in order], float)
    done = np.array([recs[j][1] if not isinstance(recs[j][3], BaseException)
                     else np.nan for j in order], float)
    return Window(t0, t1, sent, sent.copy(), done,
                  [recs[j][2] for j in order], [recs[j][3] for j in order])
