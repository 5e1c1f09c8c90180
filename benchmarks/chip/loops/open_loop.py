"""Open-loop traffic: requests are sent on a seeded schedule whether or not
earlier ones have returned, as independent users send them.

Parameters (traffic file): `rate_per_s`, `queries_per_request`. A window
of `seconds` holds exactly round(rate * seconds) requests, whose due times
are sorted uniform draws over the window (a Poisson process conditioned on
its count), so every seed offers the same load in another order. Each
request asks for `queries_per_request` rows drawn with replacement from
the query pool. Latency runs from the due time, not from the send, so a
stall also charges the requests that queue behind it.
"""
from __future__ import annotations

import functools
import threading
import time

import numpy as np

from benchmarks.chip.corpus import rng
from benchmarks.chip.window import Window

LEAD_S = 0.01


def schedule(traffic: dict, seed: int, seconds: float, pool_size: int):
    """(due offsets (n,) in seconds, pool rows (n, queries_per_request))."""
    g = rng(seed, 2)
    n = int(round(traffic["rate_per_s"] * seconds))
    due = np.sort(g.uniform(0.0, seconds, n))
    rows = g.integers(0, pool_size, (n, traffic["queries_per_request"]))
    return due, rows


def warm(fe, pool, traffic) -> None:
    """Serve every batch size the frontend can coalesce these requests
    into, up to its `max_batch`, once."""
    q = traffic["queries_per_request"]
    sizes = {q}
    while q < fe.max_batch:
        q = min(2 * q, fe.max_batch)
        sizes.add(q)
    for nq in sorted(sizes):
        fe.search(pool[:nq])


def run(fe, pool, traffic, seed, seconds, span, on_start) -> Window:
    due, rows = schedule(traffic, seed, seconds, pool.shape[0])
    n = len(due)
    t_sent = np.full(n, np.nan)
    t_done = np.full(n, np.nan)
    results = [None] * n
    all_done = threading.Event()
    left = [n]
    lock = threading.Lock()

    def finish(i, fut):
        t_done[i] = time.perf_counter()
        e = fut.exception()
        results[i] = e if e is not None else fut.result()
        with lock:
            left[0] -= 1
            if left[0] == 0:
                all_done.set()

    t0 = time.perf_counter() + LEAD_S
    on_start(t0)
    t_due = t0 + due
    for i in range(n):
        wait = t_due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        try:
            with span("bench:submit"):
                t_sent[i] = time.perf_counter()
                fut = fe.submit(pool[rows[i]])
        except Exception as e:          # refused at submit: never answered
            results[i] = e
            with lock:
                left[0] -= 1
            continue
        fut.add_done_callback(functools.partial(finish, i))
    with lock:
        if left[0] == 0:
            all_done.set()
    all_done.wait(timeout=max(t0 + seconds - time.perf_counter(), 0) + 60)
    return Window(t0, t0 + seconds, t_due, t_sent, t_done, list(rows),
                  results)


def lateness_ms(w: Window) -> dict:
    """How late the loop sent its requests, against their due times."""
    late = (w.t_sent - w.t_due) * 1e3
    late = late[np.isfinite(late)]
    if not late.size:
        return {}
    return {"p50": float(np.percentile(late, 50)),
            "p99": float(np.percentile(late, 99)),
            "max": float(late.max())}
