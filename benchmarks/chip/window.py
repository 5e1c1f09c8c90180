"""What a traffic loop hands back: every request of the measured window,
when it was due, sent and answered, which pool rows it asked for, and
what came back."""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def _fits(r, rows) -> bool:
    ids = getattr(r, "ids", None)
    scores = getattr(r, "scores", None)
    return (ids is not None and scores is not None and ids.ndim == 2
            and ids.shape[0] == len(rows) and scores.shape == ids.shape)


@dataclasses.dataclass
class Window:
    t0: float                  # perf_counter at the window's start
    t1: float                  # ... and at its end (t0 + seconds)
    t_due: np.ndarray          # per request: when it was due to be sent
    t_sent: np.ndarray         # when the loop submitted it
    t_done: np.ndarray         # when its Future resolved (nan: never)
    qidx: List[np.ndarray]     # pool rows each request asked for
    results: list              # SearchResult, or the exception raised

    @property
    def attempted(self) -> int:
        return len(self.results)

    def answered(self) -> np.ndarray:
        """Requests that came back with an answer of their own shape: one
        row of ids and of scores for each query asked."""
        return np.array([_fits(r, q) for r, q in zip(self.results, self.qidx)],
                        bool)

    def latency_s(self) -> np.ndarray:
        """Due time to resolution; inf for a request never answered."""
        lat = self.t_done - self.t_due
        return np.where(self.answered() & np.isfinite(lat), lat, np.inf)

    def answers(self):
        """(qidx, ids, scores) of every answered query, concatenated."""
        ok = np.flatnonzero(self.answered())
        if not ok.size:
            return (np.zeros(0, np.int64), np.zeros((0, 10), np.int32),
                    np.zeros((0, 10), np.float32))
        return (np.concatenate([self.qidx[i] for i in ok]),
                np.concatenate([self.results[i].ids for i in ok]),
                np.concatenate([self.results[i].scores for i in ok]))

    def in_interval(self, lo: float, hi: float) -> np.ndarray:
        """Answered requests due within [lo, hi)."""
        return self.answered() & (self.t_due >= lo) & (self.t_due < hi)
