"""The replica fan-out's span metrics (`replica_scatter_ms.rep4`,
`replica_gather_ms.rep4`, contained.py) on hand-made events."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness, spans, tracefile as tf  # noqa: E402

MS = 1_000_000
READERS = ("replica_scatter_ms.rep4", "replica_gather_ms.rep4")


def ev(name, start_ms, end_ms, thread="python3"):
    return tf.Ev(name, int(start_ms * MS), int(end_ms * MS), thread)


def replica_dispatch(t0, scatter, gather, place=0):
    """One replica dispatch from t0: a placement of `place` ms (none at
    0), then scatter, a 1 ms launch and gather, back to back."""
    t = t0 + 1
    out = [ev("frontend.coalesce", t0, t)]
    if place:
        out.append(ev("replica.place", t, t + place))
        t += place
    out += [ev("replica.scatter", t, t + scatter),
            ev("replica.launch", t + scatter, t + scatter + 1),
            ev("replica.gather", t + scatter + 1, t + scatter + 1 + gather)]
    end = t + scatter + 1 + gather
    out += [ev("frontend.resolve", end, end + 1),
            ev(spans.DISPATCH, t0, end + 2)]
    return out


def hand_trace(with_replica=True):
    """Window 0-100 ms. Replica dispatches A (5-, with a placement) and B
    (50-) lie inside it; C (90-) ends after it. A local dispatch L (30-)
    holds no replica span; one stray scatter (45-46) belongs to no
    dispatch."""
    host = [ev(tf.WINDOW, 0, 100)]
    if with_replica:
        host += (replica_dispatch(5, scatter=2, gather=12, place=4)
                 + replica_dispatch(50, scatter=4, gather=20)
                 + replica_dispatch(90, scatter=1, gather=30)
                 + [ev("replica.scatter", 45, 46)])
    host += [ev(spans.DISPATCH, 30, 40), ev("engine.download", 32, 38)]
    ops = {d: [ev("%a.1 = f32[1] a()", 0, 100)] for d in range(4)}
    return tf.from_events(ops, {}, host)


def read(name, tr):
    return harness.plugin("metrics", name).read(types.SimpleNamespace(trace=tr))


@pytest.mark.parametrize("name, want", [("replica_scatter_ms.rep4", 3.0),
                                        ("replica_gather_ms.rep4", 16.0)])
def test_mean_over_the_replica_dispatches_inside_the_window(name, want):
    """A and B only: C is cut by the window's end, the local dispatch
    holds none of the spans, and the stray span is in no dispatch."""
    assert read(name, hand_trace()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_trace_without_replica_spans_reads_nothing(name):
    assert read(name, hand_trace(with_replica=False)) is None
    assert read(name, None) is None
