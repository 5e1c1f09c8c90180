"""The reduction of the serving program's host spans (spans.py) and the
five metrics that read it, on hand-made events."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness, spans, tracefile as tf  # noqa: E402

MS = 1_000_000
READERS = ("host_prep_ms.online", "result_wait_ms.online",
           "resolve_ms.online", "idle_in_dispatch_share.online",
           "idle_in_dispatch_share.bulk")


def ev(name, start_ms, end_ms, thread="python3"):
    return tf.Ev(name, int(start_ms * MS), int(end_ms * MS), thread)


def dispatch(t0, launch_end, download_end, resolve_end):
    """One dispatch span from t0 with its six steps back to back."""
    return [ev(spans.DISPATCH, t0, resolve_end + 1),
            ev("frontend.coalesce", t0, t0 + 1),
            ev("engine.prepare", t0 + 1, t0 + 3),
            ev("engine.upload", t0 + 3, t0 + 4),
            ev("engine.launch", t0 + 4, launch_end),
            ev("engine.download", launch_end, download_end),
            ev("frontend.resolve", download_end, resolve_end)]


def hand_trace(with_dispatches=True):
    """Window 0-100 ms. Device 0 runs ops at 10-20, 30-40 and 70-80 ms;
    device 1 is busy throughout. Dispatches A (5-43) and B (60-89) lie
    inside the window; D (-10-2) and C (95-110) are cut by its edges;
    one download span (50-52) belongs to no dispatch."""
    ops = {0: [ev("%a.1 = f32[1] a()", 10, 20), ev("%b.1 = f32[1] b()", 30, 40),
               ev("%c.1 = f32[1] c()", 70, 80)],
           1: [ev("%d.1 = f32[1] d()", 0, 100)]}
    host = [ev(tf.WINDOW, 0, 100), ev("bench:submit", 0, 1)]
    if with_dispatches:
        host += (dispatch(5, 11, 41, 42)            # A: prep 6, wait 30, 1
                 + dispatch(60, 67, 85, 88)         # B: prep 7, wait 18, 3
                 + dispatch(-10, -4, 0.5, 1)        # D: starts before 0
                 + dispatch(95, 99, 108, 109)       # C: ends after 100
                 + [ev("engine.download", 50, 52)])
    return tf.from_events(ops, {}, host)


def read(name, tr):
    return harness.plugin("metrics", name).read(types.SimpleNamespace(trace=tr))


def test_dispatches_inside_the_window_hold_their_own_steps():
    ds = spans.dispatches(hand_trace())
    assert [d.span.start // MS for d in ds] == [5, 60]
    for d in ds:
        assert tuple(sorted(d.steps, key=lambda s: d.steps[s][0].start)) \
            == spans.STEPS
        assert all(len(v) == 1 for v in d.steps.values())
    assert ds[0].total_ns("engine.download") == 30 * MS
    assert ds[1].end_of("engine.launch") == 67 * MS
    assert ds[0].total_ns("no.such.span") is None


def test_a_step_that_outlasts_its_dispatch_is_not_its_own():
    host = [ev(tf.WINDOW, 0, 100), ev(spans.DISPATCH, 10, 20),
            ev("engine.download", 15, 25), ev("frontend.resolve", 12, 14)]
    (d,) = spans.dispatches(tf.from_events({}, {}, host))
    assert set(d.steps) == {"frontend.resolve"}


def test_per_dispatch_means_leave_out_dispatches_cut_by_the_window():
    tr = hand_trace()
    assert read("host_prep_ms.online", tr) == pytest.approx(6.5)
    assert read("result_wait_ms.online", tr) == pytest.approx(24.0)
    assert read("resolve_ms.online", tr) == pytest.approx(2.0)


def test_idle_splits_into_inside_and_outside_dispatches():
    """Device 0 idles 0-10, 20-30, 40-70 and 80-100 (70 ms). Dispatches
    are open 0-2 (D, clipped), 5-43, 60-89 and 95-100 (C, clipped): 44 ms
    of the idle time falls inside one, 26 ms outside."""
    tr = hand_trace()
    online = read("idle_in_dispatch_share.online", tr)
    assert online == pytest.approx(2 + 5 + 10 + 3 + 10 + 9 + 5)
    assert 100.0 * (1 - tf.busy_s(tr, 0) / tr.window_s) == pytest.approx(70)
    # the bulk reader averages the complete chips: chip 1 never idles
    assert read("idle_in_dispatch_share.bulk", tr) == pytest.approx(online / 2)


@pytest.mark.parametrize("name", READERS)
def test_a_trace_without_dispatch_spans_reads_nothing(name):
    assert read(name, hand_trace(with_dispatches=False)) is None
    assert read(name, None) is None


def test_idle_share_without_device_events_reads_nothing():
    host = [ev(tf.WINDOW, 0, 100)] + dispatch(5, 11, 41, 42)
    tr = tf.from_events({}, {}, host)
    assert read("idle_in_dispatch_share.online", tr) is None
    assert read("host_prep_ms.online", tr) == pytest.approx(6.0)
