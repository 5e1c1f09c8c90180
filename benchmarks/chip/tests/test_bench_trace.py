"""The trace-to-metrics reduction (tracefile.py), on hand-made events and
on a trace recorded on a TPU v5e: 0.8 s of `glove100` under the
`bulk1024` closed loop on one chip."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import tracefile as tf  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "glove100_bulk1024_v5e.xplane.pb.gz")
MS = 1_000_000


def ev(name, start_ms, end_ms, thread=""):
    return tf.Ev(name, int(start_ms * MS), int(end_ms * MS), thread)


def hand_trace():
    """Device 0: a program at 10-40 ms holding a loop (12-38) that holds
    two kernels; device 1: one op at 50-60 ms. Window 0-100 ms."""
    ops = {0: [ev("%while = (s32[]) while()", 12, 38),
               ev("%pq_score_window_pallas.1 = f32[8,512]{1,0} custom-call()",
                  14, 20),
               ev("%fusion.2 = f32[8,512]{1,0} fusion(%pq_score_window_pallas.1)",
                  20, 24),
               ev("%pq_score_window_pallas.1 = f32[8,512]{1,0} custom-call()",
                  26, 30)],
           1: [ev("%copy.3 = f32[100,4]{1,0} copy(f32[100,4]{0,1})", 50, 60)]}
    modules = {0: [ev("jit_search(1)", 10, 40), ev("jit_search(1)", 95, 120)],
               1: [ev("jit_search(1)", 50, 60)]}
    host = [ev(tf.WINDOW, 0, 100, "python3"),
            ev("bench:wait", 0, 100, "python3"),
            ev("np.asarray(jax.Array)", 41, 49, "python3")]
    return tf.from_events(ops, modules, host)


def test_busy_union_and_gaps_on_hand_made_events():
    tr = hand_trace()
    assert tr.window_s == pytest.approx(0.1)
    # nested loop and kernels count once: 12..38 plus nothing outside
    assert tf.busy_s(tr, 0) == pytest.approx(0.026)
    assert tf.busy_s(tr, 1) == pytest.approx(0.010)
    assert tf.idle_gaps(tr, 0) == [(0, 12 * MS), (38 * MS, 100 * MS)]
    # the gap 38..100 is best matched by the download only partly; the
    # window-wide wait scores overlap / its own length
    gaps = tf.named_gaps(tr, 0)
    assert gaps[0][1] == pytest.approx(0.062)
    assert gaps[0][0] == "bench:wait"


def test_gap_named_by_the_host_event_that_fills_it():
    ops = {0: [ev("%a.1 = f32[1] a()", 0, 10), ev("%b.1 = f32[1] b()", 18, 30)]}
    host = [ev(tf.WINDOW, 0, 30), ev("bench:wait", 0, 30),
            ev("np.asarray(jax.Array)", 9, 19)]
    tr = tf.from_events(ops, {}, host)
    assert tf.named_gaps(tr, 0) == [["np.asarray(jax.Array)",
                                     pytest.approx(0.008)]]


def test_kernel_matched_by_its_own_name_not_by_an_operand():
    tr = hand_trace()
    ks = tf.kernel_ops(tr, 0, "pq_score_window")
    assert [(k.start // MS, k.end // MS) for k in ks] == [(14, 20), (26, 30)]


def test_executions_inside_the_window_and_their_kernels():
    tr = hand_trace()
    ex = tf.executions_inside(tr, 0, "pq_score_window")
    assert [(e.start // MS, e.end // MS) for e in ex] == [(10, 40)]
    assert len(tf.within(tf.kernel_ops(tr, 0, "pq_score_window"), ex)) == 2
    assert tf.executions_inside(tr, 1, "pq_score_window") == []


def test_top_ops_leave_out_enclosing_loops_and_label_by_op_and_type():
    tr = hand_trace()
    top = dict((k, v) for k, v in tf.top_ops(tr))
    assert "while s32[]" not in top
    assert top["pq_score_window_pallas f32[8,512]"] == pytest.approx(0.010)
    assert top["copy f32[100,4]"] == pytest.approx(0.010)
    assert sum(top.values()) == pytest.approx(0.024)


def test_a_device_that_lost_events_is_not_complete():
    ops = {d: [ev("%a.1 = f32[1] a()", 1, 2)] for d in range(4)}
    modules = {d: [ev("jit_f(1)", 10 * i, 10 * i + 5) for i in range(10)]
               for d in (1, 2, 3)}
    modules[0] = [ev("jit_f(1)", 0, 5), ev("jit_f(1)", 50, 55)]
    tr = tf.from_events(ops, modules, [ev(tf.WINDOW, 0, 100)])
    assert tf.complete_devices(tr) == [1, 2, 3]


def test_one_window_annotation_is_required():
    with pytest.raises(ValueError):
        tf.from_events({}, {}, [])


def test_recorded_trace_reduces_to_the_known_shape_of_the_run():
    tr = tf.load(FIXTURE)
    assert sorted(tr.ops) == [0]
    assert tr.window_s == pytest.approx(0.801, abs=0.002)
    busy = tf.busy_s(tr, 0)
    assert 0.5 * tr.window_s < busy <= tr.window_s
    ex = tf.executions_inside(tr, 0, "pq_score_window")
    # each execution serves one 1,024-query request in 8 tiles of 128
    ks = tf.within(tf.kernel_ops(tr, 0, "pq_score_window"), ex)
    assert len(ex) >= 10 and len(ks) == 8 * len(ex)
    assert all(k.op.startswith("pq_score_window_pallas") for k in ks)
    per_ex_ms = [e.dur / MS for e in ex]
    assert 40 < min(per_ex_ms) <= max(per_ex_ms) < 60
    top = dict((k, v) for k, v in tf.top_ops(tr))
    assert "pq_score_window_pallas f32[128,27136]" in top
    assert sum(top.values()) <= busy + 1e-9
    gaps = tf.named_gaps(tr, 0)
    assert len(gaps) == 10 and all(0 < g[1] < 0.05 for g in gaps)
    assert tf.complete_devices(tr) == [0]
