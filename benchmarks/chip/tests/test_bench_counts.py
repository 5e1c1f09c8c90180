"""The window kernel's work counts (counts.py) and the roofline reader:
work comes from the live sizes of the probed partitions and the codes'
information width, never from the padded capacity or the kernel's
layout."""
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import counts, harness, tracefile as tf  # noqa: E402

PEAK = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e15}
MS = 1_000_000


def padded_ids(sizes, cap):
    ids = np.full((len(sizes), cap), -1, np.int32)
    nxt = 0
    for p, s in enumerate(sizes):
        ids[p, :s] = np.arange(nxt, nxt + s)
        nxt += s
    return ids


def test_live_sizes_ignore_padding():
    sizes = [3, 0, 5, 1]
    for cap in (5, 8, 64):
        assert list(counts.live_sizes(padded_ids(sizes, cap))) == sizes


def test_probed_live_sums_the_top_partitions_by_inner_product():
    C = np.eye(4, dtype=np.float32)
    Q = np.array([[1.0, 0.9, 0.0, 0.0], [0.0, 0.0, 0.2, 1.0]], np.float32)
    live = counts.probed_live(Q, C, np.array([3, 0, 5, 1]), top_t=2)
    assert list(live) == [3 + 0, 1 + 5]


def test_window_bytes_count_codes_at_four_bits_tables_once_scores_once():
    # 100 live candidates, m = 25: 12.5 B of codes + 4 B of score each,
    # and one (25, 16) float32 table
    assert counts.window_bytes([100], 25)[0] == pytest.approx(
        100 * 16.5 + 25 * 16 * 4)
    assert counts.window_bytes([0], 25)[0] == 1600
    assert counts.window_ops([100], 25)[0] == 2500


def test_roofline_share_takes_the_slower_bound():
    # 1 GB at 1 GB/s is 1 s; in 2 s that is half the roofline
    assert counts.roofline_share(1e9, 1.0, 2.0, PEAK) == pytest.approx(50.0)
    # operations bound: 1e15 ops at 1e15/s is 1 s
    assert counts.roofline_share(1.0, 1e15, 4.0, PEAK) == pytest.approx(25.0)


def roofline_ctx(cap):
    """Two chips, each running two executions of 10 ms holding 4 ms of
    kernel; 8 queries per request; partitions of live sizes 3, 0, 5, 1."""
    sizes = [3, 0, 5, 1]
    ops, modules = {}, {}
    for d in (0, 1):
        ops[d], modules[d] = [], []
        for start in (10, 30):
            modules[d].append(tf.Ev("jit_search(7)", start * MS,
                                    (start + 10) * MS))
            ops[d].append(tf.Ev("%pq_score_window_pallas.3 = f32[4,512]",
                                (start + 2) * MS, (start + 6) * MS))
            ops[d].append(tf.Ev("%fusion.9 = f32[4,512] fusion("
                                "%pq_score_window_pallas.3)",
                                (start + 6) * MS, (start + 9) * MS))
    tr = tf.from_events(ops, modules, [tf.Ev(tf.WINDOW, 0, 100 * MS)])
    pool = np.eye(4, dtype=np.float32)
    qidx = np.array([0, 2, 2, 3])
    idx_ids = padded_ids(sizes, cap)
    index = types.SimpleNamespace(
        centroids=np.eye(4, dtype=np.float32),
        live=counts.live_sizes(idx_ids), top_t=1, m=25, centres=16)
    res = types.SimpleNamespace(ids=np.zeros((4, 10), np.int32),
                                scores=np.zeros((4, 10), np.float32))
    from benchmarks.chip.window import Window
    w = Window(0.0, 1.0, np.zeros(1), np.zeros(1), np.ones(1), [qidx], [res])
    return types.SimpleNamespace(trace=tr, window=w, pool=pool, index=index,
                                 traffic={"queries_per_request": 8},
                                 peak=PEAK)


def test_roofline_reader_counts_live_work_and_kernel_time_only():
    reader = harness.plugin("metrics", "pq_window_roofline.bulk")
    ctx = roofline_ctx(cap=16)
    # queries probe one partition each: live 3, 5, 5, 1 -> mean 3.5
    per_q = 3.5 * 16.5 + 1600
    queries = 2 * 2 * (8 / 2)          # executions x chips x rows per chip
    seconds = 2 * 2 * 0.004            # the kernel's own events only
    want = 100 * queries * per_q / PEAK["hbm_bytes_per_s"] / seconds
    assert reader.read(ctx) == pytest.approx(want)


def test_roofline_reader_is_unmoved_by_capacity_padding():
    reader = harness.plugin("metrics", "pq_window_roofline.bulk")
    assert reader.read(roofline_ctx(cap=16)) == pytest.approx(
        reader.read(roofline_ctx(cap=4096)))


def test_roofline_reader_reads_nothing_without_a_trace():
    reader = harness.plugin("metrics", "pq_window_roofline.bulk")
    ctx = roofline_ctx(cap=16)
    ctx.trace = None
    assert reader.read(ctx) is None


def test_step_device_ms_is_the_slowest_chips_execution():
    reader = harness.plugin("metrics", "step_device_ms.bulk")
    ctx = roofline_ctx(cap=16)
    ctx.trace.modules[1][0] = tf.Ev("jit_search(7)", 10 * MS, 24 * MS)
    ctx.trace.ops[1][0] = tf.Ev("%pq_score_window_pallas.3 = f32[4,512]",
                                12 * MS, 16 * MS)
    assert reader.read(ctx) == pytest.approx(12.0)
