"""Share (%) of the HBM roofline reached by the window-scoring kernel.

Kernel time: the trace's operations matching the kernel's name, inside
executions of the search program that lie wholly in the traced stretch.
Work (counts.py): per query, the live candidates of its probed
partitions, codes at 4 bits, its lookup table once and one score per
candidate; the mean over the queries the window answered. Each execution
serves one request, split evenly over the chips by the replica fan-out.
"""
KERNEL = r"pq_score_window"


def read(ctx):
    from benchmarks.chip import counts, tracefile
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    qidx, _, _ = ctx.window.answers()
    if not qidx.size:
        return None
    ix = ctx.index
    live = counts.probed_live(ctx.pool, ix.centroids, ix.live, ix.top_t)[qidx]
    per_q_bytes = float(counts.window_bytes(live, ix.m, ix.centres).mean())
    per_q_ops = float(counts.window_ops(live, ix.m).mean())
    rows = ctx.traffic["queries_per_request"] / len(tr.ops)
    seconds, queries = 0.0, 0.0
    for dev in sorted(tr.ops):
        ex = tracefile.executions_inside(tr, dev, KERNEL)
        ks = tracefile.within(tracefile.kernel_ops(tr, dev, KERNEL), ex)
        seconds += sum(k.dur for k in ks) * 1e-9
        queries += len(ex) * rows
    if seconds <= 0:
        return None
    return counts.roofline_share(queries * per_q_bytes, queries * per_q_ops,
                                 seconds, ctx.peak)
