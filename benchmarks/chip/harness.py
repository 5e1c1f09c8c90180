"""One run of one benchmark cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name: `BENCHMARK.json`
names the cell's configuration file and traffic mix, `traffic/<mix>.json`
names the loop (`loops/<kind>.py`) that generates it, and each metric the
cell reports is read by `metrics/<name>.py`. Adding a configuration, a mix,
a cell or a metric adds files and entries and edits none.

A run: the corpus and query pool are made on the device from the seed
(`corpus.py`), the index is built and packed, the serving frontend is
started and every shape the cell's traffic uses is served once (set-up),
then the loop drives the frontend for `--seconds`. With `--trace 1` a
steady stretch of the window runs under the profiler. After the window
the exact reference is computed and every answer the window returned is
compared with it (`reference.py`); earlier stdout lines report the parts
of set-up and the diagnostics, stderr ends with each compared number
beside its limit, and the last stdout line is the result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_SKIP_S = 1.0            # steady state before the profiler starts
TRACE_S = 3.0                 # length of the profiled stretch


class NoChip(RuntimeError):
    """The run cannot measure: no TPU, too few chips, or no peaks."""


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- lookup
def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(BENCHMARK.json, cell, configuration, traffic) of a cell by name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT, conf["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return bench, cell, cfg, traffic


def plugin(kind: str, name: str):
    """The module `<kind>/<name>.py` (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# --------------------------------------------------------------- device
def chips(n: int, peaks: dict):
    """The visible devices, or NoChip where this run cannot measure."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"no peaks for device_kind {devs[0].device_kind!r}")
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at $JAX_COMPILATION_CACHE_DIR,
    else at <checkout>/.jax_cache; every program is kept."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Compiles:
    """Counts programs compiled (or fetched from the persistent cache)
    and persistent-cache hits and misses."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)
        return self

    def _dur(self, event, secs, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += secs

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"programs": self.n, "seconds": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


class GcPauses:
    """Host garbage-collector pauses, by generation, while installed."""

    def __init__(self):
        self.pauses = []          # (generation, seconds)
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def summary(self) -> dict:
        out = {}
        for g in sorted({g for g, _ in self.pauses}):
            d = [t for gg, t in self.pauses if gg == g]
            out[f"gen{g}"] = {"count": len(d), "max_ms": max(d) * 1e3,
                              "total_ms": sum(d) * 1e3}
        return out


def bytes_in_use(dev, key="bytes_in_use") -> int:
    return int((dev.memory_stats() or {}).get(key, 0))


# --------------------------------------------------------------- set-up
def build(cfg: dict, X):
    """The configuration's engine, its packed snapshot on the device."""
    import jax
    from repro.serve.engine import AnnEngine
    t = time.perf_counter()
    eng = AnnEngine.build(
        jax.random.PRNGKey(cfg["build_seed"]), X, cfg["partitions"],
        spill_mode=cfg["spill"], lam=cfg["lambda"],
        pq_subspaces=cfg["pq_subspaces"], top_t=cfg["top_t"],
        rerank_budget=cfg["rerank_budget"], bq=cfg["bq"],
        train_sample=cfg["train_sample"], shard_size=cfg["shard_size"])
    jax.block_until_ready(eng.index.pack())
    build_s = time.perf_counter() - t
    centres = eng.index.pq.centers.shape[1] if eng.index.pq else 0
    if centres != cfg["pq_centres"]:
        raise RuntimeError(f"the index has {centres} PQ centres, the "
                           f"configuration states {cfg['pq_centres']}")
    return eng, build_s


def prepare(cfg: dict, traffic: dict, seed: int, loop):
    """Corpus, build, frontend and warm-up; returns the served state."""
    from benchmarks.chip import corpus, counts
    from repro.serve.frontend import ServingFrontend
    s = types.SimpleNamespace(parts={})
    t = time.perf_counter()
    s.X, s.pool = corpus.make(cfg, seed)
    s.parts["corpus_s"] = time.perf_counter() - t
    s.engine, s.parts["build_s"] = build(cfg, s.X)
    s.fe = ServingFrontend(s.engine, **cfg["frontend"])
    t = time.perf_counter()
    loop.warm(s.fe, s.pool, traffic)
    s.parts["warmup_s"] = time.perf_counter() - t
    idx = s.engine.index
    s.index = types.SimpleNamespace(
        centroids=np.asarray(idx.centroids),
        live=counts.live_sizes(idx.part_ids),
        top_t=cfg["top_t"], m=cfg["pq_subspaces"], centres=cfg["pq_centres"],
        n_alive=s.engine.n_alive)
    return s


# --------------------------------------------------------------- tracing
class Tracer:
    """Profiles the window from a thread of its own. The profiler starts
    TRACE_SKIP_S after the window does and stops only after the window has
    closed, so that stopping it (seconds of host work) stalls no request;
    the stretch the metrics read, TRACE_S long (less in a short window),
    is marked by a `bench:traced_window` annotation, and the frontend's
    counters are read at both of its ends."""

    def __init__(self, fe, seconds: float):
        from benchmarks.chip import tracefile
        self.fe = fe
        self.skip = min(TRACE_SKIP_S, seconds / 4)
        self.length = max(min(TRACE_S, seconds - 2 * self.skip), 0.25)
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.window_name = tracefile.WINDOW
        self.closed = threading.Event()
        self.thread = None

    def on_start(self, t0: float) -> None:
        self.thread = threading.Thread(target=self._run, args=(t0,),
                                       name="bench-tracer")
        self.thread.start()

    def _run(self, t0):
        import jax
        time.sleep(max(t0 + self.skip - time.perf_counter(), 0))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host TraceMe events only
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(self.window_name):
                self.lo, self.stats0 = time.perf_counter(), dict(self.fe.stats)
                time.sleep(self.length)
                self.hi, self.stats1 = time.perf_counter(), dict(self.fe.stats)
            self.closed.wait()
        finally:
            jax.profiler.stop_trace()

    def finish(self):
        """After the window: stop the profiler and reduce its trace."""
        from benchmarks.chip import tracefile
        self.closed.set()
        self.thread.join()
        try:
            return tracefile.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _span(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


# --------------------------------------------------------------- one run
def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True):
    """One run; returns (result dict, checks). `require_tpu=False` lets a
    test drive the rest of a run on the CPU."""
    from benchmarks.chip import reference, tracefile
    bench, cell, cfg, traffic = load_cell(workload)
    peaks = load_json(HERE, "peaks.json")
    import jax
    devs = chips(cell["chips"], peaks) if require_tpu else jax.devices()
    log(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    log(f"compile_cache {enable_compile_cache()}")
    comp = Compiles().install()
    loop = plugin("loops", traffic["kind"])

    s = prepare(cfg, traffic, seed, loop)
    gc.collect()
    hbm = bytes_in_use(devs[0])
    at_setup = comp.snapshot()
    log(f"setup corpus_s={s.parts['corpus_s']:.3f} "
        f"build_s={s.parts['build_s']:.3f} "
        f"warmup_s={s.parts['warmup_s']:.3f} compiles={json.dumps(at_setup)}")
    log(f"index n_alive={s.index.n_alive} partitions={len(s.index.live)} "
        f"capacity={s.engine.index.part_ids.shape[1]} "
        f"live_slots={int(s.index.live.sum())} bytes_in_use={hbm}")

    tracer = Tracer(s.fe, seconds) if trace else None
    with GcPauses() as gcp:
        w = loop.run(s.fe, s.pool, traffic, seed, seconds, _span(trace),
                     tracer.on_start if tracer else (lambda t0: None))
    setup_s = w.t0 - t_start
    in_window = comp.snapshot()
    log(f"window compiles={in_window['programs'] - at_setup['programs']} "
        f"cache_misses={in_window['cache_misses'] - at_setup['cache_misses']}")
    log(f"host_gc_in_window {json.dumps(gcp.summary())}")
    lat = w.latency_s()
    lat = lat[np.isfinite(lat)] * 1e3
    if lat.size:
        log("latency_ms " + json.dumps(
            {f"p{q}": float(np.percentile(lat, q)) for q in (50, 90, 95, 99, 99.9)}
            | {"max": float(lat.max()), "requests": int(lat.size)}))
    if hasattr(loop, "lateness_ms"):
        log(f"generator_lateness_ms {json.dumps(loop.lateness_ms(w))}")
    fe_stats = dict(s.fe.stats)
    log(f"frontend_stats {json.dumps(fe_stats, sort_keys=True)}")
    tr = tracer.finish() if tracer else None
    peak = max(bytes_in_use(d, "peak_bytes_in_use") for d in devs)

    # the program's state goes before the reference runs on the device
    s.fe.close()
    X, pool, index, parts = s.X, s.pool, s.index, s.parts
    del s
    gc.collect()

    t = time.perf_counter()
    exact_ids, _ = reference.exact_topk_device(X, pool)
    qidx, ids, scores = w.answers()
    read = reference.readings(X, pool, qidx, ids, scores, exact_ids)
    answered = int(w.answered().sum())
    counters = {"unanswered": w.attempted - answered,
                "duplicate_ids": read["duplicate_ids"]}
    for key in ("failures", "retries", "degraded", "rejected", "shed",
                "expired"):
        counters[f"frontend_{key}"] = fe_stats[key]
    ok, checks = reference.check(read, cfg["limits"], counters)
    log(f"reference_s {time.perf_counter() - t:.3f} answers={len(qidx)}")

    ctx = types.SimpleNamespace(
        window=w, traffic=traffic, pool=pool, setup_s=setup_s, parts=parts,
        hbm_bytes=hbm, index=index, quality=read, trace=tr, tracer=tracer,
        peak=peaks.get(devs[0].device_kind))
    result_metrics = {}
    for m in cell_metrics(bench, workload, trace):
        v = plugin("metrics", m["name"]).read(ctx)
        if v is not None:
            result_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": w.attempted,
              "failed": w.attempted - answered, "metrics": result_metrics,
              "device": device}
    if tr is not None:
        used = tracefile.complete_devices(tr) or [0]
        if len(used) < len(tr.ops):
            log(f"trace lost events of devices "
                f"{sorted(set(tr.ops) - set(used))}; left out of busy_s")
        device["busy_s"] = float(np.mean([tracefile.busy_s(tr, d)
                                          for d in used]))
        device["window_s"] = tr.window_s
        result["breakdown"] = {
            "device_ops": tracefile.top_ops(tr),
            "idle_gaps": tracefile.named_gaps(tr, used[0])}
    # an id that is no base vector reads an infinite gap; JSON holds no
    # infinity, so the line carries the largest float in its place
    result["checks"] = {k: {"value": min(c["value"], sys.float_info.max),
                            "limit": c["limit"]}
                        for k, c in checks.items()}
    return result, checks


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, c in checks.items():
        rel = "<=" if c["at_most"] else ">="
        print(f"check {name} {c['value']!r} {rel} {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0
