"""Reduction of a profiler trace to device busy time, idle gaps, kernel
time and program executions.

`load` reads the `.xplane.pb` file that `jax.profiler` writes into plain
events; everything else works on those events, so the tests can check the
arithmetic on a trace recorded on the chip and on hand-made events alike.

A device is a plane named `/device:TPU:<n>`. Its `XLA Ops` line holds one
event per operation that ran, its `XLA Modules` line one per program
execution. Host threads are the lines of `/host:CPU`; the harness marks
the traced interval with a `bench:traced_window` annotation and its own
calls into the frontend with `bench:<what>` annotations, and JAX adds its
own host events beside them.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench:traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: int            # ns, on the trace's common clock
    end: int
    thread: str = ""

    @property
    def dur(self) -> int:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The operation's own name: an XLA op event is named by its HLO
        instruction, `%name = type op(operands...)`, whose operands name
        other operations."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Ev]]          # device ordinal -> XLA Ops events
    modules: Dict[int, List[Ev]]      # device ordinal -> XLA Modules events
    host: List[Ev]                    # every host-thread event
    window: Tuple[int, int]           # the traced interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _events(line, thread=""):
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Ev(e.name, start, start + int(e.duration_ns), thread))
    return out


def load(path: str) -> Trace:
    """Events of one `.xplane.pb` or `.xplane.pb.gz` file, or of the only
    `.xplane.pb` under a directory."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        if not path.endswith(".pb"):
            found = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
            if len(found) != 1:
                raise FileNotFoundError(
                    f"{len(found)} xplane files under {path}")
            path = found[0]
        pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = _events(line)
                elif line.name == "XLA Modules":
                    modules[dev] = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += _events(line, line.name)
    return from_events(ops, modules, host)


def from_events(ops, modules, host) -> Trace:
    wins = [e for e in host if e.name == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, "
                         f"found {len(wins)}")
    order = lambda e: (e.start, -e.end)      # an enclosing op comes first
    return Trace({d: sorted(v, key=order) for d, v in ops.items()},
                 {d: sorted(v, key=order) for d, v in modules.items()},
                 host, (wins[0].start, wins[0].end))


# ------------------------------------------------------------ intervals
def union(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_s(tr: Trace, dev: int) -> float:
    """Seconds of the traced window in which an operation ran on `dev`."""
    lo, hi = tr.window
    return sum(e - s for s, e in union(
        ((o.start, o.end) for o in tr.ops.get(dev, [])), lo, hi)) * 1e-9


def complete_devices(tr: Trace) -> List[int]:
    """Devices whose trace holds at least half the program executions of
    the device with the most; a device below that has lost events."""
    lo, hi = tr.window
    n = {d: sum(1 for m in tr.modules.get(d, []) if m.end > lo and m.start < hi)
         for d in tr.ops}
    top = max(n.values(), default=0)
    return sorted(d for d in tr.ops if 2 * n[d] >= top)


def idle_gaps(tr: Trace, dev: int) -> List[Tuple[int, int]]:
    """Intervals of the traced window in which `dev` ran nothing."""
    lo, hi = tr.window
    gaps, t = [], lo
    for s, e in union(((o.start, o.end) for o in tr.ops.get(dev, [])),
                      lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_doing(tr: Trace, s: int, e: int) -> str:
    """What the host was doing in [s, e): the host event, other than the
    window's own annotation, that best matches the interval, scored by
    overlap / max(event length, interval length). An event that fills the
    gap scores 1; a long wait that spans it, or a short call inside it,
    scores less."""
    best, name = 0.0, "host idle"
    span = max(e - s, 1)
    for h in tr.host:
        if h.name == WINDOW:
            continue
        ov = min(h.end, e) - max(h.start, s)
        if ov > 0:
            score = ov / max(h.dur, span)
            if score > best:
                best, name = score, h.name
    return name


def named_gaps(tr: Trace, dev: int, n: int = 10) -> List[list]:
    """The `n` longest idle gaps of `dev`, each named by host activity."""
    gaps = sorted(idle_gaps(tr, dev), key=lambda g: g[0] - g[1])[:n]
    return [[host_doing(tr, s, e), (e - s) * 1e-9] for s, e in gaps]


_HLO = re.compile(r"^%([\w\-]+?)(?:\.\d+)* = \(?([a-z0-9]+\[[^\]]*\])")


def op_label(ev: Ev) -> str:
    """A stable label for a device operation: its HLO opcode (or kernel
    name) and the type of its first result, as in `sort f32[128,26848]`;
    the instance number and the layout are dropped."""
    m = _HLO.match(ev.name)
    return f"{m.group(1)} {m.group(2)}" if m else ev.name[:80]


def leaf_ops(evs: List[Ev]) -> List[Ev]:
    """Operations that hold no other operation: a loop or call on the XLA
    Ops line encloses the operations of its body."""
    out = []
    for i, o in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start < o.end and nxt.end <= o.end:
            continue
        out.append(o)
    return out


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """Device operations by total seconds inside the window, summed over
    devices; enclosing loops are left out so that no time counts twice."""
    lo, hi = tr.window
    tot: Dict[str, int] = {}
    for evs in tr.ops.values():
        for o in leaf_ops(evs):
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                tot[op_label(o)] = tot.get(op_label(o), 0) + d
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def kernel_ops(tr: Trace, dev: int, pattern: str) -> List[Ev]:
    """Operations of `dev` whose own name matches `pattern` (not those
    that only take such an operation's result as an operand)."""
    rx = re.compile(pattern)
    return [o for o in tr.ops.get(dev, []) if rx.search(o.op)]


def executions_inside(tr: Trace, dev: int,
                      holding: Optional[str] = None) -> List[Ev]:
    """Program executions of `dev` that lie wholly inside the window; with
    `holding`, only those of the program that holds most of the time of
    operations matching that pattern."""
    lo, hi = tr.window
    mods = [m for m in tr.modules.get(dev, []) if m.start >= lo and m.end <= hi]
    if holding is None or not mods:
        return mods
    per: Dict[str, int] = {}
    for k in kernel_ops(tr, dev, holding):
        for m in mods:
            if m.start <= k.start and k.end <= m.end:
                per[m.name] = per.get(m.name, 0) + k.dur
                break
    if not per:
        return []
    name = max(per, key=per.get)
    return [m for m in mods if m.name == name]


def within(evs: List[Ev], outer: List[Ev]) -> List[Ev]:
    """Events of `evs` that lie inside one of `outer`."""
    spans = sorted((o.start, o.end) for o in outer)
    out, j = [], 0
    for e in sorted(evs, key=lambda x: x.start):
        while j < len(spans) and spans[j][1] < e.start:
            j += 1
        if j < len(spans) and spans[j][0] <= e.start and e.end <= spans[j][1]:
            out.append(e)
    return out
