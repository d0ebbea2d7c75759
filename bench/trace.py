"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes (``/device:TPU:n``) carry an ``XLA Modules`` line, one event
per program execution (name ``<module>(<program id>)``, stat ``run_id``),
and an ``XLA Ops`` line, one event per operation. Host threads carry the
benchmark's own spans (``generate`` and ``queue_wait`` with a ``req`` stat,
``window``) and the runtime's launch chain: a ``PJRT_LoadedExecutable_Execute
linkage`` event on the launching thread (flow ``_p``), the matching
``PJRT_LoadedExecutable_Execute`` (flow ``_c``) and, in the same order as the
launches, ``DoEnqueueProgram`` with the ``run_id`` the device reports.
Following that chain ties every device execution to the request whose
``generate`` launched it; within one ``generate`` the engine's first jitted
program is the prefill and the ones after it are decode steps.

All times are nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

ENGINE_MODULE = "jit__lambda"   # the engine jits prefill and decode as lambdas
LAUNCH_LINK = "PJRT_LoadedExecutable_Execute linkage"
LAUNCH = "PJRT_LoadedExecutable_Execute"
ENQUEUE = "DoEnqueueProgram"


@dataclass
class Execution:
    """One program execution on a device."""
    start: float
    end: float
    module: str                  # e.g. "jit__lambda"
    program: str                 # module name with its program id
    run_id: Optional[int]
    req: Optional[int] = None    # request whose generate launched it
    ordinal: int = -1            # index among that request's engine programs

    @property
    def kind(self) -> str:
        if self.module != ENGINE_MODULE or self.req is None:
            return "other"
        return "prefill" if self.ordinal == 0 else "decode"


@dataclass
class HostEvent:
    start: float
    end: float
    name: str
    line: int
    stats: dict


@dataclass
class Trace:
    devices: int = 0
    executions: List[Execution] = field(default_factory=list)
    ops: List[Tuple[float, float, str]] = field(default_factory=list)
    host: List[HostEvent] = field(default_factory=list)

    def spans(self, name: str) -> List[HostEvent]:
        return sorted((e for e in self.host if e.name == name),
                      key=lambda e: e.start)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    launch_lines: Dict[int, List[HostEvent]] = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            tr.devices += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        st = _stats(ev)
                        name = ev.name
                        module = name.split("(", 1)[0]
                        tr.executions.append(Execution(
                            ev.start_ns, ev.start_ns + ev.duration_ns, module,
                            name, st.get("run_id")))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        tr.ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                       ev.name))
        elif plane.name == "/host:CPU":
            for li, line in enumerate(plane.lines):
                for ev in line.events:
                    he = HostEvent(ev.start_ns, ev.start_ns + ev.duration_ns,
                                   ev.name, li, _stats(ev))
                    tr.host.append(he)
                    if ev.name in (LAUNCH, ENQUEUE):
                        launch_lines[li].append(he)
    _link(tr, launch_lines)
    tr.executions.sort(key=lambda e: e.start)
    tr.ops.sort()
    return tr


def _link(tr: Trace, launch_lines: Dict[int, List[HostEvent]]) -> None:
    """Tie each device execution to the ``generate`` span that launched it.

    A launch (flow ``_c``, matching the launching thread's linkage ``_p``)
    and the enqueue that reports its ``run_id`` are tied by order: the
    device takes programs in the order they were launched, and the trace
    runs from before the window's first request to after its last answer,
    so no launch is cut in half at either end."""
    launches = sorted((e for es in launch_lines.values() for e in es
                       if e.name == LAUNCH), key=lambda e: e.start)
    enqueues = sorted((e for es in launch_lines.values() for e in es
                       if e.name == ENQUEUE), key=lambda e: e.start)
    run_of_flow = {la.stats.get("_c"): en.stats.get("run_id")
                   for la, en in zip(launches, enqueues)}
    by_run = {e.run_id: e for e in tr.executions if e.run_id is not None}
    by_line: Dict[int, List[HostEvent]] = defaultdict(list)
    for e in tr.host:
        if e.name == LAUNCH_LINK or (e.name == "generate" and "req" in e.stats):
            by_line[e.line].append(e)
    for events in by_line.values():
        events.sort(key=lambda e: (e.start, e.name != "generate"))
        gen, n = None, 0
        for e in events:
            if e.name == "generate":
                gen, n = e, 0
                continue
            if gen is None or e.start > gen.end:
                continue
            ex = by_run.get(run_of_flow.get(e.stats.get("_p")))
            if ex is None or ex.module != ENGINE_MODULE:
                continue
            ex.req, ex.ordinal = int(gen.stats["req"]), n
            n += 1


def union(intervals: List[Interval], lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Interval]:
    """The union of intervals, clipped to [lo, hi], as sorted disjoint ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def busy(tr: Trace, lo: float, hi: float) -> List[Interval]:
    """Intervals in [lo, hi] in which some operation ran on the device."""
    src = tr.ops or [(e.start, e.end, e.program) for e in tr.executions]
    return union([(a, b) for a, b, _ in src], lo, hi)


def window(tr: Trace) -> Interval:
    """The traced window: the benchmark's ``window`` span."""
    w = tr.spans("window")
    if not w:
        raise ValueError("trace holds no 'window' span")
    return w[0].start, w[0].end


def in_service(tr: Trace, lo: float, hi: float) -> List[Interval]:
    """Times in [lo, hi] at which at least one request was in ``generate``."""
    return union([(e.start, e.end) for e in tr.spans("generate")], lo, hi)


def _short(op: str) -> str:
    return op.split(" = ", 1)[0].lstrip("%")


# control flow whose event spans the operations of its body
_CONTAINERS = ("while", "conditional", "call")


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The device operations that took most time in [lo, hi], named by the
    kind of program they ran in (prefill, decode, other). Loops and other
    control flow are left out: their body's operations are counted."""
    starts = [e.start for e in tr.executions]
    total: Dict[str, float] = defaultdict(float)
    first = bisect.bisect_left(tr.ops, (lo - 1e9,))
    for a, b, name in tr.ops[first:]:
        if a >= hi:
            break
        if b <= lo or a >= hi or _short(name).startswith(_CONTAINERS):
            continue
        i = bisect.bisect_right(starts, a) - 1
        ex = tr.executions[i] if i >= 0 and tr.executions[i].end >= a else None
        kind = ex.kind if ex is not None else "other"
        total[f"{kind}:{_short(name)}"] += (min(b, hi) - max(a, lo)) * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """Idle device time while a request was in service, summed by what the
    host was doing: the innermost host event that covers the middle of each
    gap (benchmark spans, or the runtime's and the program's trace events)."""
    idle = intersect(in_service(tr, lo, hi),
                     complement(busy(tr, lo, hi), lo, hi))
    bucket = 10e6  # 10 ms
    index: Dict[int, List[HostEvent]] = defaultdict(list)
    for e in tr.host:
        if e.name != "window" and 0 < e.end - e.start < 1e9:
            for k in range(int(e.start // bucket), int(e.end // bucket) + 1):
                index[k].append(e)
    total: Dict[str, float] = defaultdict(float)
    for a, b in idle:
        mid = (a + b) / 2
        best = None
        for e in index.get(int(mid // bucket), ()):
            if e.start <= mid <= e.end and (
                    best is None or e.end - e.start < best.end - best.start):
                best = e
        total[best.name if best is not None else "generate"] += (b - a) * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def complement(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """[lo, hi] less sorted disjoint ``intervals``."""
    out, cur = [], lo
    for a, b in intervals:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out
