"""From a profiler trace to busy time, idle gaps and program times.

Busy is the union of the intervals in which an operation ran on a device;
the idle share is 1 - busy / window. Each idle gap is named by the innermost
host span (written by the benchmark with ``jax.profiler.TraceAnnotation``)
that holds the gap's midpoint, so the breakdown says what the host was doing
while the chip waited. With several chips, busy time is averaged over them.

The reduction works on plain ``(name, start_ns, end_ns)`` tuples; ``load``
builds them from the ``.xplane.pb`` that ``jax.profiler`` writes.

    python3 bench/benchlib/trace.py <dir-or-xplane.pb>   # print the trace's layout
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import heapq
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]  # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class TraceData:
    device_ops: List[List[Interval]]  # per device, op-level events
    modules: List[List[Interval]]  # per device, program executions
    host_spans: List[Interval]  # the benchmark's spans, main thread


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


@functools.lru_cache(maxsize=None)
def short_op(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), kind=kOutput`` -> ``fusion.12
    (fusion, kOutput)``: an XLA op event's instruction name and opcode."""
    m = re.match(r"%?([^\s=]+) = ", name)
    if not m:
        return name[:80]
    rhs = name[m.end():]
    op = re.search(r"[\]\}\)] ([a-z][\w\-]*)\(", rhs)
    kind = re.search(r"kind=(k\w+)", rhs)
    return f"{m.group(1)} ({op.group(1) if op else '?'}{', ' + kind.group(1) if kind else ''})"


def _events(line) -> List[Interval]:
    return [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def load(path: str) -> TraceData:
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    ops: List[List[Interval]] = []
    modules: List[List[Interval]] = []
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops.append(_events(lines[OPS_LINE]) if OPS_LINE in lines else [])
            modules.append(_events(lines[MODULES_LINE]) if MODULES_LINE in lines else [])
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                ev = _events(ln)
                if any(name == WINDOW_SPAN for name, _, _ in ev):
                    host = ev
    return TraceData(ops, modules, host)


def merge(intervals: Sequence[Interval], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of the intervals, clipped to [lo, hi], sorted."""
    clipped = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals if e > lo and s < hi)
    out: List[Tuple[float, float]] = []
    for s, e in clipped:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Interval]) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self time) of each event: its duration less that of
    the events nested directly inside it (a loop op holds its body's ops)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [e - s for _, s, e in events]
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, events[stack[-1]][2]) - s
        stack.append(i)
    return [(n, s, e, own[i]) for i, (n, s, e) in enumerate(events)]


def covered(busy: Sequence[Tuple[float, float]], lo: float, hi: float,
            starts: Optional[Sequence[float]] = None) -> float:
    """Length of [lo, hi] that the merged intervals ``busy`` cover. With
    ``starts``, the intervals' start times, only those that can overlap
    [lo, hi] are visited."""
    i, j = 0, len(busy)
    if starts is not None:
        i, j = max(bisect.bisect_right(starts, lo) - 1, 0), bisect.bisect_left(starts, hi)
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in busy[i:j])


def idle_gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(spans: Sequence[Interval], t: float) -> Optional[str]:
    return innermost_each(spans, [t])[0]


def innermost_each(spans: Sequence[Interval], times: Sequence[float]) -> List[Optional[str]]:
    """For each of ``times``, the shortest span that holds it (``s <= t <
    e``; the first listed among equals), in one sweep over the times in
    order."""
    out: List[Optional[str]] = [None] * len(times)
    by_start = sorted(range(len(spans)), key=lambda i: spans[i][1])
    ends: List[Tuple[float, int]] = []  # heap of the open spans' (end, index)
    open_: set = set()
    k = 0
    for q in sorted(range(len(times)), key=times.__getitem__):
        t = times[q]
        while k < len(by_start) and spans[by_start[k]][1] <= t:
            heapq.heappush(ends, (spans[by_start[k]][2], by_start[k]))
            open_.add(by_start[k])
            k += 1
        while ends and ends[0][0] <= t:
            open_.discard(heapq.heappop(ends)[1])
        if open_:
            best = min(open_, key=lambda i: (spans[i][2] - spans[i][1], i))
            out[q] = spans[best][0]
    return out


def window_of(td: TraceData) -> Tuple[float, float]:
    for name, s, e in td.host_spans:
        if name == WINDOW_SPAN:
            return s, e
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over devices
    idle_by_span: List[Tuple[str, float]]  # seconds of idle device, by host span
    top_ops: List[Tuple[str, float]]  # seconds on the device, by op name
    busy: List[List[Tuple[float, float]]]  # merged busy intervals per device
    lo: float
    hi: float

    @functools.cached_property
    def starts(self) -> List[float]:
        """Start times of device 0's busy intervals."""
        return [s for s, _ in self.busy[0]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(td: TraceData, lo: Optional[float] = None, hi: Optional[float] = None,
           top: int = 10) -> Reduction:
    if lo is None or hi is None:
        lo, hi = window_of(td)
    if not td.device_ops or not any(td.device_ops):
        raise ValueError("the trace holds no device operation")
    busy = [merge(ops, lo, hi) for ops in td.device_ops]
    busy_s = sum(covered(b, lo, hi) for b in busy) / len(busy) / 1e9
    idle: Dict[str, float] = collections.defaultdict(float)
    gaps = idle_gaps(busy[0], lo, hi)
    names = innermost_each(td.host_spans, [(gs + ge) / 2 for gs, ge in gaps])
    for (gs, ge), name in zip(gaps, names):
        idle[name or "outside any span"] += (ge - gs) / 1e9
    per_op: Dict[str, float] = collections.defaultdict(float)
    for name, s, e, own in self_times(td.device_ops[0]):
        if lo <= s < hi:
            per_op[short_op(name)] += own / 1e9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Reduction((hi - lo) / 1e9, busy_s, rank(idle), rank(per_op), busy, lo, hi)


def module_seconds(td: TraceData, prefix: str, lo: float, hi: float) -> float:
    """Device seconds of the programs whose name starts with ``prefix``
    (e.g. ``jit_step``), inside [lo, hi], averaged over devices."""
    if not td.modules:
        return 0.0
    tot = 0.0
    for mods in td.modules:
        tot += sum(max(0.0, min(e, hi) - max(s, lo)) for n, s, e in mods if n.startswith(prefix))
    return tot / len(td.modules) / 1e9


def span_idle_seconds(td: TraceData, red: Reduction, name: str) -> Tuple[float, int]:
    """Idle device seconds inside the host spans called ``name`` within the
    window, and how many such spans there were."""
    tot, n = 0.0, 0
    for sname, s, e in td.host_spans:
        if sname != name or s < red.lo or e > red.hi:
            continue
        n += 1
        tot += (e - s) - covered(red.busy[0], s, e, red.starts)
    return tot / 1e9, n


def _dump(path: str) -> None:
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        print("plane", repr(plane.name))
        for ln in plane.lines:
            ev = list(ln.events)
            names = collections.Counter(e.name for e in ev).most_common(8)
            span = (ev[0].start_ns, ev[-1].start_ns) if ev else ()
            print(f"  line {ln.name!r}: {len(ev)} events {span}; {names}")


if __name__ == "__main__":
    _dump(sys.argv[1])
