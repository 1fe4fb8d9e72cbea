"""Device time by the program's named scopes, from the traced run's trace.

The program marks its layers with ``jax.named_scope`` (``paged.materialize``,
``attention``, ``mod.router``, ...). XLA keeps the scope path in the
``op_name`` metadata of every HLO instruction, and the profiler stores each
program's optimized HLO in the trace's ``/host:metadata`` plane, keyed by the
same name (``jit_step(12)``) as the program's executions on the device's
``XLA Modules`` line. So each device op event, named by its HLO instruction,
maps to a scope path with nothing but the ``.xplane.pb`` itself: a small
protobuf wire reader below takes out the two planes it needs (no protobuf
schema is imported).

A scope's time in a program is the self time (``trace.self_times``) of the
device-0 ops that run inside that program's executions and inside the
window and whose path holds the scope, per execution of the program. On a
mesh every device runs the same program on its own shard, and device 0
stands for them all. A program compiled without the scopes (an older tree,
or an executable loaded from a compile cache keyed without them) holds none
of the names: its readers return None. A reader's first look at a program
logs its whole split: device seconds by innermost scope, and those under
none.
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from .trace import WINDOW_SPAN, TraceData, self_times

# the program's device scopes, in the order of the split
SCOPES = ("paged.materialize", "paged.writeback", "attention", "mod.router",
          "mod.dispatch", "mlp", "lm_head", "optimizer")
UNSCOPED = "(no scope)"
METADATA_PLANE = "/host:metadata"
HOST_PLANE = "/host:CPU"

Table = Dict[str, str]  # HLO instruction name -> op_name


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varint and
    fixed-width fields, a ``memoryview`` for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _first(buf, num: int, default=None):
    return next((v for f, v in fields(buf) if f == num), default)


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def planes(data: bytes, names: Sequence[str]) -> Dict[str, memoryview]:
    """The named planes of an XSpace (field 1: XPlane; XPlane field 2: name)."""
    out = {}
    for num, plane in fields(memoryview(data)):
        if num == 1:
            name = _str(_first(plane, 2, b""))
            if name in names:
                out[name] = plane
    return out


def _event_metadata(plane) -> Iterator[Tuple[int, memoryview]]:
    """(id, XEventMetadata) of a plane's ``event_metadata`` map (field 4)."""
    for num, entry in fields(plane):
        if num == 4:
            key, val = 0, None
            for f, v in fields(entry):
                if f == 1:
                    key = v
                elif f == 2:
                    val = v
            if val is not None:
                yield key, val


def hlo_table(hlo_proto) -> Table:
    """Instruction name -> ``op_name`` over every computation of an HloProto
    (hlo_module 1 > computations 3 (id 5) > instructions 2 > name 1, opcode
    2, metadata 7 > op_name 2, called_computation_ids 38). A fusion that XLA
    left without metadata takes the most common ``op_name`` of the ops it
    fused."""
    rows = []  # (name, opcode, op_name, called computation ids)
    inside: Dict[int, collections.Counter] = {}
    for num, comp in fields(_first(hlo_proto, 1, b"")):
        if num != 3:
            continue
        cid, ops = 0, collections.Counter()
        for f, instr in fields(comp):
            if f == 5:
                cid = instr
            if f != 2:
                continue
            name = code = op = ""
            called = []
            for g, v in fields(instr):
                if g == 1:
                    name = _str(v)
                elif g == 2:
                    code = _str(v)
                elif g == 7:
                    op = _str(_first(v, 2, b""))
                elif g == 38:
                    called.extend(_packed(v) if isinstance(v, memoryview) else [v])
            rows.append((name, code, op, called))
            if op:
                ops[op] += 1
        inside[cid] = ops
    table: Table = {}
    for name, code, op, called in rows:
        if not op and code == "fusion":
            pooled = sum((inside.get(c, collections.Counter()) for c in called),
                         collections.Counter())
            op = pooled.most_common(1)[0][0] if pooled else ""
        table[name] = op
    return table


def _packed(buf) -> List[int]:
    out, i = [], 0
    while i < len(buf):
        v, i = _varint(buf, i)
        out.append(v)
    return out


def hlo_tables(metadata_plane, wanted=lambda name: True) -> Dict[str, Table]:
    """Program name (``jit_step(12)``) -> its instruction table, from the
    ``Hlo Proto`` stats of the metadata plane's event metadata."""
    stat_ids = set()
    for num, entry in fields(metadata_plane):
        if num == 5:
            sm = _first(entry, 2)
            if sm is not None and _str(_first(sm, 2, b"")) == "Hlo Proto":
                stat_ids.add(_first(entry, 1, 0))
    out: Dict[str, Table] = {}
    for _, em in _event_metadata(metadata_plane):
        name = _str(_first(em, 2, b""))
        if not wanted(name):
            continue
        for f, stat in fields(em):
            if f == 5 and _first(stat, 1, 0) in stat_ids:
                out[name] = hlo_table(_first(stat, 6, b""))
    return out


def host_window(host_plane) -> Optional[Tuple[float, float]]:
    """(start, end) in ns of the ``bench.window`` span on the host plane
    (XLine: timestamp_ns 3, events 4; XEvent: metadata_id 1, offset_ps 2,
    duration_ps 3)."""
    ids = {i for i, em in _event_metadata(host_plane)
           if _str(_first(em, 2, b"")) == WINDOW_SPAN}
    if not ids:
        return None
    for num, line in fields(host_plane):
        if num != 3:
            continue
        t0 = 0
        for f, v in fields(line):
            if f == 3:
                t0 = v
            elif f == 4:
                mid = off = dur = 0
                for g, w in fields(v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        off = w
                    elif g == 3:
                        dur = w
                if mid in ids:
                    start = t0 + off / 1e3
                    return start, start + dur / 1e3
    return None


_WRAP = re.compile(r"^[\w.\-]*\((.*)\)$")


@functools.lru_cache(maxsize=None)
def scope_path(op_name: str) -> Tuple[str, ...]:
    """The names of an ``op_name``'s path, transformation wrappers taken off:
    ``jit(step_fn)/transpose(jvp(attention))/dot_general`` -> (``step_fn``,
    ``attention``, ``dot_general``)."""
    out = []
    for part in op_name.split("/"):
        m = _WRAP.match(part)
        while m:
            part = m.group(1)
            m = _WRAP.match(part)
        out.append(part)
    return tuple(out)


@functools.lru_cache(maxsize=None)
def instruction(event_name: str) -> str:
    """The HLO instruction an op event names: ``%fusion.12 = bf16[..] ...`` ->
    ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


class ProgramScopes:
    """Self seconds of one program's device-0 ops inside the window, by scope
    path, and the program's executions in the window."""

    def __init__(self, td: TraceData, tables: Dict[str, Table], prefix: str,
                 lo: float, hi: float):
        mods = sorted((s, e, n) for n, s, e in (td.modules[0] if td.modules else [])
                      if n.startswith(prefix))
        self.executions = sum(1 for s, _, _ in mods if lo <= s < hi)
        self.marked = set()  # scopes some instruction of the program carries
        for name in {n for _, _, n in mods}:
            for op in tables.get(name, {}).values():
                self.marked.update(p for p in scope_path(op) if p in SCOPES)
        starts = [s for s, _, _ in mods]
        self.by_path: Dict[Tuple[str, ...], float] = collections.defaultdict(float)
        self.unscoped: Dict[str, float] = collections.Counter()  # "instr [op_name]" -> s
        ops = td.device_ops[0] if td.device_ops else []
        for name, s, e, own in self_times(ops):
            if not lo <= s < hi:
                continue
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= mods[k][1]:
                continue
            op = tables.get(mods[k][2], {}).get(instruction(name), "")
            path = tuple(p for p in scope_path(op) if p in SCOPES)
            self.by_path[path] += own / 1e9
            if not path:
                self.unscoped[f"{instruction(name)} [{op}]"] += own / 1e9

    def seconds(self, scopes: Iterable[str]) -> float:
        want = set(scopes)
        return sum(t for path, t in self.by_path.items() if want.intersection(path))

    def ms_per_execution(self, scopes: Iterable[str]) -> Optional[float]:
        """Device ms under any of ``scopes`` per execution, or None where the
        program carries none of them or did not run in the window."""
        scopes = list(scopes)
        if not self.executions or not self.marked.intersection(scopes):
            return None
        return 1e3 * self.seconds(scopes) / self.executions

    def split(self) -> Dict[str, float]:
        """Self seconds by innermost scope (``UNSCOPED`` for ops under none)."""
        out: Dict[str, float] = collections.defaultdict(float)
        for path, t in self.by_path.items():
            out[path[-1] if path else UNSCOPED] += t
        return dict(out)


def newest_xplane(root: str) -> Optional[str]:
    found = glob.glob(os.path.join(root, ".bench_trace", "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def read_xplane(path: str, prefix: str):
    """(the ``bench.window`` span or None, the instruction tables of the
    programs whose name starts with ``prefix``) of one ``.xplane.pb``."""
    with open(path, "rb") as f:
        got = planes(f.read(), (METADATA_PLANE, HOST_PLANE))
    win = host_window(got[HOST_PLANE]) if HOST_PLANE in got else None
    return win, hlo_tables(got.get(METADATA_PLANE, b""), lambda n: n.startswith(prefix))


_CACHE: Dict[Tuple, ProgramScopes] = {}


def of_run(run, prefix: str, root: Optional[str] = None) -> Optional[ProgramScopes]:
    """``prefix``'s ProgramScopes in the traced run ``run`` (its ``td`` and
    ``red``), with the tables of the newest trace under
    ``<root>/.bench_trace``; None where there is none or its window is not
    the run's."""
    from .harness import ROOT, log

    path = newest_xplane(str(root or ROOT))
    if path is None or run.td is None or run.red is None:
        return None
    key = (path, os.path.getmtime(path), prefix, run.red.lo, run.red.hi)
    if key not in _CACHE:
        win, tables = read_xplane(path, prefix)
        if win is None or abs(win[0] - run.red.lo) > 1e3 or abs(win[1] - run.red.hi) > 1e3:
            log(f"scopes: {path} is not this run's trace (its window: {win})")
            return None
        ps = ProgramScopes(run.td, tables, prefix, run.red.lo, run.red.hi)
        split = sorted(ps.split().items(), key=lambda kv: -kv[1])
        total = sum(t for _, t in split) or 1.0
        log(f"scopes: {prefix} ran {ps.executions} times in the window; device s by scope: "
            + ", ".join(f"{k} {t:.3f} ({100 * t / total:.1f}%)" for k, t in split))
        log(f"scopes: {prefix} largest ops under no scope: "
            + ", ".join(f"{k} {t:.3f}" for k, t in ps.unscoped.most_common(6)))
        _CACHE[key] = ps
    return _CACHE[key]
