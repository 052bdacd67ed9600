"""Reduction of a profiler trace to device busy time, per-program device
time, the costliest device operations and the host spans behind idle gaps.

Works on plain event tuples, so it is tested on synthetic traces; ``load``
reads them from the ``.xplane.pb`` that ``jax.profiler`` writes.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> List[Event]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Event] = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def device_planes(events: Sequence[Event]) -> List[str]:
    return sorted({e.plane for e in events if is_device(e.plane)})


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _busy_intervals(events: Sequence[Event], plane: str):
    ops = [(e.start_ns, e.end_ns) for e in events
           if e.plane == plane and e.line == OPS_LINE]
    if not ops:
        ops = [(e.start_ns, e.end_ns) for e in events
               if e.plane == plane and e.line == MODULE_LINE]
    return union(ops)


def busy_seconds(events: Sequence[Event]) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    tot = sum(sum(e - s for s, e in _busy_intervals(events, p))
              for p in planes)
    return tot / len(planes) / 1e9


def module_runs(events: Sequence[Event], plane: str
                ) -> List[Tuple[float, float, str, bool]]:
    """Executions of compiled programs on one device plane, as (start, end,
    module name, whether a loop ran inside), in time order."""
    mods = sorted((e.start_ns, e.end_ns, e.name) for e in events
                  if e.plane == plane and e.line == MODULE_LINE)
    starts = [m[0] for m in mods]
    loops = [False] * len(mods)
    for e in events:
        if e.plane == plane and e.line == OPS_LINE and is_loop(e.name):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns <= mods[i][1]:
                loops[i] = True
    return [(s, e, n, lp) for (s, e, n), lp in zip(mods, loops)]


def is_loop(op: str) -> bool:
    """An operation that holds others (a loop over layers, say): its time
    is its body's, so it is left out of the costliest operations."""
    return " while(" in op or op.startswith("while")


def program_time(events: Sequence[Event], match: Callable[[str, bool], bool]
                 ) -> Tuple[float, int]:
    """(device seconds, executions) of the compiled programs that ``match``
    (module name, ran a loop) selects, averaged over device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0, 0
    secs, n = 0.0, 0
    for p in planes:
        for s, e, name, loop in module_runs(events, p):
            if match(name, loop):
                secs += (e - s) / 1e9
                n += 1
    return secs / len(planes), round(n / len(planes))


def short_program(name: str) -> str:
    """``jit_decode_step(123)`` -> ``decode_step``."""
    base = name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


def short_op(name: str) -> str:
    """``%copy.7 = bf16[36,8]{1,0:T(8,128)} copy(...)`` ->
    ``copy.7 bf16[36,8]``."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name[:80]
    return f"{lhs.lstrip('%')} {rhs.split(' ')[0].split('{')[0][:60]}"


def top_ops(events: Sequence[Event], n: int = 10,
            label: Optional[Callable[[str, bool], str]] = None) -> List[List]:
    """The ``n`` device operations with the most time, as
    ``[program/op, seconds]``, summed over executions and averaged over
    device planes.  ``label`` names a program from (module name, ran a
    loop); loops themselves are left out, their bodies' operations count."""
    label = label or (lambda name, loop: short_program(name))
    planes = device_planes(events)
    tot: Dict[str, float] = defaultdict(float)
    for p in planes:
        runs = module_runs(events, p)
        starts = [r[0] for r in runs]
        for e in events:
            if e.plane != p or e.line != OPS_LINE or is_loop(e.name):
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            prog = (label(runs[i][2], runs[i][3])
                    if i >= 0 and e.start_ns <= runs[i][1] else "?")
            tot[f"{prog}/{short_op(e.name)}"] += e.dur_ns / 1e9 / len(planes)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Sequence[Event], n: int = 10) -> List[List]:
    """Idle device time by what the host was doing: every gap between busy
    intervals of the first device plane is charged to the innermost
    benchmark host span (``bench.*``) that covers the gap's midpoint, or to
    ``no span``.  Returns the ``n`` largest totals as ``[span, seconds]``."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = _busy_intervals(events, planes[0])
    if not busy:
        return []
    spans = sorted((e.start_ns, e.end_ns, e.name) for e in events
                   if not is_device(e.plane)
                   and e.name.startswith(HOST_SPAN_PREFIX))
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]
    tot: Dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        cover = [(se - ss, name) for ss, se, name in spans if ss <= mid <= se]
        name = min(cover)[1] if cover else "no span"
        tot[name] += (ge - gs) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
