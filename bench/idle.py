"""Idle device time split by what the engine loop was doing, from the
program's own spans (``revati.<layer>.<phase>``, ``repro.core.spans``).

``idle_split`` takes the idle time of the first device plane and splits it
into parts that do not overlap:

- ``in_program``: gaps between operations inside a run of a compiled
  program, which no host change removes;
- the rest is charged by time overlap to the innermost span on the engine
  loop's host line (the line that holds the ``engine.`` spans; spans on
  other threads are ignored): ``wait`` (``runner.wait``, the host blocked
  on the device), ``runner`` (the runner's other spans), ``engine``
  (``engine.`` spans) and ``none`` (no such span, and the idle before the
  first and after the last operation).

The five parts sum to the window minus the busy time, the idle that
``device_idle_share`` reads.  ``launch_ms`` gives how long each decode
program took to start on the device after its ``runner.dispatch`` span
began.

``load`` reads a profile as ``trace.load`` does, but names each host line
apart (``<line>#<k>``), since every Python thread's line has the same name.
``attach`` makes the real-mode cell's trace reduction
(``drive_real._reduce_trace``) carry the split and the launch delays (keys
``idle_split`` and ``launch_ms``), from the same events it reduces; the
readers call it when they are loaded, before a run.
"""

from __future__ import annotations

import bisect
import glob
import os
import statistics
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace
from bench.trace import Event

PREFIX = "revati."
PARTS = ("in_program", "runner", "wait", "engine", "none")


def load(trace_dir: str) -> List[Event]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Event] = []
    for plane in ProfileData.from_file(paths[0]).planes:
        host = not trace.is_device(plane.name)
        for k, line in enumerate(plane.lines):
            name = f"{line.name}#{k}" if host else line.name
            for e in line.events:
                out.append(Event(plane.name, name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def loop_line(events: Sequence[Event]) -> Optional[Tuple[str, str]]:
    """(plane, line) of the host line with the most ``revati.engine.``
    spans, or None if no line has one."""
    count: Dict[Tuple[str, str], int] = defaultdict(int)
    for e in events:
        if e.name.startswith(PREFIX + "engine.") and not trace.is_device(
                e.plane):
            count[(e.plane, e.line)] += 1
    return max(count, key=count.get) if count else None


def innermost(spans: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Nested spans of one thread as back-to-back segments, each named
    after the innermost span over it.  A child that outlasts its parent
    (clock jitter) is cut at the parent's end."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []          # (end, name)
    t = 0.0

    def emit(end, name):
        nonlocal t
        if end > t:
            segs.append((t, end, name))
        t = max(t, end)

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(*stack.pop())
        if stack:
            emit(s, stack[-1][1])
            e = min(e, stack[-1][0])
        t = max(t, s)
        stack.append((e, name))
    while stack:
        emit(*stack.pop())
    return segs


def part_of(span: str) -> str:
    if span == PREFIX + "runner.wait":
        return "wait"
    if span.startswith(PREFIX + "runner."):
        return "runner"
    if span.startswith(PREFIX + "engine."):
        return "engine"
    return "none"


def _subtract(gs: float, ge: float, runs: List[Tuple[float, float]],
              starts: List[float]):
    """(time of [gs, ge) inside the sorted, disjoint ``runs``, the pieces
    of it outside them)."""
    inside, outside = 0.0, []
    i = max(bisect.bisect_right(starts, gs) - 1, 0)
    t = gs
    while t < ge and i < len(runs):
        rs, re = runs[i]
        if re <= t:
            i += 1
            continue
        if rs >= ge:
            break
        if rs > t:
            outside.append((t, rs))
        inside += min(re, ge) - max(rs, t)
        t = min(re, ge)
        i += 1
    if t < ge:
        outside.append((t, ge))
    return inside, outside


def charges(events: Sequence[Event], window_ns: Optional[float] = None
            ) -> Optional[Dict[str, float]]:
    """Idle seconds of the first device plane in a window of ``window_ns``
    (default: first to last operation) by what holds them: ``in_program``,
    each innermost span name of the engine loop's line, and ``none``.
    None without a device plane or the loop's spans."""
    planes = trace.device_planes(events)
    line = loop_line(events)
    if not planes or line is None:
        return None
    plane = planes[0]
    busy = trace._busy_intervals(events, plane)
    if not busy:
        return None
    runs = trace.union([(e.start_ns, e.end_ns) for e in events
                        if e.plane == plane and e.line == trace.MODULE_LINE])
    run_starts = [r[0] for r in runs]
    segs = innermost([(e.start_ns, e.end_ns, e.name) for e in events
                      if (e.plane, e.line) == line
                      and e.name.startswith(PREFIX)])
    seg_starts = [s[0] for s in segs]
    out: Dict[str, float] = defaultdict(float)
    idle_ns = 0.0
    for (_, gs), (ge, _) in zip(busy, busy[1:]):
        idle_ns += ge - gs
        inside, host = _subtract(gs, ge, runs, run_starts)
        out["in_program"] += inside / 1e9
        for hs, he in host:
            uncovered = he - hs
            i = max(bisect.bisect_right(seg_starts, hs) - 1, 0)
            while i < len(segs) and segs[i][0] < he:
                s, e, name = segs[i]
                over = min(e, he) - max(s, hs)
                if over > 0:
                    out[name] += over / 1e9
                    uncovered -= over
                i += 1
            out["none"] += uncovered / 1e9
    if window_ns is None:
        window_ns = busy[-1][1] - busy[0][0]
    busy_ns = sum(e - s for s, e in busy)
    out["none"] += (window_ns - busy_ns - idle_ns) / 1e9
    return dict(out)


def parts(ch: Optional[Dict[str, float]]) -> Optional[Dict[str, float]]:
    """``charges`` summed into the five parts."""
    if ch is None:
        return None
    out = dict.fromkeys(PARTS, 0.0)
    for name, secs in ch.items():
        out[name if name in ("in_program", "none")
            else part_of(name)] += secs
    return out


def idle_split(events: Sequence[Event], window_ns: Optional[float] = None
               ) -> Optional[Dict[str, float]]:
    """The five parts (seconds) of the first device plane's idle time in a
    window of ``window_ns`` (default: first to last operation); None where
    the profile holds no device plane or no engine loop spans."""
    return parts(charges(events, window_ns))


def launch_ms(events: Sequence[Event]) -> Optional[Dict[str, float]]:
    """Each decode program's device start minus the start of its step's
    ``runner.dispatch`` span, p50, min and max (ms).  A program belongs to the
    step whose ``runner.wait`` is the first to end after the program does
    (that wait blocks on its result), and the step's dispatch is the last
    to start before that wait.  A decode queued behind a prompt chunk reads
    the chunk's time too.  A negative min means the host and device
    timelines of the profile disagree."""
    planes = trace.device_planes(events)
    line = loop_line(events)
    if not planes or line is None:
        return None
    own = [e for e in events if (e.plane, e.line) == line]
    disp = sorted(e.start_ns for e in own
                  if e.name == PREFIX + "runner.dispatch")
    waits = sorted((e.end_ns, e.start_ns) for e in own
                   if e.name == PREFIX + "runner.wait")
    wait_ends = [w[0] for w in waits]
    lags = []
    for s, e, name, _ in trace.module_runs(events, planes[0]):
        if "decode_step" not in name:
            continue
        j = bisect.bisect_left(wait_ends, e)
        k = bisect.bisect_right(disp, waits[j][1]) - 1 if j < len(waits) \
            else -1
        if k >= 0:
            lags.append((s - disp[k]) / 1e6)
    if not lags:
        return None
    return {"p50": statistics.median(lags), "min": min(lags),
            "max": max(lags), "n": len(lags)}


def attach() -> None:
    """Give ``drive_real._reduce_trace`` the keys ``idle_split`` (the five
    parts, seconds, over its window) and ``launch_ms``, read from the events
    it loads, and print both, with the idle seconds by span, to standard
    error.  Calling it again changes nothing."""
    from bench import drive_real
    reduce = drive_real._reduce_trace
    if hasattr(reduce, "plain_reduce"):
        return

    def reduce_and_split(trace_dir: str, window_s: float) -> Dict:
        ev = load(trace_dir)
        plain_load, trace.load = trace.load, lambda _dir: ev
        try:
            red = reduce(trace_dir, window_s)
        finally:
            trace.load = plain_load
        ch = charges(ev, window_ns=window_s * 1e9)
        red["idle_split"] = parts(ch)
        red["launch_ms"] = launch_ms(ev)
        print(f"idle_split {red['idle_split']}", file=sys.stderr)
        print(f"idle_by_span {ch}", file=sys.stderr)
        print(f"launch_ms {red['launch_ms']}", file=sys.stderr)
        return red

    reduce_and_split.plain_reduce = reduce
    drive_real._reduce_trace = reduce_and_split


def share(obs, part: str) -> Optional[float]:
    """One part of the split as a share of the traced window (%), or None
    where the run's profile had no split (no program spans)."""
    tr = obs.trace
    if tr is None or tr.get("idle_split") is None or tr["window_s"] <= 0:
        return None
    return tr["idle_split"][part] / tr["window_s"] * 100.0
