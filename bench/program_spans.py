"""The program's own spans, reduced over the window of a traced run.

The KV tier opens ``jax.profiler.TraceAnnotation`` spans at its layer
boundaries (``SPANS``). They land in the profiler trace on the thread that
opened them, on the same clock as the device's ``XLA Ops``. For the window
that the single ``bench.window`` span covers, on the host thread that holds
it (spans of other threads are left out), the reduction gives:

* the time of each program span name inside the window, and how many such
  spans the window holds;
* device idle time, each gap between a device's busy intervals charged to
  the innermost program span that encloses its midpoint.

A traced run's ``.xplane.pb`` is read once and the reduction cached for
every metric reader of the run (``for_run``).
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench import harness
from bench.trace import (DEVICE_PREFIX, OPS_LINE, Event, Interval, clip,
                         find_xplane, gaps, union)

SPANS = ("serving.attend", "serving.dispatch", "serving.fetch",
         "serving.decode", "serving.replicate", "kvcache.pool_write",
         "kvcache.restore", "kvcache.offload", "memory.admission_wait")
OUTSIDE = "(no program span)"


@dataclass
class ProgramSpans:
    window_ns: Interval
    span_ns: Dict[str, float] = field(default_factory=dict)   # name ->
    count: Dict[str, int] = field(default_factory=dict)       # name ->
    idle_ns: Dict[str, float] = field(default_factory=dict)   # span ->
    devices: int = 0              # devices whose idle ``idle_ns`` sums

    def ms(self, name: str) -> Optional[float]:
        """Milliseconds of ``name`` spans in the window; None when the
        window holds none."""
        return self.span_ns[name] / 1e6 if name in self.span_ns else None

    def ms_per_span(self, name: str) -> Optional[float]:
        if name not in self.count:
            return None
        return self.ms(name) / self.count[name]

    def idle_share(self, name: str) -> Optional[float]:
        """Device idle under ``name`` (innermost), in % of the window,
        averaged over the devices."""
        lo, hi = self.window_ns
        if not self.devices or hi <= lo:
            return None
        return 100.0 * self.idle_ns.get(name, 0.0) / self.devices / (hi - lo)


def innermost_each(spans: Sequence[Event], points: Sequence[float]
                   ) -> List[str]:
    """Name of the shortest span that contains each point, for spans that
    nest (those of one thread do): a sweep over spans and points in order,
    with a stack of the spans open at the current point."""
    spans = sorted(spans, key=lambda s: (s.start_ns, -s.end_ns))
    out = [OUTSIDE] * len(points)
    stack: List[Event] = []
    i = 0
    for k in sorted(range(len(points)), key=points.__getitem__):
        t = points[k]
        while i < len(spans) and spans[i].start_ns <= t:
            s = spans[i]
            i += 1
            while stack and stack[-1].end_ns < s.start_ns:
                stack.pop()
            stack.append(s)
        while stack and stack[-1].end_ns < t:
            stack.pop()
        if stack:
            out[k] = stack[-1].name
    return out


def window_thread(lines: Sequence[Sequence[Event]]
                  ) -> Tuple[Event, List[Event]]:
    """The ``bench.window`` span and the program spans of its thread, from
    the host threads' spans (one list a thread)."""
    held = [(w, line) for line in lines for w in line
            if w.name == harness.WINDOW_SPAN]
    if len(held) != 1:
        raise ValueError(f"expected one {harness.WINDOW_SPAN!r} span in the "
                         f"trace, found {len(held)}")
    window, line = held[0]
    return window, [s for s in line if s.name in SPANS]


def reduce_spans(device_ops: Dict[str, List[Event]], spans: List[Event],
                 window: Interval) -> ProgramSpans:
    """The reduction over plain events: ``spans`` are the program spans of
    the window's thread."""
    lo, hi = window
    red = ProgramSpans(window_ns=window, devices=len(device_ops))
    inside = [s for s in spans if s.end_ns > lo and s.start_ns < hi]
    for s in inside:
        a, b = max(s.start_ns, lo), min(s.end_ns, hi)
        red.span_ns[s.name] = red.span_ns.get(s.name, 0.0) + (b - a)
        red.count[s.name] = red.count.get(s.name, 0) + 1
    for ops in device_ops.values():
        busy = union(clip(((e.start_ns, e.end_ns) for e in ops), lo, hi))
        idle = gaps(busy, lo, hi)
        names = innermost_each(inside, [(a + b) / 2 for a, b in idle])
        for (a, b), name in zip(idle, names):
            red.idle_ns[name] = red.idle_ns.get(name, 0.0) + (b - a)
    return red


def read_xplane(path: str
                ) -> Tuple[Dict[str, List[Event]], List[List[Event]]]:
    """(device ops, each host thread's window and program spans) of one
    ``.xplane.pb``."""
    import jax
    names = set(SPANS) | {harness.WINDOW_SPAN}
    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    lines: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                lines.append([Event(e.name, e.start_ns, e.duration_ns)
                              for e in line.events if e.name in names])
    return ops, lines


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int, size: int
                 ) -> Optional[ProgramSpans]:
    """The reduction of one trace file, read once for every reader of a run
    (the file's time and size tell a rewritten file apart)."""
    ops, lines = read_xplane(path)
    try:
        window, spans = window_thread(lines)
    except ValueError:
        return None
    return reduce_spans(ops, spans, (window.start_ns, window.end_ns))


def for_run(run) -> Optional[ProgramSpans]:
    """The program spans of a traced run's window. None for an untraced
    run, for a program that opens none of ``SPANS`` in the window, or when
    the newest trace of the cell is not the run's (its window is not the
    one the harness reduced)."""
    if run.trace is None:
        return None
    try:
        path = find_xplane(str(harness.ROOT / harness.TRACE_DIR_NAME
                               / run.cell.name))
    except FileNotFoundError:
        return None
    st = os.stat(path)
    red = _reduce_file(path, st.st_mtime_ns, st.st_size)
    if (red is None or not red.count
            or tuple(red.window_ns) != tuple(run.trace.window_ns)):
        return None
    return red
