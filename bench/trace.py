"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData``. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
that ran, and their ``XLA Modules`` line one event per program. Host planes
hold the benchmark's own spans (``jax.profiler.TraceAnnotation``) on the
thread that opened them. All timestamps share one clock (nanoseconds).

The reduction gives, for the window that the span ``window_span`` covers:

* busy time per device: the union of its op intervals;
* device time per op, labelled ``<program>/<op> <shape>``, and per kernel
  (ops whose name matches a kernel pattern);
* idle time per device, each gap between busy intervals charged to the
  innermost benchmark span that encloses its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
OUTSIDE = "(no benchmark span)"

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


@dataclass
class Reduction:
    window_ns: Interval
    busy_ns: Dict[str, float] = field(default_factory=dict)       # device ->
    op_ns: Dict[str, float] = field(default_factory=dict)         # label ->
    kernel_ns: Dict[str, float] = field(default_factory=dict)     # kernel ->
    kernel_calls: Dict[str, int] = field(default_factory=dict)
    idle_ns: Dict[str, float] = field(default_factory=dict)       # span ->

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    def non_kernel_ns(self) -> float:
        return sum(self.op_ns.values()) - sum(self.kernel_ns.values())

    def top_ops(self, n: int = 10) -> List[List]:
        top = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    def top_idle(self, n: int = 10) -> List[List]:
        top = sorted(self.idle_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi] outside the (merged, sorted) busy ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def innermost(spans: Sequence[Event], t: float) -> str:
    """Name of the shortest span that contains time ``t``."""
    best: Optional[Event] = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and (
                best is None or s.duration_ns < best.duration_ns):
            best = s
    return best.name if best is not None else OUTSIDE


def op_label(op_name: str, module: str) -> str:
    """``%copy.3 = f32[32,256,...]{...} copy(...)`` in ``jit_scatter(123)``
    -> ``jit_scatter/copy.3 f32[32,256,...]``."""
    head, _, rest = op_name.partition(" = ")
    shape = re.split(r"[{ ]", rest, maxsplit=1)[0] if rest else ""
    mod = module.split("(", 1)[0]
    return f"{mod}/{head.lstrip('%')} {shape}".strip()[:160]


def reduce_events(device_ops: Dict[str, List[Event]],
                  device_modules: Dict[str, List[Event]],
                  host_spans: List[Event], window: Interval,
                  kernels: Dict[str, str]) -> Reduction:
    """The reduction over plain events (what ``reduce_trace`` reads from the
    file): ``kernels`` maps a kernel's name to a regular expression that its
    op names match."""
    lo, hi = window
    red = Reduction(window_ns=window)
    patterns = {k: re.compile(p) for k, p in kernels.items()}
    inside = [s for s in host_spans if s.end_ns > lo and s.start_ns < hi]
    for dev, ops in device_ops.items():
        ops = [e for e in ops if e.end_ns > lo and e.start_ns < hi]
        mods = sorted(device_modules.get(dev, []), key=lambda e: e.start_ns)
        starts = [m.start_ns for m in mods]
        busy = union(clip(((e.start_ns, e.end_ns) for e in ops), lo, hi))
        red.busy_ns[dev] = sum(b - a for a, b in busy)
        for e in ops:
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            i = bisect.bisect_right(starts, e.start_ns) - 1
            module = mods[i].name if i >= 0 and e.start_ns <= mods[i].end_ns \
                else ""
            label = op_label(e.name, module)
            red.op_ns[label] = red.op_ns.get(label, 0.0) + (b - a)
            for kernel, pat in patterns.items():
                if pat.search(e.name):
                    red.kernel_ns[kernel] = red.kernel_ns.get(kernel, 0.0) \
                        + (b - a)
                    red.kernel_calls[kernel] = red.kernel_calls.get(
                        kernel, 0) + 1
        for a, b in gaps(busy, lo, hi):
            span = innermost(inside, (a + b) / 2)
            red.idle_ns[span] = red.idle_ns.get(span, 0.0) + (b - a)
    return red


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_trace(path: str, span_names: Iterable[str]):
    """(device ops, device modules, host spans) of one ``.xplane.pb``."""
    import jax
    names = set(span_names)
    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name in names)
    return ops, modules, spans


def reduce_trace(trace_dir: str, window_span: str, span_names: Iterable[str],
                 kernels: Dict[str, str]) -> Reduction:
    """Reduce the newest trace under ``trace_dir`` over the window that the
    (single) span ``window_span`` covers."""
    ops, modules, spans = read_trace(find_xplane(trace_dir),
                                     set(span_names) | {window_span})
    windows = [s for s in spans if s.name == window_span]
    if len(windows) != 1:
        raise ValueError(f"expected one {window_span!r} span in the trace, "
                         f"found {len(windows)}")
    w = windows[0]
    spans = [s for s in spans if s is not w]
    return reduce_events(ops, modules, spans, (w.start_ns, w.end_ns), kernels)
