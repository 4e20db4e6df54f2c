"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read.

Device planes are ``/device:<platform>:<n>``; on them the ``XLA Ops`` line
holds one event per executed operation and ``XLA Modules`` one per executed
program (``jit_<function>(<id>)``). Host planes carry the benchmark's own
``TraceAnnotation`` spans, named ``bench:<what>``. Busy time is the union of
the operation intervals, averaged over the chips; idle gaps are the
stretches of the traced window in which no operation ran, named by the
innermost ``bench:`` span around them.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

ANNOTATION_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTROL_FLOW = ("while", "conditional", "call")


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _overlap(intervals: Sequence[Interval], start: float, end: float) -> float:
    return sum(max(0.0, min(e, end) - max(s, start)) for s, e in intervals)


class Trace:
    """One traced window: device operations and programs per chip, and the
    benchmark's host spans, all in nanoseconds on the profiler's clock."""

    def __init__(self, ops, modules, spans, window: Optional[Interval] = None):
        self.ops: Dict[str, List[Tuple[str, float, float]]] = ops
        self.modules: Dict[str, List[Tuple[str, float, float]]] = modules
        self.spans: List[Tuple[str, float, float]] = sorted(spans, key=lambda s: s[1])
        if window is None:
            named = [s for s in self.spans if s[0] == ANNOTATION_PREFIX + "window"]
            window = (named[0][1], named[0][2]) if named else self._extent()
        self.window = window
        self.busy = {
            chip: _union([(s, s + d) for _, s, d in events])
            for chip, events in ops.items()
        }

    @classmethod
    def load(cls, path: str) -> "Trace":
        """Read ``path`` (an ``.xplane.pb`` or a directory holding one)."""
        from jax.profiler import ProfileData

        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        data = ProfileData.from_file(path)
        ops, modules, spans = defaultdict(list), defaultdict(list), []
        for plane in data.planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                for ev in line.events:
                    if device and line.name == OPS_LINE:
                        ops[plane.name].append((ev.name, ev.start_ns, ev.duration_ns))
                    elif device and line.name == MODULES_LINE:
                        modules[plane.name].append((ev.name, ev.start_ns, ev.duration_ns))
                    elif not device and ev.name.startswith(ANNOTATION_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        return cls(dict(ops), dict(modules), spans)

    def _extent(self) -> Interval:
        points = [s for s in self.spans] + [
            (n, s, s + d) for evs in self.ops.values() for n, s, d in evs
        ]
        if not points:
            return (0.0, 0.0)
        return (min(p[1] for p in points), max(p[2] for p in points))

    # ------------------------------------------------------------ reads

    @property
    def chips(self) -> int:
        return len(self.busy)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in the window in which an operation ran, averaged over
        the chips (0 when the trace holds no device operation)."""
        if not self.busy:
            return 0.0
        start, end = self.window
        return sum(_overlap(b, start, end) for b in self.busy.values()) / len(self.busy) * 1e-9

    def spans_named(self, name: str) -> List[Interval]:
        full = ANNOTATION_PREFIX + name
        return [(s, e) for n, s, e in self.spans if n == full]

    def busy_within_s(self, intervals: Sequence[Interval]) -> float:
        """Device-busy seconds inside the given host intervals, averaged
        over the chips."""
        if not self.busy:
            return 0.0
        total = sum(
            _overlap(b, s, e) for b in self.busy.values() for s, e in intervals
        )
        return total / len(self.busy) * 1e-9

    def module_seconds(self, prefixes: Sequence[str]) -> float:
        """Device seconds of the programs whose names start with one of
        ``prefixes``, inside the window, averaged over the chips."""
        if not self.modules:
            return 0.0
        start, end = self.window
        total = sum(
            _overlap([(s, s + d)], start, end)
            for events in self.modules.values()
            for name, s, d in events
            if name.startswith(tuple(prefixes))
        )
        return total / len(self.modules) * 1e-9

    def top_ops(self, count: int = 10) -> List[List]:
        """The device operations that took most time in the window,
        ``[[name, seconds], ...]``, averaged over the chips. An event's name
        is its HLO instruction (``%fusion.3 = ...``), cut to the instruction
        name; control flow (``while``, ``conditional``, ``call``) is left
        out, since its body's operations are events of their own."""
        start, end = self.window
        totals: Dict[str, float] = defaultdict(float)
        for events in self.ops.values():
            for name, s, d in events:
                short = name.split(" = ", 1)[0].lstrip("%")
                if short.startswith(CONTROL_FLOW):
                    continue
                totals[short] += _overlap([(s, s + d)], start, end)
        chips = max(1, len(self.ops))
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:count]
        return [[name, ns / chips * 1e-9] for name, ns in ranked if ns > 0]

    def idle_gaps(self, count: int = 10) -> List[List]:
        """The longest idle stretches of the first chip in the window,
        ``[[what the host was doing, seconds], ...]``."""
        if not self.busy:
            return []
        start, end = self.window
        busy = next(iter(self.busy.values()))
        gaps, cursor = [], start
        for s, e in busy:
            if s > cursor:
                gaps.append((cursor, min(s, end)))
            cursor = max(cursor, e)
            if cursor >= end:
                break
        if cursor < end:
            gaps.append((cursor, end))
        gaps = sorted((g for g in gaps if g[1] > g[0]), key=lambda g: g[0] - g[1])[:count]
        return [[self.host_doing((s + e) / 2), (e - s) * 1e-9] for s, e in gaps]

    def host_doing(self, t: float) -> str:
        """The innermost ``bench:`` span open at ``t``."""
        inner = None
        for name, s, e in self.spans:
            if s <= t <= e and name != ANNOTATION_PREFIX + "window":
                if inner is None or (e - s) < (inner[2] - inner[1]):
                    inner = (name, s, e)
        return inner[0][len(ANNOTATION_PREFIX):] if inner else "outside any span"
