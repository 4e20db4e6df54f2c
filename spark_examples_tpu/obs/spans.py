"""Hierarchical run spans — the structured successor of ``StageTimes``.

A :class:`SpanRecorder` holds a tree of named, timed spans. The driver
opens coarse stages (``ingest+similarity``, ``center+pca``) exactly where
``StageTimes`` used to; finer phases nest under them — the prefetch
iterator contributes its parse-time aggregate (``chunk-parse``), the
Gramian accumulators their flush aggregate (``dispatch``) and finalize
(``reduce-flush``), and the PCA stage its ``center``/``eigh`` children —
so one manifest shows where a run's wall-clock went, layer by layer.

Honest-timing semantics carried over from ``StageTimes.stage(sync=)``
(``utils/tracing.py``): dispatch is asynchronous, so a span's wall time
includes its device work only when it ends in a synchronous fetch.
``span(..., sync=fn)`` calls ``fn`` before closing the measurement and the
span records ``synced: true`` — manifest consumers can tell honest
wall-clock from dispatch-time-only numbers.

Thread model: the open-span stack is per-thread (ingest worker threads and
the driver thread each nest correctly); completed spans attach to their
parent, or to the recorder's root list when nothing is open on that
thread. Pre-measured durations recorded with :meth:`SpanRecorder.add`
(e.g. a flush-time aggregate) attach the same way.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List, Optional


class Span:
    """One timed region: name, seconds, sync-honesty flag, children."""

    __slots__ = ("name", "seconds", "synced", "children", "started_unix")

    def __init__(self, name: str, synced: bool, started_unix: float):
        self.name = str(name)
        self.seconds: Optional[float] = None  # None while still open
        self.synced = bool(synced)
        self.children: List["Span"] = []
        self.started_unix = started_unix

    def as_dict(self) -> Dict:
        return {
            "name": self.name,
            "seconds": self.seconds,
            "synced": self.synced,
            "started_unix": self.started_unix,
            "children": [c.as_dict() for c in self.children],
        }


class SpanRecorder:
    """A tree of spans with a per-thread open stack."""

    def __init__(self) -> None:
        # lock order: recorder lock is a leaf — nothing else is acquired
        # while holding it.
        self._lock = threading.Lock()
        self.roots: List[Span] = []
        self._stacks: Dict[int, List[Span]] = {}

    def _attach(self, span: Span) -> None:
        tid = threading.get_ident()
        with self._lock:
            stack = self._stacks.get(tid)
            if stack:
                stack[-1].children.append(span)
            else:
                self.roots.append(span)

    @contextlib.contextmanager
    def span(self, name: str, sync: Optional[Callable[[], object]] = None):
        """Open a child span of the current thread's innermost open span
        (or a new root). ``sync`` is called before the measurement closes —
        pass a tiny device fetch for honest wall-clock on async backends."""
        span = Span(name, synced=sync is not None, started_unix=time.time())
        self._attach(span)
        tid = threading.get_ident()
        with self._lock:
            self._stacks.setdefault(tid, []).append(span)
        start = time.perf_counter()
        try:
            yield span
        finally:
            try:
                if sync is not None:
                    sync()
            finally:
                # The span closes and the stack pops even when the sync
                # fetch raises (device error mid-measurement) — otherwise
                # every later span on this thread would silently nest
                # under a dead parent.
                span.seconds = time.perf_counter() - start
                with self._lock:
                    stack = self._stacks.get(tid, [])
                    if span in stack:
                        # Pop through `span` (robust to a child left open
                        # by a mid-body exception: everything above it
                        # closes too).
                        del stack[stack.index(span):]
                    if not stack:
                        self._stacks.pop(tid, None)

    def add(self, name: str, seconds: float, synced: bool = False) -> None:
        """Attach a pre-measured duration (an aggregate timed elsewhere,
        e.g. total Gramian flush host time) as a closed span."""
        span = Span(name, synced=synced, started_unix=time.time())
        span.seconds = float(seconds)
        self._attach(span)

    # -------------------------------------------------------------- exports

    def as_list(self) -> List[Dict]:
        """The completed span tree, JSON-safe (open spans report
        ``seconds: null``)."""
        with self._lock:
            roots = list(self.roots)
        return [s.as_dict() for s in roots]

    def flat(self) -> List[Dict]:
        """Depth-first ``{path, seconds, synced}`` rows, '/'-joined paths —
        the grep-able form of the tree."""
        rows: List[Dict] = []

        def walk(span: Span, prefix: str) -> None:
            path = f"{prefix}/{span.name}" if prefix else span.name
            rows.append(
                {"path": path, "seconds": span.seconds, "synced": span.synced}
            )
            for child in span.children:
                walk(child, path)

        with self._lock:
            roots = list(self.roots)
        for root in roots:
            walk(root, "")
        return rows

    def find(self, path: str) -> Optional[Span]:
        """The first span at a '/'-joined path, or ``None``."""
        parts = path.split("/")
        with self._lock:
            level = list(self.roots)
        span = None
        for part in parts:
            span = next((s for s in level if s.name == part), None)
            if span is None:
                return None
            level = span.children
        return span


__all__ = ["Span", "SpanRecorder"]
