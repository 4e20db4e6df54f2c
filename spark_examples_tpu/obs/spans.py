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

Three views of one measurement:

- the recorder's own tree (:meth:`SpanRecorder.as_list`), which the run
  manifest snapshots;
- the profiler's timeline: each span opened with :meth:`SpanRecorder.span`
  is also a ``jax.profiler.TraceAnnotation`` named ``sxt:<path>`` (e.g.
  ``sxt:ingest/enqueue``), so under a profiler session (``--profile-dir``)
  it sits on the host timeline beside the device programs, on the same
  clock; with no session the annotation is a no-op. JAX is used only if
  something in the process has already imported it, so JAX-free callers
  (``check/``) stay JAX-free;
- a bounded process-wide buffer of closed spans, :func:`recent_spans`,
  for readers that outlive the recorder (a driver is discarded after its
  job). Each record carries its path, its parent's path, the recorder's
  ``run_id`` (one per driver run: a served job's trace id, or a fresh one)
  and its integer attributes — counts taken at the span's boundary.

Thread model: the open-span stack is per-thread (ingest worker threads and
the driver thread each nest correctly); completed spans attach to their
parent, or to the recorder's root list when nothing is open on that
thread. Pre-measured durations recorded with :meth:`SpanRecorder.add`
(e.g. a flush-time aggregate) attach the same way.
"""

from __future__ import annotations

import collections
import contextlib
import sys
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

#: Closed spans of every recorder in the process, newest last. Bounded: a
#: long-lived service keeps only the latest spans; a batch job closes about
#: ten, so hundreds of jobs fit.
_RECENT: Deque["Span"] = collections.deque(maxlen=4096)

#: Prefix of the profiler annotations the spans open.
ANNOTATION_PREFIX = "sxt:"


class Span:
    """One timed region: name, path, seconds, sync-honesty flag, children,
    integer attributes."""

    __slots__ = (
        "name",
        "path",
        "parent",
        "run_id",
        "seconds",
        "synced",
        "children",
        "started_unix_ns",
        "attrs",
    )

    def __init__(
        self,
        name: str,
        synced: bool,
        parent: Optional["Span"] = None,
        run_id: Optional[str] = None,
    ):
        self.name = str(name)
        self.parent = parent.path if parent is not None else None
        self.path = f"{self.parent}/{self.name}" if parent is not None else self.name
        self.run_id = run_id
        self.seconds: Optional[float] = None  # None while still open
        self.synced = bool(synced)
        self.children: List["Span"] = []
        self.started_unix_ns = time.time_ns()
        self.attrs: Dict[str, int] = {}

    @property
    def started_unix(self) -> float:
        return self.started_unix_ns * 1e-9

    @property
    def self_seconds(self) -> Optional[float]:
        """Seconds not covered by a closed child span."""
        if self.seconds is None:
            return None
        return self.seconds - sum(c.seconds for c in self.children if c.seconds is not None)

    def as_dict(self) -> Dict:
        doc = {
            "name": self.name,
            "seconds": self.seconds,
            "synced": self.synced,
            "started_unix": self.started_unix,
            "children": [c.as_dict() for c in self.children],
        }
        if self.attrs:
            doc["attrs"] = dict(self.attrs)
        return doc

    def record(self) -> Dict:
        """The flat form :func:`recent_spans` returns."""
        return {
            "name": self.name,
            "path": self.path,
            "parent": self.parent,
            "run_id": self.run_id,
            "started_unix_ns": self.started_unix_ns,
            "seconds": self.seconds,
            "self_seconds": self.self_seconds,
            "synced": self.synced,
            "attrs": dict(self.attrs),
        }


def recent_spans() -> List[Dict]:
    """The process's most recently closed spans, oldest first, as
    :meth:`Span.record` dicts."""
    return [span.record() for span in list(_RECENT)]


def _annotation(path: str):
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + path)


class SpanRecorder:
    """A tree of spans with a per-thread open stack. ``run_id`` stamps
    every span this recorder closes."""

    def __init__(self, run_id: Optional[str] = None) -> None:
        # lock order: recorder lock is a leaf — nothing else is acquired
        # while holding it.
        self._lock = threading.Lock()
        self.run_id = run_id
        self.roots: List[Span] = []
        self._stacks: Dict[int, List[Span]] = {}

    def _open(self, name: str, synced: bool) -> Span:
        """A new span, attached under this thread's innermost open span
        (or as a root)."""
        tid = threading.get_ident()
        with self._lock:
            stack = self._stacks.get(tid)
            parent = stack[-1] if stack else None
            span = Span(name, synced, parent, self.run_id)
            if parent is not None:
                parent.children.append(span)
            else:
                self.roots.append(span)
        return span

    @contextlib.contextmanager
    def span(self, name: str, sync: Optional[Callable[[], object]] = None):
        """Open a child span of the current thread's innermost open span
        (or a new root). ``sync`` is called before the measurement closes —
        pass a tiny device fetch for honest wall-clock on async backends."""
        span = self._open(name, sync is not None)
        tid = threading.get_ident()
        with self._lock:
            self._stacks.setdefault(tid, []).append(span)
        annotation = _annotation(span.path)
        annotation.__enter__()
        start = time.perf_counter_ns()
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
                span.seconds = (time.perf_counter_ns() - start) * 1e-9
                annotation.__exit__(None, None, None)
                with self._lock:
                    stack = self._stacks.get(tid, [])
                    if span in stack:
                        # Pop through `span` (robust to a child left open
                        # by a mid-body exception: everything above it
                        # closes too).
                        del stack[stack.index(span):]
                    if not stack:
                        self._stacks.pop(tid, None)
                _RECENT.append(span)

    def add(self, name: str, seconds: float, synced: bool = False) -> None:
        """Attach a pre-measured duration (an aggregate timed elsewhere,
        e.g. total Gramian flush host time) as a closed span."""
        span = self._open(name, synced)
        span.seconds = float(seconds)
        _RECENT.append(span)

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

        def walk(span: Span) -> None:
            rows.append(
                {"path": span.path, "seconds": span.seconds, "synced": span.synced}
            )
            for child in span.children:
                walk(child)

        with self._lock:
            roots = list(self.roots)
        for root in roots:
            walk(root)
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


__all__ = ["ANNOTATION_PREFIX", "Span", "SpanRecorder", "recent_spans"]
