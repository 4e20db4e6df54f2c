"""Tracing / profiling: the Spark-web-UI stand-in (SURVEY.md §5).

The reference delegated observability to the Spark UI (stage timelines on
ports 8080/4040, ``README.md:148-178``) and log4j. The TPU equivalents:

- :class:`StageTimes` — coarse per-stage wall-clock accounting for the
  driver pipeline, now a thin shim over the hierarchical span recorder
  (``obs/spans.py``): every stage it times is also a span in the run
  manifest, while the printed report stays byte-identical;
- :func:`device_trace` — a ``jax.profiler`` trace context producing a
  TensorBoard-loadable profile of the XLA ops (the fine-grained equivalent
  of drilling into a Spark stage), enabled by ``--profile-dir``.

Honest-timing note: dispatch is asynchronous, so a stage's wall time
includes its device work only when the stage ends in a synchronous fetch
(the driver's PCA stage does) or when ``sync=`` passes a device value to
fetch.
The span recorder carries this as the per-span ``synced`` flag.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Tuple

from spark_examples_tpu.obs.spans import SpanRecorder


class StageTimes:
    """Ordered per-stage wall-clock accounting, recorded as spans.

    ``recorder`` shares the run's :class:`SpanRecorder` (stages nest under
    whatever span is open, and deeper phases nest under the stages); a
    private recorder is created otherwise. ``stages`` keeps the historical
    ``[(name, seconds)]`` list so ``as_dict()`` and the printed report are
    unchanged.
    """

    def __init__(self, recorder: Optional[SpanRecorder] = None) -> None:
        self.recorder = recorder if recorder is not None else SpanRecorder()
        self.stages: List[Tuple[str, float]] = []

    @contextlib.contextmanager
    def stage(self, name: str, sync: Optional[Callable[[], object]] = None):
        """Time a stage; ``sync`` (if given) is called before closing the
        measurement to force outstanding device work to completion — pass a
        tiny fetch, e.g. ``lambda: jax.device_get(counter)``."""
        span = None
        try:
            with self.recorder.span(name, sync=sync) as span:
                yield self
        finally:
            if span is not None and span.seconds is not None:
                self.stages.append((name, span.seconds))

    def as_dict(self) -> Dict[str, float]:
        return dict(self.stages)

    def __str__(self) -> str:
        lines = ["Stage timings:", "-------------------------------"]
        total = 0.0
        for name, seconds in self.stages:
            lines.append(f"{name}: {seconds:.3f} s")
            total += seconds
        lines.append(f"total: {total:.3f} s")
        return "\n".join(lines)


@contextlib.contextmanager
def device_trace(profile_dir: Optional[str]):
    """``jax.profiler.trace`` when a directory is given, no-op otherwise.

    The resulting trace loads in TensorBoard's profile plugin (or
    ``xprof``) and shows per-op device timelines — ingest kernels, MXU
    Gramian updates, collectives, and the eigensolve."""
    if not profile_dir:
        yield
        return
    import jax

    with jax.profiler.trace(profile_dir):
        yield


__all__ = ["StageTimes", "device_trace"]
