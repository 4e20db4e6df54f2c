"""Structured telemetry: metrics registry, run spans, heartbeat, manifest.

The reference delegated all observability to the Spark web UI and log4j
(SURVEY.md §5); our earlier stand-ins were print-only strings scattered
across four modules — invisible to CI and to multi-host runs.
This package is the structured replacement:

- :mod:`metrics <spark_examples_tpu.obs.metrics>` — a thread-safe registry
  of named, labeled counters / gauges / histograms with JSON and
  Prometheus-text export. Every ad-hoc counter in the pipeline
  (``pipeline/stats.py``, ``sources/*`` client counters, the Gramian flush
  accounting) is now a view over this registry.
- :mod:`spans <spark_examples_tpu.obs.spans>` — hierarchical run spans
  (ingest → chunk-parse → dispatch → reduce-flush → eigh) with the honest
  device-sync semantics of ``StageTimes.stage(sync=)`` carried over.
- :mod:`heartbeat <spark_examples_tpu.obs.heartbeat>` — a background
  progress line for long runs (``--heartbeat-seconds``).
- :mod:`manifest <spark_examples_tpu.obs.manifest>` — the schema-versioned
  end-of-run machine-readable manifest (``--metrics-json``), consumed by
  CI's manifest checks and aggregated across processes under
  ``jax.distributed``.

Naming scheme (see DESIGN.md §9): ``<subsystem>_<what>[_<unit>]``;
counters end in ``_total``, durations in ``_seconds``. Subsystem prefixes:
``io_`` (dataset I/O stats), ``ingest_`` (parse/overlap/progress),
``prefetch_`` (the bounded queue), ``gramian_`` (accumulator flushes),
``client_`` (per-source request counters).
"""

from spark_examples_tpu.obs.metrics import MetricsRegistry
from spark_examples_tpu.obs.spans import SpanRecorder

__all__ = ["MetricsRegistry", "SpanRecorder"]
