"""Shared persistent XLA compile cache placement + warm-geometry ledger.

First TPU compile of a shape costs seconds to tens of seconds. Every entry
point (CLI, daemon, ``serve_load.py``, ``benchmark/run.py``) keeps its
compile cache in the one directory :func:`compile_cache_dir` names:
``JAX_COMPILATION_CACHE_DIR`` when it is set, else ``<checkout>/.jax_cache``
(gitignored). The path is part of the cache key, so it never derives from
a run directory, a temp dir, a pid or the time.

The warm-geometry ledger is the resident service's half of the story
(``serve/``): a process-wide record of every analysis geometry this
process has already run. The fingerprint covers exactly the flags that
shape compiled programs (cohort width, block size, mesh, strategy, dtype
ladder, ingest path), so a repeated geometry inside one process — the
compile-once promise of the daemon — is a *hit* and a fresh geometry is a
*miss*. The counters are exported as well-known gauges
(``obs/metrics.py``), sampled by the heartbeat, and recorded in the run
manifest's ``compile_cache`` block: warm-vs-cold is observable, not
inferred from wall-clock.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Optional, Set, Tuple

#: The environment variable JAX itself reads for the cache directory.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """The persistent compile cache directory every entry point uses:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def compile_cache_entries() -> int:
    """Entries in :func:`compile_cache_dir` (0 when it does not exist yet)."""
    try:
        return len(os.listdir(compile_cache_dir()))
    except OSError:
        return 0


def enable_persistent_compile_cache(persist_all: bool = False) -> None:
    """Turn on XLA's persistent compilation cache in
    :func:`compile_cache_dir`.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory and no directory is set in code. ``SPARK_EXAMPLES_TPU_NO_CACHE=1``
    (test/CI hygiene) leaves the default location off. ``persist_all``
    (the daemon) stores every compile, not only those past JAX's 1 s
    floor: the daemon's geometry ledger claims "warm" for every fingerprint
    it primes, which is only honest if sub-second compiles left artifacts
    too."""
    import jax

    if persist_all:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(CACHE_DIR_ENV):
        return
    if os.environ.get("SPARK_EXAMPLES_TPU_NO_CACHE") == "1":
        return
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())


# ---------------------------------------------------------------------------
# Warm-geometry ledger (process-wide; the serve/ executor's cache key).
# ---------------------------------------------------------------------------

#: Conf fields that do NOT shape compiled programs: output/telemetry
#: placement, credentials, and the robustness flags (checkpoint placement,
#: resume source, fault plans change WHEN work runs, never what the
#: compiled kernels compute — and the Gramian-checkpoint fingerprint
#: (``pipeline/checkpoint.py``) requires the saving and the resuming run
#: to digest identically despite differing in exactly these flags).
#: Everything else (cohort, block size, mesh, strategy, dtype flags,
#: ingest path, references, input files) is part of the geometry —
#: conservative on purpose: a fingerprint hit promises the in-process jit
#: caches are warm for every kernel this run dispatches.
_NON_GEOMETRY_FIELDS = frozenset(
    {
        "output_path",
        "metrics_json",
        "heartbeat_seconds",
        "profile_dir",
        "client_secrets",
        "spark_master",
        "gramian_checkpoint_dir",
        "checkpoint_every_sites",
        "resume_from",
        "fault_plan",
        # The analyses' output placements: pure artifact paths, no effect
        # on compiled programs — the fingerprint stays placement-invariant
        # (same contract as output_path/metrics_json above).
        "grm_out",
        "ld_out",
        "assoc_out",
        # The plan validator's stacked-group knob (`graftcheck plan
        # --fused-jobs K`): it sizes the ADMISSION question, not the
        # per-job program — a job's compile geometry is the same whether
        # it later rides a fused group or runs serially (the group's own
        # geometry is keyed by fused_group_fingerprint).
        "fused_jobs",
    }
)

#: Conf fields that select WHICH contig windows stream through the
#: compiled programs without changing the programs themselves: blocks are
#: shaped by (block_size, cohort width), not by the region list. Excluded
#: from :func:`batch_compile_fingerprint` (the continuous-batching
#: compatibility key) ON TOP of the non-geometry fields — two small-region
#: queries over different windows of the same cohort dispatch through the
#: same warm kernels and may coalesce into one dispatch group.
_REGION_FIELDS = frozenset({"references", "all_references"})

# lock order: geometry-ledger lock is a leaf — nothing else is acquired
# while holding it (machine-checked by `graftcheck lockgraph`).
_geometry_lock = threading.Lock()
_seen_geometries: Set[str] = set()
_geometry_hits = 0
_geometry_misses = 0
_ledger_path: Optional[str] = None


def _fingerprint_doc(conf, kind: str, exclude: frozenset) -> str:
    fields = getattr(conf, "__dataclass_fields__", None)
    if fields is not None:
        doc = {
            name: getattr(conf, name)
            for name in sorted(fields)
            if name not in exclude
        }
    else:  # mapping-shaped confs (tests)
        doc = {
            k: v for k, v in sorted(dict(conf).items()) if k not in exclude
        }
    doc["__kind__"] = kind
    blob = json.dumps(doc, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


def compile_fingerprint(conf, kind: str = "pca") -> str:
    """Stable digest of one analysis geometry: every conf field except the
    output/telemetry placement flags, canonically serialized. ``kind``
    ("pca" | "similarity") is part of the geometry — a similarity-only run
    never compiles the center/eigh kernels, so it must not pre-warm the
    PCA fingerprint. Two equal fingerprints compile (and dispatch)
    identical programs."""
    return _fingerprint_doc(conf, kind, _NON_GEOMETRY_FIELDS)


def batch_compile_fingerprint(conf, kind: str = "pca") -> str:
    """The continuous-batching compatibility key (``serve/queue.py``):
    :func:`compile_fingerprint` made region-invariant. Two requests with
    equal batch fingerprints differ at most in WHICH contig windows they
    scan — same cohort width, block size, mesh, strategy, dtype ladder,
    ingest path — so they dispatch through the same compiled kernels and
    can safely ride one dispatch group back to back. Strictly coarser
    than the compile fingerprint, never coarser than the admission
    class."""
    return _fingerprint_doc(
        conf, kind, _NON_GEOMETRY_FIELDS | _REGION_FIELDS
    )


def fused_group_fingerprint(batch_fingerprint: str, num_jobs: int) -> str:
    """The fused batch group's OWN compile geometry: a K-lane stacked
    program (``ops/batched.py``) traces ``(K, N, N)`` shapes no serial
    member ever compiles, so warm-vs-cold attribution for fused dispatch
    is keyed by (shared batch fingerprint, jobs-axis size) — a repeat
    group of the same shape and size rides warm stacked kernels, a new K
    is honestly a miss even when every member geometry is warm."""
    blob = f"fused:{batch_fingerprint}:{int(num_jobs)}"
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


def geometry_seen(key: str) -> bool:
    """Has this process already run (and therefore compiled) ``key``?
    Read-only: no counter moves, no ledger mutation."""
    with _geometry_lock:
        return key in _seen_geometries


def record_geometry(key: str) -> bool:
    """Record one run of geometry ``key``; returns ``True`` when the
    geometry was already warm (hit) and ``False`` on first sight (miss).
    The hit/miss counters move exactly once per call. With a persistent
    ledger attached (:func:`attach_geometry_ledger`), a first-sight key is
    appended to the ledger file so the NEXT process primes it back."""
    global _geometry_hits, _geometry_misses
    with _geometry_lock:
        if key in _seen_geometries:
            _geometry_hits += 1
            return True
        _seen_geometries.add(key)
        _geometry_misses += 1
        ledger = _ledger_path
    # Outside the leaf lock: an fsync'd file append must never extend the
    # ledger lock's hold time (O_APPEND keeps concurrent writers whole).
    if ledger is not None:
        try:
            with open(ledger, "a", encoding="utf-8") as f:
                f.write(key + "\n")
                f.flush()
                os.fsync(f.fileno())
        except OSError as e:
            import sys

            print(
                f"warning: geometry ledger append failed ({e}); the next "
                "daemon incarnation will see this geometry cold",
                file=sys.stderr,
            )
    return False


def attach_geometry_ledger(path: str) -> int:
    """Make the warm-geometry ledger survive process restarts: prime
    ``_seen_geometries`` from ``path`` (one fingerprint per line; a torn
    final line from a crashed append is skipped) and append every future
    first-sight geometry there. Returns the number of primed geometries.

    A primed fingerprint makes ``geometry_seen`` answer ``True`` in a
    process that never compiled it — that is the POINT: paired with the
    persistent XLA compilation cache (``enable_persistent_compile_cache``),
    a repeat-geometry job after a
    daemon restart rebuilds its jit entries from disk artifacts instead of
    recompiling, so "warm" honestly means "no from-scratch compile", not
    only "in-process jit cache populated". Priming moves no hit/miss
    counters — those stay the lifetime record of THIS process's jobs."""
    global _ledger_path
    primed = 0
    keys = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                key = line.strip()
                # A fingerprint is exactly 16 hex chars; anything else is
                # a torn append from a killed writer — skip, don't raise.
                if len(key) == 16 and all(
                    c in "0123456789abcdef" for c in key
                ):
                    keys.append(key)
    except FileNotFoundError:
        pass
    with _geometry_lock:
        for key in keys:
            if key not in _seen_geometries:
                _seen_geometries.add(key)
                primed += 1
        _ledger_path = path
    return primed


def compile_cache_stats() -> Tuple[int, int]:
    """Process-wide ``(hits, misses)`` of the warm-geometry ledger."""
    with _geometry_lock:
        return _geometry_hits, _geometry_misses


def reset_compile_cache_stats() -> None:
    """Clear the ledger and counters (tests and bench isolation only —
    the daemon never resets: its counters are the service's lifetime
    warm-vs-cold record). Detaches any persistent ledger file too."""
    global _geometry_hits, _geometry_misses, _ledger_path
    with _geometry_lock:
        _seen_geometries.clear()
        _geometry_hits = 0
        _geometry_misses = 0
        _ledger_path = None


__all__ = [
    "compile_cache_dir",
    "compile_cache_entries",
    "enable_persistent_compile_cache",
    "compile_fingerprint",
    "batch_compile_fingerprint",
    "fused_group_fingerprint",
    "geometry_seen",
    "record_geometry",
    "attach_geometry_ledger",
    "compile_cache_stats",
    "reset_compile_cache_stats",
]
