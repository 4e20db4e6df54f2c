"""Fused batch execution: one device program for a whole batch group.

``serve/executor.py`` runs a continuous-batching group (jobs sharing a
``batch_compile_fingerprint``) through this runner instead of
back-to-back ``run_pipeline`` calls: every job becomes one lane of a
:class:`~spark_examples_tpu.ops.batched.StackedJobsAccumulator`, the
whole group accumulates through ONE ``(K, N, N)`` device program (one
dispatch and one reduction per step for K jobs), and each job's Gramian
is sliced out of the stacked accumulator — byte-identical to its serial
run, see ``ops/batched.py`` for the identity argument. Everything after
the slice IS the serial epilogue, reused verbatim: the same
``compute_pca``/``_summarize_similarity``, the same printed result rows,
the same warm-ledger recording, the same schema-v2 manifest built from
the same per-driver registry — a fused job's artifacts are
indistinguishable from a serial job's except for the additive
``fused_size`` stamp the daemon adds to its envelope.

Two-phase contract the executor relies on:

- :func:`preflight_fused` is SIDE-EFFECT FREE (no prints, no device
  work, no files). It raises :class:`FusedIneligible` for any group the
  stacked program cannot carry — mixed kinds, non-synthetic sources,
  sharded strategies, mismatched cohort geometry, a dtype-ladder risk,
  or a jobs axis past the HBM cap — and the caller falls back to serial
  execution with nothing to undo.
- :func:`run_fused_pipeline` then runs an eligible group to completion.
  Per-job output (driver banner, result rows, epilogue, manifest
  notice) is routed through the caller's ``stdout_factory`` so each
  job's prints land in its own log exactly as the serial executor
  routes them; the interleaved accumulation phase prints nothing
  per-job by construction.
"""

from __future__ import annotations

import contextlib
import io
from typing import Callable, ContextManager, List, Optional, Sequence

import numpy as np

from spark_examples_tpu.config import PcaConf
from spark_examples_tpu.ops.batched import (
    FusedIneligible,
    StackedJobsAccumulator,
    max_fused_jobs,
)
from spark_examples_tpu.ops.contracts import EXACT_F32_LIMIT
from spark_examples_tpu.pipeline.pca_driver import (
    PipelineResult,
    VariantsPcaDriver,
    _export_compile_cache_gauges,
    _register_prover_conformance,
    _summarize_similarity,
    _sync_scalar,
    jax_default_device,
    make_source,
)
from spark_examples_tpu.sharding.partitioners import VariantsPartitioner
from spark_examples_tpu.sources import partition_page_requests

#: The only request kinds with a stacked device program. ``grm`` finalizes
#: through a different kernel family and stays serial.
FUSABLE_KINDS = ("pca", "similarity")


def _check(condition: bool, reason: str) -> None:
    if not condition:
        raise FusedIneligible(reason)


def preflight_fused(
    confs: Sequence[PcaConf],
    kinds: Sequence[str],
    device_bytes: Optional[int] = None,
) -> int:
    """Prove a group can ride ONE stacked device program, or raise
    :class:`FusedIneligible` — before any side effect, so serial fallback
    has nothing to undo. Returns the group size K.

    The checks mirror the stacked accumulator's contract: one kind, the
    packed synthetic ingest for every lane (the only stream whose blocks
    are pure functions of the conf — file/REST lanes would interleave
    I/O nondeterministically), identical cohort geometry (the stacked
    buffer has ONE (K, N, N) shape), the dense strategy (a sharded lane
    has no N×N slice to stack), no per-lane stateful machinery
    (checkpoints, fault plans, range telemetry), a dtype ladder that
    provably never climbs mid-stream, and K inside the HBM cap."""
    k = len(confs)
    _check(k >= 1, "empty group")
    _check(
        len(kinds) == k, f"{k} confs but {len(kinds)} kinds"
    )
    distinct = sorted(set(kinds))
    _check(
        len(distinct) == 1,
        f"mixed-kind group {distinct}: one stacked program serves one "
        "kind",
    )
    _check(
        distinct[0] in FUSABLE_KINDS,
        f"kind {distinct[0]!r} has no stacked device program",
    )
    base = confs[0]
    for conf in confs:
        _check(
            conf.source == "synthetic",
            f"source {conf.source!r}: only the synthetic packed stream "
            "is a pure function of the conf",
        )
        _check(not conf.input_path, "--input-path resumes are serial")
        _check(
            conf.pca_backend == "tpu",
            f"--pca-backend {conf.pca_backend!r} has no device program",
        )
        _check(
            len(conf.variant_set_id) == 1,
            "packed lanes need a single variant set",
        )
        _check(
            getattr(conf, "num_samples_per_set", None) is None,
            "per-set cohort sizes change the lane width",
        )
        _check(
            conf.ingest in ("auto", "packed"),
            f"--ingest {conf.ingest!r} is not the packed lane stream",
        )
        _check(
            getattr(conf, "similarity_strategy", "auto") != "sharded",
            "sharded lanes have no dense N×N slice to stack",
        )
        _check(
            not getattr(conf, "save_variants", False),
            "--save-variants needs the wire ingest",
        )
        _check(
            not getattr(conf, "check_ranges", False),
            "--check-ranges telemetry is per-accumulator",
        )
        _check(
            not getattr(conf, "gramian_checkpoint_dir", None)
            and not getattr(conf, "resume_from", None),
            "Gramian checkpointing cursors are per-accumulator",
        )
        _check(
            getattr(conf, "fault_plan", None) is None,
            "a fault plan must fire inside its own job only",
        )
        _check(
            conf.num_samples == base.num_samples,
            f"cohort width {conf.num_samples} != {base.num_samples}: "
            "the stacked buffer has one sample axis",
        )
        _check(
            conf.block_size == base.block_size,
            "lane staging needs one block size",
        )
        _check(
            bool(getattr(conf, "exact_similarity", False))
            == bool(getattr(base, "exact_similarity", False)),
            "mixed dtype ladders cannot share the stacked buffer",
        )
    from spark_examples_tpu.ops.gramian import dense_strategy_fits

    _check(
        dense_strategy_fits(base.num_samples),
        f"cohort {base.num_samples} is past the dense HBM rule "
        "(sharded lanes cannot stack)",
    )
    if not getattr(base, "exact_similarity", False):
        # The serial accumulator climbs to int32 when a lane's projected
        # per-entry count could leave f32's exact window — a per-lane
        # event one stacked buffer cannot carry. Bound each lane's total
        # rows from the declared synthetic site grid (exact for the
        # synthetic source; flush increments are rows × 1² for {0,1}
        # operands), silently: preflight must not print.
        for conf in confs:
            source = make_source(conf)
            with contextlib.redirect_stdout(io.StringIO()):
                contigs = conf.get_contigs(source, conf.variant_set_id)
            total_sites = sum(source.declared_sites(c) for c in contigs)
            _check(
                total_sites <= EXACT_F32_LIMIT,
                f"{total_sites} projected sites could climb the dtype "
                f"ladder mid-stream (f32 exact window {EXACT_F32_LIMIT})",
            )
    cap = max_fused_jobs(base.num_samples, device_bytes=device_bytes)
    _check(
        k <= cap,
        f"group of {k} exceeds max_fused_jobs={cap} for "
        f"N={base.num_samples} (stacked HBM charge is K× per-job)",
    )
    return k


def _lane_stream(conf: PcaConf, driver: VariantsPcaDriver):
    """One job's packed block stream, verbatim the serial packed branch of
    ``pca_driver._similarity_stage`` (same partition order, same io_stats
    accounting, same progress gauges) — the lane feeds the stacked
    accumulator the identical blocks its serial run would stage."""
    from spark_examples_tpu.obs.metrics import (
        INGEST_PARTITIONS_DONE,
        INGEST_PARTITIONS_PLANNED,
        well_known_gauge,
    )

    source = driver.source
    contigs = driver._host_contigs(
        conf.get_contigs(source, conf.variant_set_id)
    )
    partitioner = VariantsPartitioner(contigs, conf.bases_per_partition)
    partitions = partitioner.get_partitions(conf.variant_set_id[0])
    well_known_gauge(driver.registry, INGEST_PARTITIONS_PLANNED).set(
        len(partitions)
    )
    done_gauge = well_known_gauge(driver.registry, INGEST_PARTITIONS_DONE)

    def blocks():
        for index, part in enumerate(partitions):
            if driver.io_stats is not None:
                driver.io_stats.add_partition(part.range)
                driver.io_stats.add_requests(
                    partition_page_requests(
                        source,
                        part.variant_set_id,
                        part.contig,
                        conf.bases_per_partition,
                    )
                )
            window_variants = 0
            for block in source.genotype_blocks(
                part.variant_set_id,
                part.contig,
                block_size=conf.block_size,
                min_allele_frequency=conf.min_allele_frequency,
            ):
                window_variants += len(block["positions"])
                yield block["has_variation"]
            if driver.io_stats is not None:
                driver.io_stats.add_variants(window_variants)
            done_gauge.set(index + 1)

    return blocks()


def run_fused_pipeline(
    confs: Sequence[PcaConf],
    kinds: Sequence[str],
    devices=None,
    stdout_factory: Optional[Callable[[int], ContextManager]] = None,
    run_ids: Optional[Sequence[Optional[str]]] = None,
) -> List[PipelineResult]:
    """Run an eligible group as ONE stacked device program; one
    :class:`PipelineResult` per job, in group order, each byte-identical
    to the serial ``run_pipeline`` result for the same conf.

    ``stdout_factory(j)`` returns a context manager routing prints to job
    j's log; per-job phases (driver construction, result emission,
    manifest notice) run inside it. The interleaved accumulation phase
    runs outside any job context and prints nothing. ``run_ids[j]``
    stamps job j's spans (its trace id)."""
    from spark_examples_tpu.obs.manifest import (
        build_run_manifest,
        write_manifest,
    )
    from spark_examples_tpu.utils.cache import (
        batch_compile_fingerprint,
        compile_fingerprint,
        fused_group_fingerprint,
        record_geometry,
    )
    from spark_examples_tpu.utils.tracing import StageTimes

    k = preflight_fused(confs, kinds)
    job_stdout = stdout_factory or (lambda j: contextlib.nullcontext())
    kind = kinds[0]
    similarity_only = kind == "similarity"
    placement = (
        jax_default_device(devices[0]) if devices else contextlib.nullcontext()
    )
    results: List[PipelineResult] = []
    with placement:
        drivers: List[VariantsPcaDriver] = []
        times: List[StageTimes] = []
        for j, conf in enumerate(confs):
            with job_stdout(j):
                # The serial preamble, per lane: contig banner + driver
                # construction ("Matrix size: N.") print into job j's log.
                driver = VariantsPcaDriver(
                    conf,
                    devices=devices,
                    run_id=run_ids[j] if run_ids is not None else None,
                )
                _export_compile_cache_gauges(driver.registry)
                drivers.append(driver)
                times.append(StageTimes(recorder=driver.spans))
        n = len(drivers[0].indexes)
        for driver in drivers:
            if len(driver.indexes) != n:
                raise FusedIneligible(
                    f"lane cohort width {len(driver.indexes)} != {n}"
                )
        acc = StackedJobsAccumulator(
            k,
            n,
            block_size=confs[0].block_size,
            exact_int=bool(getattr(confs[0], "exact_similarity", False)),
            pipeline_depth=2,
        )
        with contextlib.ExitStack() as stack:
            # Every job's ingest+similarity stage spans the shared
            # accumulation — the honest wall-clock of a fused lane IS the
            # group's wall (that is the throughput win: K lanes, one
            # wall). The spans land in each driver's own recorder, so
            # each manifest still carries its own stage tree.
            for j in range(k):
                stack.enter_context(times[j].stage("ingest+similarity"))
            streams = [
                _lane_stream(confs[j], drivers[j]) for j in range(k)
            ]
            # Lockstep round-robin: one block per live lane per round
            # keeps every lane's pending depth O(1), so host memory stays
            # O(K × block) — the bounded-ingest contract, fused.
            live = list(range(k))
            while live:
                for j in list(live):
                    block = next(streams[j], None)
                    if block is None:
                        acc.finish_lane(j)
                        live.remove(j)
                    else:
                        acc.add_rows(j, np.asarray(block, dtype=np.uint8))
            G_stack = acc.finalize()
            import jax

            jax.block_until_ready(G_stack)
        # Warm the fused-group geometry once per group: the K-lane
        # stacked program is its own compile geometry, keyed off the
        # group's shared batch fingerprint.
        record_geometry(
            fused_group_fingerprint(
                batch_compile_fingerprint(confs[0], kind=kind), k
            )
        )
        for j, (conf, driver) in enumerate(zip(confs, drivers)):
            with job_stdout(j):
                similarity = acc.job_slice(j)
                _sync_scalar(similarity)
                similarity_summary = None
                result = None
                if similarity_only:
                    similarity_summary = _summarize_similarity(similarity, n)
                else:
                    with times[j].stage("center+pca"):
                        result = driver.compute_pca(similarity)
                # The serial epilogue, verbatim (run_pipeline's tail):
                # warm ledger, conformance snapshot, printed rows, stats,
                # manifest — same order, same prints, same artifacts.
                record_geometry(compile_fingerprint(conf, kind=kind))
                _register_prover_conformance(driver)
                lines = (
                    driver.emit_result(result) if result is not None else []
                )
                driver.report_io_stats()
                manifest_doc = None
                manifest_path = None
                if getattr(conf, "metrics_json", None):
                    manifest_doc = build_run_manifest(
                        conf=conf,
                        spans=driver.spans,
                        registry=driver.registry,
                        io_stats=driver.io_stats,
                        overlap=driver._overlap,
                    )
                    try:
                        write_manifest(conf.metrics_json, manifest_doc)
                    except OSError as e:
                        import sys

                        print(
                            f"Run manifest NOT written to "
                            f"{conf.metrics_json}: {e}",
                            file=sys.stderr,
                        )
                    else:
                        manifest_path = conf.metrics_json
                        print(
                            f"Run manifest written to {conf.metrics_json}."
                        )
                driver.stop()
                results.append(
                    PipelineResult(
                        lines=lines,
                        similarity_summary=similarity_summary,
                        manifest=manifest_doc,
                        manifest_path=manifest_path,
                    )
                )
    return results


__all__ = [
    "FUSABLE_KINDS",
    "FusedIneligible",
    "preflight_fused",
    "run_fused_pipeline",
]
