"""The flagship PCoA pipeline: ``VariantsPcaDriver`` rebuilt TPU-first.

Mirrors the 7-stage pipeline of ``VariantsPca.scala:45-336`` —
data → filter → calls → similarity → PCA → emit → stats — with the Spark
machinery replaced stage-by-stage:

- per-partition Breeze pair counting + ``reduceByKey`` shuffle
  (``:222-231``) → blockwise ``G += XᵀX`` on the MXU + one cross-device
  reduction (``ops/gramian.py``);
- driver-side ``collect`` of row sums + broadcast centering (``:238-263``)
  → fused on-device Gower centering (``ops/centering.py``);
- MLlib ``RowMatrix.computePrincipalComponents`` (``:264-266``) →
  ``jnp.linalg.eigh`` on the HBM-resident matrix (``ops/pca.py``);
- join/merge of multiple datasets via key shuffles (``:155-188``) →
  per-window hash joins (windows align across datasets because all datasets
  share one partitioner, exactly as the reference builds one
  ``VariantsPartitioner`` from the flattened contig list, ``:111-125``).

Two compute backends, selected by ``--pca-backend`` (the BASELINE.json north
star): ``tpu`` (device pipeline) and ``host`` (a literal NumPy replication of
the reference algorithm, kept as the cross-check oracle).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_examples_tpu.config import PcaConf
from spark_examples_tpu.models.variant import Variant
from spark_examples_tpu.ops.centering import gower_center
from spark_examples_tpu.ops.gramian import (
    GramianAccumulator,
    ShardedGramianAccumulator,
    accumulate_index_rows,
)
from spark_examples_tpu.ops.pca import (
    mllib_reference_pca,
    principal_components_subspace,
)
from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS, resolve_run_mesh
from spark_examples_tpu.pipeline.checkpoint import load_variants
from spark_examples_tpu.pipeline.datasets import VariantsDataset, _parallel_shards
from spark_examples_tpu.pipeline.stats import VariantsDatasetStats
from spark_examples_tpu.sharding.partitioners import VariantsPartitioner
from spark_examples_tpu.sources import partition_page_requests
from spark_examples_tpu.sources.base import GenomicsSource
from spark_examples_tpu.sources.files import FileGenomicsSource, af_float
from spark_examples_tpu.sources.stream import MergeJoinStats, merge_join
from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource
from spark_examples_tpu.utils import faults


@dataclass(frozen=True)
class CallData:
    """``(hasVariation, callsetIndex)`` (``VariantsPca.scala:338``)."""

    has_variation: bool
    callset_id: int


def extract_call_info(variant: Variant, mapping: Dict[str, int]) -> List[CallData]:
    """``VariantsPcaDriver.extractCallInfo`` (``VariantsPca.scala:65-69``)."""
    if variant.calls is None:
        return []
    return [
        CallData(call.has_variation(), mapping[call.callset_id])
        for call in variant.calls
    ]


def _samples_sharded_mesh(similarity):
    """The mesh of a samples-axis row-sharded similarity matrix, or ``None``.

    Shardedness travels WITH the matrix (its ``NamedSharding``), not via
    driver state: ``compute_pca`` routes to the sharded centering/eigensolve
    exactly when the rows are actually partitioned over ``samples``.
    """
    sharding = getattr(similarity, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if (
        spec is not None
        and len(spec) > 0
        and spec[0] == SAMPLES_AXIS
        and sharding.mesh.shape.get(SAMPLES_AXIS, 1) > 1
    ):
        return sharding.mesh
    return None


def _fetch_components_and_nonzero(device_components, nz, mesh):
    """ONE host transfer for {components, nonzero-row count}: the count
    rides behind the flattened (N, num_pc) components (cohort sizes are far
    below f32's 2^24 exact-integer range). Returns ``(components, nonzero)``.

    The separate nonzero and components fetches were the dominant share of
    small-region wall-clock; the batched-transfer
    pattern lives in ``parallel/mesh.py:packed_host_fetch``. ``mesh`` is
    the samples-sharded mesh for the sharded eigensolve path (the packed
    result is replicated so every process of a multi-controller run reads
    its local copy); ``None`` for the dense path, whose operands are
    process-local or fully replicated already.
    """
    import jax.numpy as jnp

    from spark_examples_tpu.parallel.mesh import packed_host_fetch

    rows, num_pc = device_components.shape
    flat = packed_host_fetch(
        [
            jnp.asarray(device_components, jnp.float32),
            nz.astype(jnp.float32),
        ],
        mesh,
    )
    return flat[:-1].reshape(rows, num_pc), int(flat[-1])


def _device_peak_bytes(devices) -> Optional[int]:
    """The largest ``peak_bytes_in_use`` over ``devices``, or ``None`` where
    a device reports no memory stats (the CPU)."""
    peaks = []
    for device in devices:
        stats = device.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks, default=None)


def make_source(conf: PcaConf) -> GenomicsSource:
    if conf.source == "synthetic":
        sizes = getattr(conf, "num_samples_per_set", None)
        return SyntheticGenomicsSource(
            num_samples=conf.num_samples,
            seed=conf.seed,
            cohort_sizes=(
                dict(zip(conf.variant_set_id, sizes)) if sizes else None
            ),
        )
    if conf.source == "file":
        return FileGenomicsSource(
            conf.input_files or [],
            stream_chunk_bytes=getattr(conf, "stream_chunk_bytes", None),
            ingest_workers=getattr(conf, "ingest_workers", None),
        )
    from spark_examples_tpu.sources.base import get_access_token
    from spark_examples_tpu.sources.rest import RestGenomicsSource

    return RestGenomicsSource(auth=get_access_token(conf.client_secrets))


class VariantsPcaDriver:
    """Reusable driver (``VariantsPca.scala:89-336``)."""

    def __init__(
        self,
        conf: PcaConf,
        source: Optional[GenomicsSource] = None,
        devices=None,
        run_id: Optional[str] = None,
    ):
        # One telemetry namespace per run: every counter/gauge/span of this
        # driver's pipeline lands here, and the run manifest
        # (``--metrics-json``) snapshots exactly this registry+recorder —
        # concurrent drivers (tests, bench configs) never cross-contaminate.
        # ``run_id`` stamps every span of the run (a served job passes its
        # trace id); a fresh id is minted otherwise.
        from spark_examples_tpu.obs import MetricsRegistry, SpanRecorder
        from spark_examples_tpu.obs.trace import mint_trace_id

        self.registry = MetricsRegistry()
        self.spans = SpanRecorder(run_id=run_id or mint_trace_id())
        with self.spans.span("driver-init"):
            self._init_run(conf, source, devices)

    def _init_run(self, conf: PcaConf, source, devices) -> None:
        self.conf = conf
        self.source = source if source is not None else make_source(conf)
        # Executor-slice support (serve/): when given, every mesh this
        # driver resolves is built over exactly these devices, so
        # concurrent drivers on disjoint slices never contend for HBM or
        # accumulator state. None = all devices (the historical rule).
        self.devices = list(devices) if devices is not None else None
        self._overlap: Optional[Dict] = None
        # Crash-consistent Gramian checkpointing (pipeline/checkpoint.py):
        # the resume artifact is loaded HERE, before any ingest, so a conf
        # fingerprint mismatch or a corrupt artifact fails the run in
        # milliseconds instead of after a re-ingest pass. The feeder is
        # created lazily around the run's accumulator (_wrap_accumulator).
        self.feeder = None
        # The manifest's ``schedule`` block (reduction-schedule kind +
        # predicted-vs-measured ring bytes), stashed from the sharded
        # accumulator when one runs; None on dense/host runs.
        self._sched_block: Optional[Dict] = None
        # Host-sharded pod ingest (sharding/contig.py:host_partition):
        # resolved ONCE per run by _plan_host_sharded_ingest (every contig
        # enumeration and the finalize merge must agree on the same
        # decision); None = not yet resolved, 1 = whole-cohort ingest.
        self._ingest_hosts: Optional[int] = None
        self._gramian_resume: Optional[Dict] = None
        self._ckpt_fingerprint = ""
        if getattr(conf, "gramian_checkpoint_dir", None) or getattr(
            conf, "resume_from", None
        ):
            from spark_examples_tpu.pipeline.checkpoint import (
                gramian_checkpoint_fingerprint,
                load_gramian_checkpoint,
            )

            self._ckpt_fingerprint = gramian_checkpoint_fingerprint(conf)
            if getattr(conf, "resume_from", None):
                self._gramian_resume = load_gramian_checkpoint(
                    conf.resume_from, self._ckpt_fingerprint
                )
                if self._gramian_resume is not None:
                    meta = self._gramian_resume["meta"]
                    print(
                        f"Resuming from Gramian checkpoint at "
                        f"{conf.resume_from}: {meta['sites']} sites "
                        f"already accumulated."
                    )
        # Stats are disabled when resuming from materialized input
        # (``VariantsPca.scala:332-335``).
        self.io_stats: Optional[VariantsDatasetStats] = (
            None if conf.input_path else VariantsDatasetStats(self.registry)
        )
        # Driver-side callset fetch → (indexes, names) (``VariantsPca.scala:97-109``).
        callsets = self.source.search_callsets(conf.variant_set_id)
        self.indexes: Dict[str, int] = {
            cs["id"]: i for i, cs in enumerate(callsets)
        }
        # The inverse of ``indexes``: callset ids in matrix-row order.
        self.callset_ids: List[str] = list(self.indexes)
        self.names: Dict[str, str] = {cs["id"]: cs["name"] for cs in callsets}
        print(f"Matrix size: {len(self.indexes)}.")
        # After callset discovery: the static bound needs the REAL cohort
        # width (file sources carry theirs in the data, not the flag).
        self._register_host_memory_gauges()

    def _register_host_memory_gauges(self) -> None:
        """The host-memory cross-validation pair (``graftcheck hostmem``'s
        runtime half): a function-backed peak-RSS gauge — every read
        (heartbeat tick, manifest snapshot) samples the OS high-water mark
        — and the static bound from the ONE formula
        ``parallel/mesh.py:host_peak_bytes`` (resolved by
        ``check/hostmem.py:conf_host_peak_bytes``, which is TOTAL — every
        configured ingest path gets a finite bound — and the same
        resolver ``graftcheck plan --host-mem-budget`` enforces, so the
        bound the manifest records and the budget the validator proves
        cannot drift). Best-effort: telemetry must never take down a run;
        if the resolver itself raises, the runtime-baseline bound is
        registered so the gauge is never absent."""
        from spark_examples_tpu.obs.metrics import (
            HOST_PEAK_RSS_BYTES,
            HOST_STATIC_BOUND_BYTES,
            read_host_peak_rss_bytes,
            well_known_gauge,
        )

        if read_host_peak_rss_bytes() is not None:
            well_known_gauge(self.registry, HOST_PEAK_RSS_BYTES).set_function(
                lambda: float(read_host_peak_rss_bytes() or 0)
            )
        try:
            from spark_examples_tpu.check.hostmem import conf_host_peak_bytes

            # Resolved against the declared flag surface; the device count
            # only caps the default mesh's data axis, so jax stays
            # uninitialized here unless a mesh decision truly needs it.
            device_count = None
            num_hosts = 1
            if self.devices is not None:
                device_count = len(self.devices)
            elif not getattr(self.conf, "mesh_shape", None):
                import jax

                device_count = jax.device_count()
            import sys

            if "jax" in sys.modules:
                # PER-HOST bound: under multi-process init every process
                # registers the same formula with the merge term charged
                # (conservative for ring runs, exact for host-sharded
                # ingest — the merge gather is the peak either way). The
                # probe never forces a backend into being on its own.
                import jax

                num_hosts = jax.process_count()
            bound = conf_host_peak_bytes(
                self.conf,
                device_count=device_count,
                num_samples=len(self.indexes) or None,
                num_hosts=num_hosts,
            )
        except Exception:
            from spark_examples_tpu.parallel.mesh import (
                HOST_RUNTIME_BASELINE_BYTES,
            )

            bound = HOST_RUNTIME_BASELINE_BYTES
        well_known_gauge(self.registry, HOST_STATIC_BOUND_BYTES).set(
            float(bound)
        )

    # ------------------------------------------------------------------ data

    def get_data(self) -> List[VariantsDataset]:
        """One sharded dataset per variant set (``VariantsPca.scala:111-125``);
        all datasets share one partitioner built from the flattened contig
        list, or a checkpoint reader under ``--input-path``."""
        if self.conf.input_path:
            return [load_variants(self.conf.input_path)]
        contigs = self._host_contigs(
            self.conf.get_contigs(self.source, self.conf.variant_set_id)
        )
        partitioner = VariantsPartitioner(contigs, self.conf.bases_per_partition)
        return [
            VariantsDataset(
                self.source,
                variant_set_id,
                partitioner,
                stats=self.io_stats,
                num_workers=getattr(self.conf, "num_workers", 8),
            )
            for variant_set_id in self.conf.variant_set_id
        ]

    # ---------------------------------------------------------------- filter

    def filter_variant(self, variant: Variant) -> bool:
        """``--min-allele-frequency`` on the AF info field
        (``VariantsPca.scala:136-148``): strictly greater, first AF value,
        variants without AF dropped.

        For the synthetic source the comparison uses the canonical micro-unit
        rule (``utils/af.py``) so the wire path agrees bit-for-bit with the
        packed and device ingest paths (whose AF lives on the 6-decimal
        grid); generic sources keep the reference's plain float comparison.
        """
        if self.conf.min_allele_frequency is None:
            return True
        af = variant.info.get("AF")
        if not af:
            return False
        if isinstance(self.source, SyntheticGenomicsSource):
            from spark_examples_tpu.utils.af import af_passes

            return bool(
                af_passes(float(af[0]), self.conf.min_allele_frequency)
            )
        if isinstance(self.source, FileGenomicsSource):
            # Same AF grammar as the packed/native ingest of the SAME file
            # (unparseable → NaN → dropped): the two ingest modes must agree
            # record for record.
            return af_float(af[0]) > self.conf.min_allele_frequency
        return float(af[0]) > self.conf.min_allele_frequency

    # ----------------------------------------------------------------- calls

    def iter_calls(self, datasets: List[VariantsDataset]) -> Iterator[List[int]]:
        """Variant → varying callset column indices
        (``VariantsPca.scala:193-208``): single-dataset map, two-dataset key
        join, ≥3 merge-intersect; keep varying calls, drop empty rows."""
        n_sets = len(self.conf.variant_set_id)
        if self.conf.min_allele_frequency is not None:
            print(f"Min allele frequency {self.conf.min_allele_frequency}.")

        if n_sets == 1:
            save_path = getattr(self.conf, "save_variants", None)
            if save_path:
                yield from self._iter_calls_saving(datasets[0], save_path)
                return
            for variant in datasets[0].variants():
                if not self.filter_variant(variant):
                    continue
                calls = extract_call_info(variant, self.indexes)
                row = [c.callset_id for c in calls if c.has_variation]
                if row:
                    yield row
            return

        # Multi-dataset: all datasets share the same partitions, so records
        # with equal variant keys co-locate per window; join there (multi-set
        # --save-variants is rejected up front: --input-path resume loads ONE
        # dataset, so a joined save could not round-trip). Window
        # record-building streams through the same bounded thread pool the
        # single-set path uses (the Spark-executor analog,
        # ``pipeline/datasets.py:_parallel_shards``): windows N+1..N+k build
        # all their datasets' records while window N's join is consumed,
        # keeping --num-workers saturated instead of computing every
        # dataset's window serially per index. The join itself is the
        # streaming k-way ``sources/stream.py:merge_join`` over per-set
        # key-sorted streams: only the records of the CURRENT group key are
        # resident per set, which is exactly the merge-join term the
        # host-memory bound charges (``parallel/mesh.py:host_peak_bytes``).
        partitions = datasets[0].partitions()
        # One partition list per dataset, built once — not per window per
        # worker (a whole-genome join has thousands of windows).
        partition_lists = [dataset.partitions() for dataset in datasets]
        debug = self.conf.debug_datasets

        def window_records(index: int) -> List[List[Tuple[str, List[CallData]]]]:
            per_set: List[List[Tuple[str, List[CallData]]]] = []
            for dataset, parts in zip(datasets, partition_lists):
                part = parts[index]
                keyed: List[Tuple[str, List[CallData]]] = []
                for variant in (v for _, v in dataset.compute(part)):
                    if not self.filter_variant(variant):
                        continue
                    keyed.append(
                        (
                            variant.variant_key(debug),
                            extract_call_info(variant, self.indexes),
                        )
                    )
                # Within one window the records are per-set ordered but not
                # necessarily key-sorted; sort here (window-sized, bounded)
                # so merge_join's sortedness contract holds per stream.
                keyed.sort(key=lambda kr: kr[0])
                per_set.append(keyed)
            return per_set

        num_workers = getattr(self.conf, "num_workers", 8)
        stats = MergeJoinStats()
        for _, per_set in _parallel_shards(
            list(range(len(partitions))), window_records, num_workers
        ):
            for _key, groups in merge_join(
                [iter(keyed) for keyed in per_set], stats=stats
            ):
                if n_sets == 2:
                    # joinDatasets (``VariantsPca.scala:155-168``): inner
                    # join, concatenate both call lists.
                    calls_a, calls_b = groups
                    for ca in calls_a:
                        for cb in calls_b:
                            row = [
                                c.callset_id
                                for c in ca + cb
                                if c.has_variation
                            ]
                            if row:
                                yield row
                else:
                    # mergeDatasets (``VariantsPca.scala:176-188``): keep
                    # keys whose total record count equals the dataset
                    # count, flatten.
                    if sum(len(g) for g in groups) != n_sets:
                        continue
                    merged: List[CallData] = []
                    for records in groups:
                        for calls in records:
                            merged.extend(calls)
                    row = [c.callset_id for c in merged if c.has_variation]
                    if row:
                        yield row

    def _iter_calls_saving(self, dataset, path: str) -> Iterator[List[int]]:
        """Single-set wire ingest that ALSO materializes every shard as a
        checkpoint part while it streams (``--save-variants``): records are
        written UNFILTERED, before the AF filter — the reference applied its
        filters after ``getData`` (``VariantsPca.scala:112-148``), so a
        resumed run re-applies them and any threshold still works against
        the saved data. Stats are untouched (accounting lives in
        ``dataset.compute``). The manifest is written only after the last
        shard, so an interrupted save fails loudly on resume instead of
        silently analyzing a truncated cohort."""
        from spark_examples_tpu.pipeline.checkpoint import CheckpointWriter

        writer = CheckpointWriter(path)
        for _part, records in dataset.iter_shards():
            writer.write_shard(records)
            for _key, variant in records:
                if not self.filter_variant(variant):
                    continue
                calls = extract_call_info(variant, self.indexes)
                row = [c.callset_id for c in calls if c.has_variation]
                if row:
                    yield row
        writer.close()
        print(f"Saved {writer.total} variants to {path}.")

    # ------------------------------------------------------------ similarity

    def _make_mesh(self):
        return resolve_run_mesh(
            self.conf.mesh_shape,
            self.conf.num_reduce_partitions,
            devices=self.devices,
        )

    def _resolve_sharded(self, sharded: Optional[bool], mesh) -> bool:
        """``--similarity-strategy``: explicit dense/sharded, or auto from
        per-device memory (the reference's ~50K-samples/~20GB in-memory
        guidance, ``VariantsPca.scala:216-217,296-297``, restated in bytes
        against the actual HBM — ``ops/gramian.py:dense_strategy_fits``)."""
        from spark_examples_tpu.ops.gramian import dense_strategy_fits

        strategy = getattr(self.conf, "similarity_strategy", "auto")
        if sharded is None:
            if strategy == "sharded":
                sharded = True
            elif strategy == "dense":
                sharded = False
            else:
                sharded = not dense_strategy_fits(len(self.indexes))
        if sharded and (mesh is None or SAMPLES_AXIS not in mesh.shape or mesh.shape[SAMPLES_AXIS] < 2):
            if strategy == "sharded":
                raise ValueError(
                    "--similarity-strategy sharded needs a mesh with a "
                    "samples axis of at least 2 (use --mesh-shape data,samples)"
                )
            sharded = False
        return sharded

    def _plan_host_sharded_ingest(self) -> int:
        """Resolve ONCE whether this run ingests host-sharded, and over how
        many hosts — the pod-scale ingest split (``sharding/contig.py:
        partition_contigs_by_host``).

        Host-sharded ingest engages only when ALL of:

        - the run is multi-process (``jax.process_count() > 1``);
        - the resolved similarity strategy is DENSE — the sharded ring is a
          global SPMD program whose every process must feed the identical
          site stream in lockstep, so it keeps the full-cohort ingest;
        - the device path owns the data plane (``--pca-backend tpu``) with a
          live source (no ``--input-path`` resume, no ``--save-variants``
          wire materialization, no Gramian checkpoint cursor — a
          fast-forward cursor over a PARTITION would not match the artifact
          of a differently-sized fleet).

        When it engages, each process ingests only its contig partition on
        a process-local mesh and the partial Gramians are merged exactly at
        finalize (``_merge_host_partials``) — byte-identical to the
        single-process run, with per-host ingest bytes ~1/H of it.
        """
        if self._ingest_hosts is not None:
            return self._ingest_hosts
        hosts = 1
        conf = self.conf
        if (
            getattr(conf, "pca_backend", "tpu") == "tpu"
            and not getattr(conf, "input_path", None)
            and not getattr(conf, "save_variants", None)
            and not getattr(conf, "gramian_checkpoint_dir", None)
            and not getattr(conf, "resume_from", None)
        ):
            import jax

            if jax.process_count() > 1 and not self._resolve_sharded(
                None, self._make_mesh()
            ):
                hosts = jax.process_count()
        self._ingest_hosts = hosts
        return hosts

    def _host_contigs(self, contigs) -> List:
        """This process's contig partition under host-sharded ingest; the
        full list otherwise. The ONE seam every ingest path (wire, packed,
        device-generation) partitions through, so they cannot disagree on
        the split."""
        contigs = list(contigs)
        hosts = self._plan_host_sharded_ingest()
        if hosts <= 1:
            return contigs
        import jax

        from spark_examples_tpu.sharding.contig import host_partition

        local = host_partition(
            contigs,
            jax.process_index(),
            hosts,
            weight=self.source.declared_sites,
        )
        print(
            f"Host-sharded ingest: process {jax.process_index()} of "
            f"{hosts} reads {len(local)} of {len(contigs)} contig(s)."
        )
        return local

    def _ingest_mesh(self):
        """The dense accumulator's mesh: the run mesh, or — under
        host-sharded ingest — a mesh over THIS process's local devices
        only, so per-process ingest streams of different lengths never
        deadlock a global collective (each process accumulates its partial
        Gramian independently; the one cross-process collective is the
        finalize merge)."""
        if self._plan_host_sharded_ingest() > 1:
            import jax

            return resolve_run_mesh(
                None,
                self.conf.num_reduce_partitions,
                devices=jax.local_devices(),
            )
        return self._make_mesh()

    def _merge_host_partials(self, result):
        """The ONE cross-process collective of host-sharded ingest: gather
        every process's dense N×N partial Gramian and sum them exactly.
        ``G += XᵀX`` commutes over any partition of the row set, and the
        sum runs in an 8-byte intermediate (int64 for count partials,
        float64 otherwise) before casting back — int partials are exact
        outright, and float partials hold integer-valued counts inside the
        accumulator's proven exact window (GR005), so the merged matrix is
        byte-identical to the single-process result. No-op for
        single-process runs."""
        if self._plan_host_sharded_ingest() <= 1:
            return result
        from jax.experimental import multihost_utils

        partial = np.asarray(result)
        stacked = np.asarray(multihost_utils.process_allgather(partial))
        wide = (
            np.int64
            if np.issubdtype(partial.dtype, np.integer)
            else np.float64
        )
        return stacked.astype(wide).sum(axis=0).astype(partial.dtype)

    def _wrap_accumulator(self, acc):
        """Interpose the checkpoint feeder between the ingest stream and a
        fresh accumulator when checkpointing/resume is configured; a plain
        pass-through otherwise (zero overhead for normal runs). The feeder
        restores the persisted partial into ``acc`` on construction and
        fast-forwards the first ``checkpoint_sites`` rows it is fed."""
        conf = self.conf
        directory = getattr(conf, "gramian_checkpoint_dir", None)
        if directory is None and getattr(conf, "resume_from", None) is None:
            # Neither flag: pure pass-through, zero overhead. (A resume
            # flag with no complete artifact yet still gets a feeder — it
            # starts from zero and the manifest records that honestly.)
            return acc
        from spark_examples_tpu.pipeline.checkpoint import GramianFeeder

        self.feeder = GramianFeeder(
            acc,
            directory=directory,
            every_sites=getattr(conf, "checkpoint_every_sites", None),
            fingerprint=self._ckpt_fingerprint,
            resume=self._gramian_resume,
            registry=self.registry,
        )
        return self.feeder

    def _finish_checkpointing(self) -> None:
        """End of ingest: final snapshot (a crash between here and the
        finalize reduce resumes at O(1) re-ingest), then the registered
        pre-finalize kill-point — a no-op unless a fault plan names it."""
        if self.feeder is not None:
            self.feeder.finish()
        faults.kill_point("driver.pre-finalize")

    def get_similarity_matrix(
        self, calls: Iterable[List[int]], sharded: Optional[bool] = None
    ) -> np.ndarray:
        """Similarity counts G = XᵀX (``VariantsPca.scala:210-231`` dense
        strategy; ``sharded=True`` is the memory-bounded analog of
        ``getSimilarityMatrixStream``, ``:288-319``; ``None`` resolves
        ``--similarity-strategy``)."""
        n = len(self.indexes)
        if self.conf.pca_backend == "host":
            return self._host_similarity(calls)
        mesh = self._make_mesh()
        exact = getattr(self.conf, "exact_similarity", False)
        check_ranges = bool(getattr(self.conf, "check_ranges", False))
        if self._resolve_sharded(sharded, mesh):
            acc: object = ShardedGramianAccumulator(
                n, mesh, block_size=self.conf.block_size, exact_int=exact,
                registry=self.registry, spans=self.spans,
                pack_bits=getattr(self.conf, "ring_pack_bits", "auto"),
                check_ranges=check_ranges,
                reduce_schedule=getattr(
                    self.conf, "reduce_schedule", "auto"
                ),
            )
        else:
            acc = GramianAccumulator(
                n, self._ingest_mesh(), block_size=self.conf.block_size,
                exact_int=exact, registry=self.registry, spans=self.spans,
                check_ranges=check_ranges,
            )
        # Duplicate callset indices only arise when a variant set is joined
        # with itself (duplicate ids collapse the column index); only then is
        # the slower unbuffered accumulation needed to reproduce the
        # reference's pair-loop multiplicity (``VariantsPca.scala:224-229``).
        ids = self.conf.variant_set_id
        accumulate_index_rows(
            self._wrap_accumulator(acc),
            calls,
            n,
            self.conf.block_size,
            accumulate_duplicates=len(set(ids)) != len(ids),
        )
        self._finish_checkpointing()
        # Stay on device either way: centering/PCA consume this directly;
        # fetching the N×N matrix to host would only copy it back. The
        # sharded result
        # remains row-tile-sharded (padded) for the sharded PCA stage.
        if isinstance(acc, GramianAccumulator):
            return self._merge_host_partials(acc.finalize_device())
        self._sched_block = acc.schedule_block()
        return acc.finalize_sharded()

    def get_similarity_rows(
        self,
        blocks: Iterable[np.ndarray],
        sharded: Optional[bool] = None,
        pipeline_depth: Optional[int] = None,
    ) -> np.ndarray:
        """Packed fast path: feed dense uint8 row blocks directly.

        ``pipeline_depth`` (dense accumulator only) keeps that many flushed
        device updates in flight instead of syncing per flush — the
        double-buffered feed that overlaps block *k+1*'s host pack +
        ``device_put`` with block *k*'s Gramian dispatch
        (``ops/gramian.py``)."""
        n = len(self.indexes)
        if self.conf.pca_backend == "host":
            # Host oracle on the packed rows (same result surface as
            # _host_similarity): keeps compute_pca's host branch centered
            # over the true N.
            matrix = np.zeros((n, n), dtype=np.int64)
            for block in blocks:
                X = np.asarray(block, dtype=np.int64)
                matrix += X.T @ X
            return matrix.astype(np.float64)
        mesh = self._make_mesh()
        exact = getattr(self.conf, "exact_similarity", False)
        check_ranges = bool(getattr(self.conf, "check_ranges", False))
        if self._resolve_sharded(sharded, mesh):
            acc: object = ShardedGramianAccumulator(
                n, mesh, block_size=self.conf.block_size, exact_int=exact,
                registry=self.registry, spans=self.spans,
                pack_bits=getattr(self.conf, "ring_pack_bits", "auto"),
                check_ranges=check_ranges,
                reduce_schedule=getattr(
                    self.conf, "reduce_schedule", "auto"
                ),
            )
        else:
            acc = GramianAccumulator(
                n,
                self._ingest_mesh(),
                block_size=self.conf.block_size,
                exact_int=exact,
                pipeline_depth=pipeline_depth,
                registry=self.registry,
                spans=self.spans,
                check_ranges=check_ranges,
            )
        feed = self._wrap_accumulator(acc)
        for block in blocks:
            feed.add_rows(block)
        self._finish_checkpointing()
        if isinstance(acc, GramianAccumulator):
            return self._merge_host_partials(acc.finalize_device())
        self._sched_block = acc.schedule_block()
        return acc.finalize_sharded()

    def get_similarity_device_gen(self, contigs) -> "object":
        """Fully fused TPU ingest+similarity for the synthetic source: the
        host streams per-site thresholds, the device generates genotypes and
        accumulates ``G += XᵀX`` in one scanned XLA program per dispatch group
        (``ops/devicegen.py``).

        Multi-dataset configurations need no join machinery here: synthetic
        variant sets share the site grid, so the reference's 2-set join and
        ≥3-set merge-intersect (``VariantsPca.scala:155-188``) reduce to
        column concatenation of per-set genotype matrices — verified against
        the wire path in tests.

        The ``ingest`` span covers the call; its children are
        ``accumulator-init`` (G and the counters), ``enqueue`` (the contig
        loop), on ring runs ``finalize`` (the row tiles' assembly; attribute
        ``ring_mirrored_tiles``, the blocks per device taken from another
        device and transposed) and ``sync`` (the closing fetch). Inside
        ``enqueue``, the
        ``dispatch`` aggregate is the host time spent handing dispatches to
        the runtime (where the host waits on a full device queue) and
        ``stats`` the per-shard stats accounting; its self time is the
        loop's own host work. ``ingest`` carries
        ``sites_valid`` and ``sites_capacity``, the padding of the
        dispatched grid, ``pop_segments``, the population segments
        generated in one pass (0 where the thresholds are gathered),
        ``gramian_bytes_per_device``, one device's tile of the finished G,
        ``state_bytes_per_device``, one copy of the accumulator state (the
        dense tiles on and above the diagonal, or the half ring's step
        tiles), ``gramian_copies_max``, the most copies of that state the
        loop can keep live (``ops/devicegen.py:gramian_copies_max``),
        ``dispatch_depth``, the queued dispatches the memory rule allowed
        (``ops/devicegen.py:dispatch_depth``; absent where no dispatch ran),
        ``dense_tiles_per_side``, the column tiles per side of the dense
        state (``ops/devicegen.py:dense_tiles_per_side``; 1 where it is one
        G, and on the ring); on ring runs only ``ring_bytes``, the ring's
        ICI bytes, and ``ring_dots_per_block``, the int8 dots per block and
        device (⌊D/2⌋+1 on the half ring); and ``device_peak_bytes``, the
        largest
        device memory peak over the loop's local devices after the sync
        (absent where devices report no memory stats, as the CPU's do).
        """
        from spark_examples_tpu.ops.devicegen import auto_blocks_per_dispatch

        with self.spans.span("ingest") as span:
            conf = self.conf
            mesh = self._make_mesh()
            # Dispatch-group length: explicit flag, or constant-work auto rule
            # (small cohorts get longer scans — per-dispatch overhead is fixed).
            # `is None`, not falsy-or: config validation rejects non-positive
            # explicit values, and a falsy test would silently remap them to
            # auto if that gate were ever bypassed.
            blocks_per_dispatch = (
                conf.blocks_per_dispatch
                if conf.blocks_per_dispatch is not None
                else auto_blocks_per_dispatch(len(self.indexes), conf.block_size)
            )
            use_ring = self._resolve_sharded(None, mesh)
            # The generation ring speaks both schedules: `hier` factors the
            # samples axis host-major and runs the two-level tile exchange
            # (ops/gramian.py:_hier_ring_tiles inside ops/devicegen.py:
            # _ring_update), byte-identical to flat. An explicit hier request
            # whose host factor does not divide the samples axis still raises
            # inside the accumulator — same policy as the host-fed path.
            reduce_schedule = getattr(conf, "reduce_schedule", "auto")
            if not use_ring:
                # Dense multi-process: host-sharded pod ingest. Each process
                # generates/accumulates only its contig partition on its local
                # devices; the partials merge exactly at finalize.
                contigs = self._host_contigs(contigs)
                mesh = self._ingest_mesh()
            with self.spans.span("accumulator-init"):
                acc = self._device_gen_accumulator(
                    mesh, use_ring, blocks_per_dispatch, reduce_schedule
                )

            from spark_examples_tpu.obs.metrics import (
                INGEST_PARTITIONS_PLANNED,
                INGEST_SITES_SCANNED,
                well_known_gauge,
            )

            source: SyntheticGenomicsSource = self.source  # type: ignore[assignment]
            self._device_gen_scanned = 0
            sites_gauge = well_known_gauge(self.registry, INGEST_SITES_SCANNED)
            ring_counter = None
            if use_ring:
                from spark_examples_tpu.obs.metrics import (
                    GRAMIAN_RING_BYTES,
                    well_known_counter,
                )

                # Deterministic host-side accounting of the ICI ring traffic
                # (the device-generation ring has no host flush to instrument);
                # same counter the host-fed sharded accumulator feeds. Advanced
                # per contig so the heartbeat's "ring traffic" segment is live
                # during ingest, not a post-finalize surprise.
                ring_counter = well_known_counter(self.registry, GRAMIAN_RING_BYTES)
            ring_bytes_published = 0
            with self.spans.span("enqueue"):
                stats_start = time.perf_counter()
                # One shard enumeration per contig, shared by the planned-work
                # gauge and the per-contig stats accounting below.
                shards_by_contig = [
                    (contig, contig.get_shards(conf.bases_per_partition))
                    for contig in contigs
                ]
                well_known_gauge(self.registry, INGEST_PARTITIONS_PLANNED).set(
                    sum(len(shards) for _, shards in shards_by_contig)
                    * len(conf.variant_set_id)
                )
                stats_seconds = time.perf_counter() - stats_start
                for contig, shards in shards_by_contig:
                    k0, k1 = source.site_grid_range(contig)
                    if k1 > k0:
                        acc.add_grid(k0, k1)
                    self._device_gen_scanned += k1 - k0
                    sites_gauge.set(self._device_gen_scanned)
                    if ring_counter is not None:
                        ring_counter.inc(acc.ring_bytes_total - ring_bytes_published)
                        ring_bytes_published = acc.ring_bytes_total
                    if self.io_stats is not None:
                        stats_start = time.perf_counter()
                        # Wire-equivalent accounting: per shard, per variant set
                        # (``SyntheticGenomicsSource.page_requests``).
                        for _ in conf.variant_set_id:
                            for shard in shards:
                                self.io_stats.add_partition(shard.range)
                        self.io_stats.add_requests(
                            source.page_requests(contig, conf.bases_per_partition)
                            * len(conf.variant_set_id)
                        )
                        stats_seconds += time.perf_counter() - stats_start
                self.spans.add("dispatch", acc.dispatch_ns * 1e-9)
                self.spans.add("stats", stats_seconds)
            self._device_gen_acc = acc
            if use_ring:
                # Row-sharded (padded) result; compute_pca routes to the sharded
                # centering/eigensolve from its NamedSharding. The accumulator
                # lets go of its state here (the half ring's step tiles, or
                # the row tile the result then takes), so centering's output
                # is the job's second row tile per device, not its third.
                self._sched_block = acc.schedule_block()
                with self.spans.span("finalize") as finalize:
                    result = acc.finalize_sharded(donate=True)
                finalize.attrs["ring_mirrored_tiles"] = int(acc.ring_mirrored_tiles)
            else:
                result = self._merge_host_partials(acc.finalize_device())
            from spark_examples_tpu.obs.metrics import (
                DEVICEGEN_DISPATCHES,
                DEVICEGEN_SITES_CAPACITY,
            )

            well_known_gauge(self.registry, DEVICEGEN_DISPATCHES).set(
                acc.dispatches
            )
            # Dispatched grid capacity vs the valid sites inside it — the
            # padding-waste denominator (the fixed tail-group overhead that
            # dominates small regions). Ring traffic was already published
            # incrementally inside the ingest loop.
            well_known_gauge(self.registry, DEVICEGEN_SITES_CAPACITY).set(
                acc.sites_capacity
            )
            # Epilogue: record the device-counted variant rows (per variant set,
            # rows with variation in that set's columns — the same count the
            # packed host path reports after its nonzero drop). Doing it here
            # rather than leaving a flush for callers to remember keeps the
            # stats-parity invariant even if a later stage raises, and the
            # synchronous counter fetch makes the ingest stage's wall-clock
            # honest on asynchronous backends. With stats disabled only the
            # honesty sync remains (one fetch instead of two).
            with self.spans.span("sync"):
                if self.io_stats is not None:
                    per_set, _kept = acc.ingest_counters()
                    self.io_stats.add_variants(int(per_set.sum()))
                else:
                    acc.sync()
            span.attrs.update(
                sites_valid=int(acc.sites_valid),
                sites_capacity=int(acc.sites_capacity),
                pop_segments=int(acc.pop_segments),
                gramian_bytes_per_device=int(acc.gramian_bytes_per_device),
                state_bytes_per_device=int(acc.state_bytes_per_device),
                gramian_copies_max=int(acc.gramian_copies_max),
                dense_tiles_per_side=int(acc.dense_tiles_per_side),
            )
            if acc.depth is not None:
                span.attrs["dispatch_depth"] = int(acc.depth)
            if use_ring:
                span.attrs.update(
                    ring_bytes=int(acc.ring_bytes_total),
                    ring_dots_per_block=int(acc.ring_dots_per_block),
                )
            peak = _device_peak_bytes(acc.kept_sites.sharding.addressable_devices)
            if peak is not None:
                span.attrs["device_peak_bytes"] = peak
        return result

    def _device_gen_accumulator(
        self, mesh, use_ring: bool, blocks_per_dispatch: int, reduce_schedule
    ):
        """The device-generation accumulator of this run's configuration:
        the ring when the samples axis is sharded, else the dense one."""
        from spark_examples_tpu.ops.devicegen import (
            DeviceGenGramianAccumulator,
            DeviceGenRingGramianAccumulator,
        )
        from spark_examples_tpu.sources.synthetic import af_filter_micro

        source: SyntheticGenomicsSource = self.source  # type: ignore[assignment]
        conf = self.conf
        if use_ring and len(conf.variant_set_id) > 1:
            # Sharded multi-set: the joint cohort's concatenated per-set
            # column blocks ride the same ring kernel (the join/merge
            # scenario past the dense HBM rule, ``VariantsPca.scala:
            # 155-188`` — previously a silent fallback to host wire
            # ingest, orders of magnitude slower).
            sizes = [source.num_samples_for(v) for v in conf.variant_set_id]
            acc: object = DeviceGenRingGramianAccumulator(
                num_samples=source.num_samples,
                vs_key=[
                    source.genotype_stream_key(v) for v in conf.variant_set_id
                ],
                pops=source.populations,
                site_key=source.site_key,
                spacing=source.variant_spacing,
                ref_block_fraction=source.ref_block_fraction,
                mesh=mesh,
                min_af_micro=af_filter_micro(conf.min_allele_frequency),
                block_size=conf.block_size,
                blocks_per_dispatch=blocks_per_dispatch,
                exact_int=True,
                n_pops=source.n_pops,
                set_sizes=sizes,
                pops_per_set=[
                    source.populations_for(v) for v in conf.variant_set_id
                ],
                pack_bits=getattr(conf, "ring_pack_bits", "auto"),
                reduce_schedule=reduce_schedule,
            )
        elif use_ring:
            # Sharded strategy, fully on device: each samples-slice
            # generates its own column block and ring-exchanges tiles — the
            # large-cohort (~50K samples) regime with zero host traffic.
            acc = DeviceGenRingGramianAccumulator(
                num_samples=source.num_samples_for(conf.variant_set_id[0]),
                vs_key=source.genotype_stream_key(conf.variant_set_id[0]),
                pops=source.populations_for(conf.variant_set_id[0]),
                site_key=source.site_key,
                spacing=source.variant_spacing,
                ref_block_fraction=source.ref_block_fraction,
                mesh=mesh,
                min_af_micro=af_filter_micro(conf.min_allele_frequency),
                block_size=conf.block_size,
                blocks_per_dispatch=blocks_per_dispatch,
                exact_int=True,
                n_pops=source.n_pops,
                pack_bits=getattr(conf, "ring_pack_bits", "auto"),
                reduce_schedule=reduce_schedule,
            )
        else:
            # Asymmetric joint cohorts (per-set sizes) ride the same kernel
            # via concatenated per-set population vectors.
            sizes = [source.num_samples_for(v) for v in conf.variant_set_id]
            asymmetric = any(s != source.num_samples for s in sizes)
            acc = DeviceGenGramianAccumulator(
                num_samples=source.num_samples,
                vs_keys=[
                    source.genotype_stream_key(v) for v in conf.variant_set_id
                ],
                pops=source.populations,
                site_key=source.site_key,
                spacing=source.variant_spacing,
                ref_block_fraction=source.ref_block_fraction,
                min_af_micro=af_filter_micro(conf.min_allele_frequency),
                block_size=conf.block_size,
                blocks_per_dispatch=blocks_per_dispatch,
                exact_int=True,
                mesh=mesh,
                n_pops=source.n_pops,
                set_sizes=sizes if asymmetric else None,
                pops_per_set=(
                    [source.populations_for(v) for v in conf.variant_set_id]
                    if asymmetric
                    else None
                ),
            )
        return acc

    def _host_similarity(self, calls: Iterable[List[int]]) -> np.ndarray:
        """Literal host replication of ``getSimilarityMatrix``
        (``VariantsPca.scala:222-231``)."""
        n = len(self.indexes)
        matrix = np.zeros((n, n), dtype=np.int64)
        for row in calls:
            idx = np.asarray(row, dtype=np.int64)
            # Unbuffered accumulation: duplicate callset indices contribute
            # per occurrence pair, as the reference's loop does
            # (``VariantsPca.scala:224-229``).
            np.add.at(matrix, np.ix_(idx, idx), 1)
        return matrix.astype(np.float64)

    # ----------------------------------------------------------------- pca

    def compute_pca(self, similarity) -> List[Tuple[str, List[float]]]:
        """Center + eigendecompose (``VariantsPca.scala:238-271``).

        ``similarity`` may be a host array or a device-resident matrix from
        :meth:`get_similarity_matrix`; the TPU path runs every stage on
        device and fetches only the (N, num_pc) result.

        Spans: ``center`` and ``eigh`` time the host ENQUEUE of their
        device programs (nothing waits for the device inside them); the
        ``fetch`` span around the one synchronous transfer of the
        components waits for all of it.
        """
        import jax
        import jax.numpy as jnp

        n = len(self.indexes)
        sharded_mesh = _samples_sharded_mesh(similarity)
        if self.conf.pca_backend == "host":
            similarity = np.asarray(similarity)
            nonzero = int((similarity.sum(axis=1) > 0).sum())
            print(f"Non zero rows in matrix: {nonzero} / {n}.")
            centered = self._host_center(similarity)
            components, _ = mllib_reference_pca(centered, self.conf.num_pc)
        elif sharded_mesh is not None:
            # Sharded strategy end to end: the (padded) Gramian stays
            # row-tile-sharded through centering AND the eigensolve — no
            # device ever holds the full N×N (the large-N completion of
            # ``VariantsPca.scala:288-319``'s memory-bounded path).
            from spark_examples_tpu.ops.centering import gower_center_sharded
            from spark_examples_tpu.ops.pca import (
                principal_components_subspace_sharded,
            )

            # Centering arithmetic in float64 (fused upcast, f32 tiles out):
            # the reference centers in Double (``VariantsPca.scala:
            # 246-263``), and whole-genome counts exceed f32's 2^24 exact
            # range — this is what keeps --exact-similarity exact PAST the
            # accumulator (ops/centering.py:_dtypes).
            with self.spans.span("center"):
                with jax.enable_x64(True):
                    centered = gower_center_sharded(
                        similarity, sharded_mesh, n_true=n
                    )
            with self.spans.span("eigh"):
                device_components, _ = principal_components_subspace_sharded(
                    centered, sharded_mesh, self.conf.num_pc, n_true=n
                )
            # any() rather than sum() > 0: entries are non-negative counts,
            # and int32 row sums would overflow at whole-genome scale. Under
            # x64 because the finalize reduce hands back an int64 Gramian.
            with jax.enable_x64(True):
                nz = jnp.any(similarity != 0, axis=1).sum()
            with self.spans.span("fetch"):
                fetched, nonzero = _fetch_components_and_nonzero(
                    device_components, nz, sharded_mesh
                )
            print(f"Non zero rows in matrix: {nonzero} / {n}.")
            components = fetched.astype(np.float64)[:n]
        else:
            # Subspace iteration, not full eigh: num_pc is tiny and XLA's TPU
            # eigh is pathologically slow at cohort sizes (see ops/pca.py).
            # f64 centering arithmetic under x64 (the reference's Double
            # centering) with f32 out for the eigensolve; identical results
            # for an int32 exact Gramian and an f32 Gramian holding the
            # same integers (ops/centering.py:_dtypes). The asarray sits
            # INSIDE the x64 block so a float64 host similarity (exact
            # counts past 2^24) is not silently truncated to f32 on entry.
            with self.spans.span("center"):
                with jax.enable_x64(True):
                    S = jnp.asarray(similarity)
                    centered = gower_center(S)
                centered = centered.astype(jnp.float32)
            with self.spans.span("eigh"):
                device_components, _ = principal_components_subspace(
                    centered, self.conf.num_pc
                )
            # All dispatches issued; fetching results is now safe. any()
            # rather than sum() > 0: int32 row sums would overflow at
            # whole-genome scale. Under x64 because S may be the int64
            # result of the finalize reduce.
            with jax.enable_x64(True):
                nz = jnp.any(S != 0, axis=1).sum()
            with self.spans.span("fetch"):
                fetched, nonzero = _fetch_components_and_nonzero(
                    device_components, nz, None
                )
            print(f"Non zero rows in matrix: {nonzero} / {n}.")
            components = fetched.astype(np.float64)
        return list(zip(self.callset_ids, components.tolist()))

    @staticmethod
    def _host_center(similarity: np.ndarray) -> np.ndarray:
        """Literal replication of the centering at ``VariantsPca.scala:246-263``."""
        n = similarity.shape[0]
        row_sums = similarity.sum(axis=1)
        matrix_mean = row_sums.sum() / n / n
        row_mean = row_sums / n
        col_mean = row_sums / n  # symmetric matrix: column sums == row sums
        return similarity - row_mean[:, None] - col_mean[None, :] + matrix_mean

    # ----------------------------------------------------------------- emit

    def emit_result(self, result: Sequence[Tuple[str, List[float]]]) -> List[str]:
        """Print and optionally save the TSV (``VariantsPca.scala:273-286``).

        Console format: ``name<TAB>dataset<TAB>pc...``, sorted by name; saved
        format keeps the reference's column order ``name, pcs..., dataset``
        under ``<output-path>-pca.tsv/part-00000``.
        """
        rows = []
        for callset_id, pcs in result:
            dataset = callset_id.split("-")[0]
            rows.append((self.names[callset_id], dataset, pcs))
        rows.sort(key=lambda r: r[0])
        lines = []
        for name, dataset, pcs in rows:
            pc_text = "\t".join(str(c) for c in pcs)
            lines.append(f"{name}\t{dataset}\t{pc_text}")
            print(lines[-1])
        if self.conf.output_path:
            out_dir = self.conf.output_path + "-pca.tsv"
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, "part-00000"), "w") as f:
                for name, dataset, pcs in rows:
                    pc_text = "\t".join(str(c) for c in pcs)
                    f.write(f"{name}\t{pc_text}\t{dataset}\n")
        return lines

    # ---------------------------------------------------------------- stats

    def report_io_stats(self) -> None:
        if self.io_stats is not None:
            print(str(self.io_stats))

    def stop(self) -> None:
        pass  # no SparkContext to tear down; kept for API parity


@dataclass
class PipelineResult:
    """One completed analysis: the emitted TSV lines (empty for
    similarity-only runs), the similarity summary (similarity-only runs),
    the run-manifest document when one was built, and the path it was
    written to when the write succeeded. This is the library surface the
    resident service (``serve/executor.py``) consumes; ``run`` keeps the
    historical lines-only CLI contract on top of it."""

    lines: List[str]
    similarity_summary: Optional[Dict] = None
    manifest: Optional[Dict] = None
    manifest_path: Optional[str] = None


def jax_default_device(device):
    """``jax.default_device(device)`` behind a lazy import (the driver
    module must stay importable without initializing a backend)."""
    import jax

    return jax.default_device(device)


def run(argv: Sequence[str]) -> List[str]:
    """``VariantsPcaDriver.main`` (``VariantsPca.scala:47-59``)."""
    conf = PcaConf.parse(argv)
    conf.init_distributed()
    return run_pipeline(conf).lines


def run_pipeline(
    conf: PcaConf,
    similarity_only: bool = False,
    devices=None,
    run_id: Optional[str] = None,
) -> PipelineResult:
    """The run-an-analysis core, CLI-free: config in, result + manifest
    out. ``run`` (batch) and the resident service's executor
    (``serve/executor.py``) both call this, so a served job and a batch
    invocation execute the identical pipeline and produce the identical
    schema-v2 manifest. ``similarity_only`` stops after the
    ingest+similarity stage and returns a host-side summary of the
    Gramian instead of PC rows (the service's similarity request kind).
    ``devices`` restricts the run to an executor slice's devices
    (``parallel/mesh.py:plan_executor_slices``): meshes resolve over the
    slice only, and mesh-less (dense, single-device) work is pinned to
    the slice's first device so concurrent slices never contend for one
    default device. ``run_id`` stamps the run's spans (a served job's
    trace id; a fresh id when ``None``)."""
    if getattr(conf, "fault_plan", None) is not None:
        # The flag wins over the SPARK_EXAMPLES_TPU_FAULTS environment
        # variable; configuring resets hit counts, so every run starts a
        # fresh deterministic schedule.
        faults.configure(conf.fault_plan)
    else:
        # Force the lazy env-var plan to parse NOW: a typo'd site name
        # must fail here in milliseconds, not hours later at the first
        # checkpoint hook of a whole-genome run.
        faults.active()
    synthetic_tpu = (
        conf.source == "synthetic"
        and not conf.input_path
        and conf.pca_backend == "tpu"
    )
    # Device generation needs distinct variant sets (duplicate ids collapse
    # the column index, a same-set join the wire path handles via count
    # multiplicity). Both strategies now cover multi-set configurations:
    # dense concatenates per-set column blocks, and past the HBM rule the
    # ring kernel does the same per samples-slice
    # (``get_similarity_device_gen``).
    unique_sets = len(set(conf.variant_set_id)) == len(conf.variant_set_id)
    device_ok = unique_sets
    use_device = conf.ingest == "device" or (
        conf.ingest == "auto" and synthetic_tpu and device_ok
    )
    if conf.ingest == "auto" and synthetic_tpu and not device_ok:
        # The one remaining fallback to wire ingest must be loud — it is
        # orders of magnitude slower than device generation.
        print(
            "Device ingest unavailable (duplicate variant-set ids collapse "
            "the column index); using wire ingest."
        )
    # Every auto-eligible synthetic single-set config now takes the device
    # path (dense or ring); packed ingest remains available explicitly —
    # for the synthetic source AND for single-set VCF file inputs (the
    # native-parser fast path, ``sources/files.py:genotype_blocks``).
    use_packed = conf.ingest == "packed"
    file_packed = (
        conf.source == "file"
        and not conf.input_path
        and conf.pca_backend == "tpu"
    )
    source = make_source(conf) if conf.source != "rest" else None
    if (
        not use_packed
        and conf.ingest == "auto"
        and file_packed
        and len(conf.variant_set_id) == 1
        and isinstance(source, FileGenomicsSource)
        and source.wants_streaming(conf.variant_set_id[0])
    ):
        # Auto-ingest for a large (or explicitly streamed) single-set VCF:
        # the packed path with the bounded-memory streaming pass — the wire
        # path would materialize the whole file as Python records.
        use_packed = True
    if conf.save_variants:
        # The writer materializes WIRE records shard by shard; device/packed
        # ingest never builds them. 'auto' quietly takes the wire path;
        # an explicit fast-path request conflicts and must fail loudly.
        if conf.ingest in ("device", "packed"):
            raise ValueError(
                "--save-variants materializes wire records; it needs the "
                "wire ingest (--ingest wire, or leave --ingest auto)"
            )
        if conf.input_path:
            raise ValueError(
                "--save-variants with --input-path would re-save an "
                "existing checkpoint; copy the directory instead"
            )
        if len(conf.variant_set_id) != 1:
            raise ValueError(
                "--save-variants supports a single variant set "
                "(--input-path resume loads one dataset)"
            )
        if isinstance(source, FileGenomicsSource) and source.wants_streaming(
            conf.variant_set_id[0]
        ):
            # The wire ingest the writer needs would materialize every
            # record of a streaming-scale VCF in host memory — refuse
            # rather than silently OOM a file that runs fine without the
            # flag. (The input is already an on-disk source; resume from
            # it directly.)
            raise ValueError(
                "--save-variants uses the wire ingest, which would load "
                "this streaming-scale VCF fully into host memory; the "
                "input is already resumable from disk. Force the in-memory "
                "path with --stream-chunk-bytes 0 if the host has room."
            )
        use_device = False
        use_packed = False
    if getattr(conf, "gramian_checkpoint_dir", None) or getattr(
        conf, "resume_from", None
    ):
        # Gramian checkpointing snapshots the DEVICE accumulator against a
        # host-fed, deterministically-ordered row cursor; the host backend
        # has no accumulator and the fused on-device generator has no
        # host-side cursor to fast-forward.
        if conf.pca_backend != "tpu":
            raise ValueError(
                "--gramian-checkpoint-dir/--resume-from checkpoint the "
                "device accumulator; they need --pca-backend tpu"
            )
        if conf.ingest == "device":
            raise ValueError(
                "--ingest device has no host-fed row cursor to checkpoint "
                "or resume; use --ingest packed or wire (or leave --ingest "
                "auto, which falls back for checkpointed runs)"
            )
        if use_device:
            print(
                "Device ingest disabled for Gramian checkpointing (the "
                "fused generator has no host-fed cursor); using "
                + (
                    "packed ingest."
                    if len(conf.variant_set_id) == 1
                    else "wire ingest."
                )
            )
            use_device = False
            use_packed = len(conf.variant_set_id) == 1
    if use_device and not (synthetic_tpu and device_ok):
        raise ValueError(
            "--ingest device requires --source synthetic, --pca-backend tpu, "
            "and distinct variant-set ids"
        )
    if use_packed and not (synthetic_tpu or file_packed):
        raise ValueError(
            "--ingest packed requires --pca-backend tpu and --source "
            "synthetic or file (VCF inputs)"
        )
    if use_packed and len(conf.variant_set_id) != 1:
        raise ValueError(
            "--ingest packed supports a single variant set; use --ingest "
            "device (distinct sets) or --ingest wire"
        )
    if use_packed and file_packed and not synthetic_tpu:
        # Fail fast here with the other ingest preconditions, not from a
        # worker thread mid-pipeline: packed file ingest is VCF-only.
        from spark_examples_tpu.sources.files import file_set_ids

        selected = dict(zip(file_set_ids(conf.input_files or []), conf.input_files))[
            conf.variant_set_id[0]
        ]
        lowered = selected[:-3] if selected.endswith(".gz") else selected
        if not lowered.endswith(".vcf"):
            raise ValueError(
                f"--ingest packed needs a .vcf[.gz] input; got {selected!r} "
                "(use --ingest wire for JSONL/checkpoint inputs)"
            )
    driver = VariantsPcaDriver(conf, source, devices=devices, run_id=run_id)
    _export_compile_cache_gauges(driver.registry)
    from spark_examples_tpu.utils.tracing import StageTimes, device_trace

    # Stages record into the driver's span recorder, so the manifest's span
    # tree and the printed "Stage timings" report are views of one
    # measurement; deeper phases (chunk-parse, dispatch, reduce-flush,
    # center, eigh) nest under the stages they ran in.
    times = StageTimes(recorder=driver.spans)
    heartbeat = None
    if getattr(conf, "heartbeat_seconds", 0) and conf.heartbeat_seconds > 0:
        from spark_examples_tpu.obs.heartbeat import Heartbeat

        heartbeat = Heartbeat(conf.heartbeat_seconds, driver.registry).start()
    similarity_summary: Optional[Dict] = None
    recorder = None
    if getattr(conf, "trace_dir", None):
        # Crash-durable stage timeline (obs/recorder.py): one segment per
        # process named by its multi-controller identity, so an N-process
        # run's timelines merge into ONE Chrome trace (`trace export
        # --run-dir <dir>`) with each host its own trace process row.
        from spark_examples_tpu.obs.recorder import FlightRecorder

        import jax

        recorder = FlightRecorder(
            conf.trace_dir, f"host{jax.process_index()}"
        )
        recorder.begin("run", tid="pipeline")
    import contextlib

    # Slice placement: without a mesh, jit'd work lands on the process
    # default device — two concurrent slices would silently share device
    # 0. Pinning the default to the slice's first device keeps mesh-less
    # paths (dense accumulator, small cohorts) inside the slice too.
    placement = (
        jax_default_device(devices[0])
        if devices
        else contextlib.nullcontext()
    )
    try:
        with placement, device_trace(conf.profile_dir):
            # The device path already ends in a synchronous counter fetch
            # (the stats epilogue); packed/wire paths end in a one-scalar
            # fetch so the stage wall-clock is honest on asynchronous
            # backends rather than dispatch-time only (utils/tracing.py).
            if recorder is not None:
                recorder.begin("ingest+similarity", tid="pipeline")
            with times.stage("ingest+similarity"):
                similarity = _similarity_stage(
                    conf, driver, use_device, use_packed
                )
                if not use_device:
                    _sync_scalar(similarity)
            if recorder is not None:
                recorder.end("ingest+similarity", tid="pipeline")
                if (driver._ingest_hosts or 1) > 1:
                    recorder.record(
                        "host_sharded_ingest",
                        tid="pipeline",
                        hosts=int(driver._ingest_hosts),
                    )
            if similarity_only:
                result = None
                similarity_summary = _summarize_similarity(
                    similarity, len(driver.indexes)
                )
            else:
                # compute_pca ends in the synchronous components fetch, so
                # its stage time includes the device work it dispatched.
                if recorder is not None:
                    recorder.begin("center+pca", tid="pipeline")
                with times.stage("center+pca"):
                    result = driver.compute_pca(similarity)
                if recorder is not None:
                    recorder.end("center+pca", tid="pipeline")
    finally:
        # Emits-then-stops-cleanly contract: a mid-run exception gets its
        # last heartbeat, then silence — never a progress line racing the
        # traceback (or a leaked thread outliving the run).
        if heartbeat is not None:
            heartbeat.stop()
        if recorder is not None:
            # Durability before correctness of shape: whatever happened
            # above, the events recorded so far reach the segment file
            # (the crash-durable contract; an open "run" span exports as
            # a truncated span, never disappears).
            recorder.flush()
    # Warm the ledger only now, with every kernel this run dispatches
    # compiled and executed — a failure above must not leave a fingerprint
    # behind that makes a retry report "warm" for kernels never built. The
    # kind is part of the key: a similarity-only run does not pre-warm the
    # PCA geometry. Recorded before the manifest snapshot below so the
    # run's own hit/miss is in its own manifest.
    from spark_examples_tpu.utils.cache import (
        compile_fingerprint,
        record_geometry,
    )

    record_geometry(
        compile_fingerprint(
            conf, kind="similarity" if similarity_only else "pca"
        )
    )
    _register_prover_conformance(driver)
    lines = driver.emit_result(result) if result is not None else []
    driver.report_io_stats()
    if conf.profile_dir:
        print(str(times))
        print(f"Device trace written to {conf.profile_dir}.")
    import jax

    manifest_doc: Optional[Dict] = None
    manifest_path: Optional[str] = None
    if getattr(conf, "metrics_json", None) or jax.process_count() > 1:
        # Built LAST, after every report printed above, so the manifest
        # snapshots the same registry state the epilogue rendered — the
        # numbers are identical by construction, not by parallel
        # bookkeeping. Multi-controller runs build it on EVERY process
        # (not only those given --metrics-json): the cross-process counter
        # aggregation inside is a collective, and a process skipping it
        # would deadlock the ones that reached it.
        from spark_examples_tpu.obs.manifest import (
            build_run_manifest,
            write_manifest,
        )

        resume_block = None
        if driver.feeder is not None:
            # The v2-additive ``resume`` block: where this run started
            # from (0 for a fresh checkpointed run), how much ingest the
            # cursor fast-forwarded, and whether any deterministic fault
            # fired in-process — the chaos matrix's assertion surface.
            resume_block = {
                "checkpoint_sites": int(driver.feeder.checkpoint_sites),
                "sites_skipped": int(driver.feeder.sites_skipped),
                "faults_injected": int(faults.injected_count()),
            }
        manifest_doc = build_run_manifest(
            conf=conf,
            spans=driver.spans,
            registry=driver.registry,
            io_stats=driver.io_stats,
            overlap=driver._overlap,
            resume=resume_block,
            schedule=driver._sched_block,
        )
        if conf.metrics_json:
            try:
                write_manifest(conf.metrics_json, manifest_doc)
            except OSError as e:
                # A bad path must not destroy hours of completed compute:
                # the results are already printed/returned — report the
                # telemetry loss loudly and keep the run's exit intact.
                import sys

                print(
                    f"Run manifest NOT written to {conf.metrics_json}: {e}",
                    file=sys.stderr,
                )
            else:
                manifest_path = conf.metrics_json
                print(f"Run manifest written to {conf.metrics_json}.")
    if recorder is not None:
        recorder.end("run", tid="pipeline")
        recorder.close()
    driver.stop()
    return PipelineResult(
        lines=lines,
        similarity_summary=similarity_summary,
        manifest=manifest_doc,
        manifest_path=manifest_path,
    )


def _register_prover_conformance(driver: "VariantsPcaDriver") -> None:
    """The run-epilogue prover-conformance snapshot: for every static
    prover with a runtime-measured subject this run produced, register the
    measured/proven pair as the labeled conformance gauges
    (``obs/metrics.py:record_prover_conformance``) — the manifest's
    ``conformance`` block and the serve fleet's ``/metrics`` mirror both
    read these. Pairs: ``hostmem`` (peak RSS vs the ``host_peak_bytes``
    bound the driver proved at startup — measured always recorded, bound
    null on declared-unbounded paths), ``sched`` (the sharded
    accumulator's per-flush-accounted ring bytes vs its static
    projection), ``ranges`` (the ``--check-ranges`` entry-max sample vs
    the GR005-proven projection). Best-effort: telemetry must never take
    down a completed run."""
    from spark_examples_tpu.obs.metrics import (
        GRAMIAN_ENTRY_MAX,
        GRAMIAN_STATIC_ENTRY_BOUND,
        HOST_PEAK_RSS_BYTES,
        HOST_STATIC_BOUND_BYTES,
        record_prover_conformance,
    )

    registry = driver.registry
    try:
        measured_rss = registry.value(HOST_PEAK_RSS_BYTES)
        if measured_rss is not None and measured_rss == measured_rss:
            bound = registry.value(HOST_STATIC_BOUND_BYTES)
            record_prover_conformance(
                registry,
                "hostmem",
                measured_rss,
                bound if bound is not None and bound == bound else None,
            )
        sched = driver._sched_block
        if sched is not None:
            record_prover_conformance(
                registry,
                "sched",
                sched["measured_ring_bytes"],
                sched["predicted_ring_bytes"],
            )
        entry_max = registry.value(GRAMIAN_ENTRY_MAX)
        if entry_max is not None and entry_max == entry_max:
            entry_bound = registry.value(GRAMIAN_STATIC_ENTRY_BOUND)
            record_prover_conformance(
                registry,
                "ranges",
                entry_max,
                entry_bound
                if entry_bound is not None and entry_bound == entry_bound
                else None,
            )
    except Exception:
        pass


def _export_compile_cache_gauges(registry) -> None:
    """Expose the warm-geometry ledger's counters (``utils/cache.py``) as
    the well-known function-backed gauges, so the manifest and any
    heartbeat sampling this registry show warm-vs-cold directly. The
    ledger itself is fed at the END of ``run_pipeline`` — only a run that
    actually compiled and executed its kernels warms a fingerprint.
    Inside the resident daemon a repeated geometry is a hit (the
    in-process jit caches are warm); each batch CLI process starts cold
    by construction — both are honest."""
    from spark_examples_tpu.obs.metrics import (
        COMPILE_CACHE_GEOMETRY_HITS,
        COMPILE_CACHE_GEOMETRY_MISSES,
        well_known_gauge,
    )
    from spark_examples_tpu.utils.cache import compile_cache_stats

    well_known_gauge(registry, COMPILE_CACHE_GEOMETRY_HITS).set_function(
        lambda: float(compile_cache_stats()[0])
    )
    well_known_gauge(registry, COMPILE_CACHE_GEOMETRY_MISSES).set_function(
        lambda: float(compile_cache_stats()[1])
    )


def _summarize_similarity(similarity, n: int) -> Dict:
    """Host-side facts about a similarity matrix (the similarity request
    kind's result surface): the served response must not ship an N×N
    matrix, so the summary carries shape, dtype, the nonzero-row count the
    PCA path would have printed, and the trace (total variation count) as
    a cheap content fingerprint. Padded sharded results are trimmed to
    the true cohort before summarizing."""
    S = np.asarray(similarity)
    S = S[:n, :n]
    counts = S.astype(np.int64, copy=False)
    return {
        "shape": [int(s) for s in S.shape],
        "dtype": str(S.dtype),
        "nonzero_rows": int((counts.sum(axis=1) > 0).sum()),
        "trace": float(np.trace(counts)),
    }


def _sync_scalar(similarity) -> None:
    """Force outstanding device work to completion with a one-scalar fetch
    that depends on the full accumulation chain (a host array is a
    no-op)."""
    import jax
    import jax.numpy as jnp

    if isinstance(similarity, jax.Array):
        jax.device_get(jnp.any(similarity != 0))


def _similarity_stage(conf, driver, use_device: bool, use_packed: bool):
    """The ingest+similarity stage of :func:`run`, one of the three paths."""
    if use_device:
        contigs = conf.get_contigs(driver.source, conf.variant_set_id)
        return driver.get_similarity_device_gen(contigs)
    if use_packed:
        # Packed fast path: dense genotype blocks straight onto the device
        # — synthetic generation, or VCF arrays from the chunk-parallel
        # native parser (``sources/files.py``; pure-Python fallback,
        # identical output). With ingest workers enabled, the block stream
        # rides a bounded prefetch queue (parse runs ahead of the feeder)
        # and the dense accumulator double-buffers its device feed
        # (``pipeline_depth=2``): parse, H2D transfer, and Gramian dispatch
        # of consecutive blocks overlap instead of serializing.
        from spark_examples_tpu.pipeline.datasets import PrefetchIterator
        from spark_examples_tpu.sources.files import _resolve_ingest_workers

        # The ONE resolution of --ingest-workers (None→default, 0=serial),
        # shared with the parse pool inside FileGenomicsSource — the
        # prefetch/double-buffer decision must not drift from it.
        ingest_workers = _resolve_ingest_workers(conf.ingest_workers)
        pipeline_depth = 2 if ingest_workers > 0 else None

        def feed_rows(row_stream):
            """Run the row stream through the prefetch queue (when enabled)
            and the double-buffered accumulator; the structured overlap
            numbers land in the registry/manifest either way, and the
            historical one-line report still prints under --profile-dir."""
            prefetch = None
            if ingest_workers > 0:
                row_stream = prefetch = PrefetchIterator(
                    row_stream,
                    depth=2,
                    registry=driver.registry,
                    spans=driver.spans,
                )
            try:
                return driver.get_similarity_rows(
                    row_stream, pipeline_depth=pipeline_depth
                )
            finally:
                if prefetch is not None:
                    prefetch.close()
                    driver._overlap = prefetch.overlap_stats()
                    if conf.profile_dir:
                        print(prefetch.overlap_report())

        source = driver.source
        synthetic = isinstance(source, SyntheticGenomicsSource)
        contigs = driver._host_contigs(
            conf.get_contigs(source, conf.variant_set_id)
        )
        partitioner = VariantsPartitioner(contigs, conf.bases_per_partition)
        partitions = partitioner.get_partitions(conf.variant_set_id[0])
        from spark_examples_tpu.obs.metrics import (
            INGEST_PARTITIONS_DONE,
            INGEST_PARTITIONS_PLANNED,
            well_known_gauge,
        )

        well_known_gauge(driver.registry, INGEST_PARTITIONS_PLANNED).set(
            len(partitions)
        )

        if not synthetic and source.wants_streaming(conf.variant_set_id[0]):
            # Bounded-memory ingest: ONE pass over the file serves every
            # shard window in file order (G += XᵀX commutes), peak host
            # memory O(chunk) instead of O(file) — the capability the
            # reference's paging had by construction
            # (``rdd/VariantsRDD.scala:198-225``). Stats are accumulated
            # in-pass with the same per-shard page/variant accounting the
            # random-access path computes.
            from spark_examples_tpu.sources.files import StreamCounters

            counters = StreamCounters(len(partitions), registry=driver.registry)
            set_id = conf.variant_set_id[0]
            shard_windows = [p.contig for p in partitions]

            def streamed_rows():
                for block in source.stream_genotype_blocks(
                    set_id,
                    shard_windows,
                    block_size=conf.block_size,
                    min_allele_frequency=conf.min_allele_frequency,
                    counters=counters,
                ):
                    yield block["has_variation"]

            similarity = feed_rows(streamed_rows())
            # The pass is over: every window is done, including any past
            # the file's last record that the cursor never reached — the
            # heartbeat's progress gauge must converge to planned.
            well_known_gauge(driver.registry, INGEST_PARTITIONS_DONE).set(
                len(partitions)
            )
            # get_similarity_rows consumed the stream; the counters are
            # complete. Partition/request accounting matches the per-shard
            # path: every shard contributes its range and ≥1 page.
            if driver.io_stats is not None:
                for part in partitions:
                    driver.io_stats.add_partition(part.range)
                driver.io_stats.add_requests(counters.requests())
                driver.io_stats.add_variants(counters.variants)
            return similarity

        def block_stream():
            # Bounded iteration (the first nibble of ROADMAP item 1): blocks
            # flow one at a time from the per-window producer into the
            # prefetch queue — peak host memory O(block), not O(window).
            # This replaced the per-window `list(genotype_blocks)` pool
            # worker, which was the hostmem declared_unbounded inventory's
            # pca_driver entry; stats account per block as it streams, with
            # identical totals and identical block order (windows in
            # partition order, blocks in producer order — byte-identical
            # output, test-asserted).
            done_gauge = well_known_gauge(
                driver.registry, INGEST_PARTITIONS_DONE
            )
            for index, part in enumerate(partitions):
                if driver.io_stats is not None:
                    driver.io_stats.add_partition(part.range)
                    # Wire-equivalent page accounting (shared helper —
                    # the same rule analyses/base.py streams under).
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

        return feed_rows(block_stream())
    data = driver.get_data()
    calls = driver.iter_calls(data)
    return driver.get_similarity_matrix(calls)


__all__ = [
    "CallData",
    "PipelineResult",
    "VariantsPcaDriver",
    "extract_call_info",
    "make_source",
    "run",
    "run_pipeline",
]
