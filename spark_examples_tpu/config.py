"""CLI configuration: the reference's flag grammar, preserved.

``GenomicsConf`` mirrors ``GenomicsConf.scala:29-64`` and ``PcaConf`` mirrors
``GenomicsConf.scala:66-98``. The flag surface is the API contract
(``BASELINE.md``): names, defaults, and the ``--references`` grammar
(``ref:start:end,...`` — one list per variant set) are identical. TPU-specific
additions are kept separate and optional:

- ``--source {synthetic,rest}``: which genomics backend to stream from (the
  reference always hit the live Google Genomics API, which no longer exists);
- ``--pca-backend {tpu,host}``: device pipeline vs. pure-NumPy reference
  implementation (the BASELINE.json north-star flag);
- ``--mesh-shape``: devices for the data×samples mesh; by analogy with the
  reference, ``--num-reduce-partitions`` bounds the data-axis size when
  ``--mesh-shape`` is not given (BASELINE.json maps the Spark reduce
  parallelism onto the device mesh).
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

from spark_examples_tpu.constants import GoogleGenomicsPublicData
from spark_examples_tpu.sharding.contig import (
    BRCA1,
    DEFAULT_BASES_PER_SHARD,
    Contig,
    SexChromosomeFilter,
    parse_contigs,
)


def _num_samples_value(text: str) -> str:
    """Validate ``--num-samples`` (an int, or a comma list of ints) at parse
    time so malformed input gets argparse's usage error, not a traceback."""
    values = [v for v in text.split(",") if v.strip()]
    if not values:
        raise argparse.ArgumentTypeError("needs at least one value")
    for v in values:
        try:
            int(v)
        except ValueError:
            raise argparse.ArgumentTypeError(f"invalid int value: {v!r}")
    return text


def _build_base_parser(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument(
        "--bases-per-partition",
        type=int,
        default=DEFAULT_BASES_PER_SHARD,
        help="Partition each reference using a fixed number of bases",
    )
    parser.add_argument("--client-secrets", default="client_secrets.json")
    parser.add_argument("--input-path", default=None)
    parser.add_argument(
        "--num-reduce-partitions",
        type=int,
        default=10,
        help=(
            "Set it to a number greater than the number of cores, to achieve "
            "maximum throughput. Maps onto the device-mesh data axis."
        ),
    )
    parser.add_argument("--output-path", default=None)
    parser.add_argument(
        "--references",
        default=BRCA1,
        help=(
            "Comma separated tuples of reference:start:end,... one list of "
            "tuples should be specified per variantset in the corresponding "
            "order (lists separated by ';')."
        ),
    )
    parser.add_argument(
        "--spark-master",
        default=None,
        help="Accepted for flag compatibility with the reference; unused.",
    )
    parser.add_argument(
        "--variant-set-id",
        default=GoogleGenomicsPublicData.THOUSAND_GENOMES_PHASE_1,
        help="Comma-separated list of VariantSetIds to use in the analysis.",
    )
    # TPU-native additions.
    parser.add_argument(
        "--source",
        choices=["synthetic", "rest", "file"],
        default="synthetic",
        help="Genomics backend to stream from.",
    )
    parser.add_argument(
        "--input-files",
        default=None,
        help=(
            "Comma-separated input files for --source file: .vcf[.gz] / "
            ".jsonl[.gz] variants (or a checkpoint directory), .sam reads. "
            "Each file becomes one variant set whose id is its sanitized "
            "stem; --variant-set-id defaults to all of them in order."
        ),
    )
    parser.add_argument(
        "--stream-chunk-bytes",
        type=int,
        default=None,
        help=(
            "Bounded-memory streaming ingest for --source file VCF inputs: "
            "parse in chunks of this many decompressed bytes instead of "
            "loading the file (one pass, coordinate-sorted VCFs only). "
            "Unset = automatic (streams when the file exceeds the size "
            "threshold); 0 = never stream; N > 0 = always stream with "
            "N-byte chunks."
        ),
    )
    parser.add_argument(
        "--ingest-workers",
        type=int,
        default=None,
        help=(
            "Parse threads for the chunk-parallel file ingest engine "
            "(--source file VCF inputs): the decompressed text is split "
            "into line-aligned chunks parsed concurrently through the "
            "GIL-releasing native parser, with an order-preserving merge. "
            "Default: min(8, cpu_count). 0 = the serial oracle path "
            "(byte-identical output, kept as the parity reference)."
        ),
    )
    parser.add_argument(
        "--num-samples",
        type=_num_samples_value,
        default="2504",
        help=(
            "Synthetic-source cohort size (1KG phase 1 has 2,504 samples). "
            "A comma-separated list gives per-variant-set cohort sizes, "
            "zipped positionally with --variant-set-id (e.g. '2504,17' for "
            "the 1KG × Platinum joint-cohort scenario); sets beyond the "
            "list use the first value."
        ),
    )
    parser.add_argument(
        "--seed", type=int, default=42, help="Synthetic-source base seed."
    )
    # Observability (obs/): background progress heartbeat + machine-readable
    # run manifest. Both default off, so stdout/stderr are byte-identical to
    # telemetry-free runs unless asked for.
    parser.add_argument(
        "--heartbeat-seconds",
        type=float,
        default=0.0,
        help=(
            "Emit a progress line to stderr every N seconds during the run "
            "(sites scanned + rate, partition progress with ETA, prefetch "
            "queue occupancy, dispatch pipeline depth, device memory when "
            "the backend reports it — obs/heartbeat.py). 0 = off (default)."
        ),
    )
    parser.add_argument(
        "--metrics-json",
        default=None,
        metavar="PATH",
        help=(
            "Write the schema-versioned end-of-run manifest here: config "
            "echo, hierarchical stage spans, every registry metric, I/O "
            "stats, and ingest-overlap accounting (obs/manifest.py). The "
            "numbers match the printed epilogue exactly; bench.py and CI "
            "consume this instead of scraping stdout."
        ),
    )
    parser.add_argument(
        "--trace-dir",
        default=None,
        metavar="DIR",
        help=(
            "Record crash-durable per-stage flight-recorder events under "
            "DIR/trace (obs/recorder.py): one append-only segment per "
            "process, so a multi-process run's stage timelines merge into "
            "ONE Chrome trace with `python -m spark_examples_tpu trace "
            "export --run-dir DIR` (obs/trace.py). Off by default."
        ),
    )
    # Robustness (pipeline/checkpoint.py): crash-consistent Gramian
    # checkpointing + resume. The Gramian is additive over variants, so a
    # preempted/killed analysis pass resumes at O(remaining) device cost
    # with byte-identical results (the graftcheck-ranges exactness
    # contracts make this exact, not approximate).
    parser.add_argument(
        "--gramian-checkpoint-dir",
        default=None,
        metavar="DIR",
        help=(
            "Periodically persist the device accumulator state (partial "
            "Gramian + dtype-ladder position + site cursor + conf "
            "fingerprint) as one atomically-published artifact under DIR, "
            "so a killed run can resume without restarting from zero "
            "(host-fed ingest paths: packed/wire; --pca-backend tpu)."
        ),
    )
    parser.add_argument(
        "--checkpoint-every-sites",
        type=int,
        default=None,
        metavar="N",
        help=(
            "Snapshot cadence for --gramian-checkpoint-dir: one atomic "
            "checkpoint per N accumulated sites (each costs one "
            "accumulator sync + one O(N^2) host fetch + write). Default: "
            "~18 snapshots across a whole genome "
            "(pipeline/checkpoint.py:DEFAULT_CHECKPOINT_EVERY_SITES)."
        ),
    )
    parser.add_argument(
        "--resume-from",
        default=None,
        metavar="DIR",
        help=(
            "Resume an interrupted analysis pass from the newest complete "
            "Gramian checkpoint in DIR: the artifact's conf fingerprint "
            "must match this run's flags (CheckpointMismatchError "
            "otherwise), the persisted partial merges into a fresh "
            "accumulator, and ingest fast-forwards to the saved cursor. "
            "No complete artifact yet = start from zero. Point it at the "
            "same directory as --gramian-checkpoint-dir to keep "
            "checkpointing while resumed."
        ),
    )
    # Robustness (utils/faults.py): a deterministic fault plan for chaos
    # testing. Normally injected via the SPARK_EXAMPLES_TPU_FAULTS env var
    # (subprocess harnesses); the flag form serves interactive repros.
    parser.add_argument(
        "--fault-plan",
        default=None,
        metavar="SPEC",
        help=(
            "Deterministic fault-injection plan (testing): comma-separated "
            "action@site[#nth][=arg] entries fired at registered "
            "kill-points and IO boundaries (utils/faults.py:KILL_POINTS/"
            "IO_POINTS). Equivalent to the SPARK_EXAMPLES_TPU_FAULTS "
            "environment variable; the flag wins when both are set."
        ),
    )
    # Multi-host initialization (jax.distributed) — the analog of pointing
    # the reference at a Spark cluster master (GenomicsConf.scala:50-57).
    # With these set, jax.devices() spans all hosts and the device mesh
    # (and therefore data-parallel ingest + the finalize psum) runs
    # multi-controller SPMD over ICI/DCN.
    parser.add_argument("--coordinator-address", default=None)
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    return parser


@dataclass
class GenomicsConf:
    """Parsed base flags (``GenomicsConf.scala:29-64``)."""

    bases_per_partition: int = DEFAULT_BASES_PER_SHARD
    client_secrets: str = "client_secrets.json"
    input_path: Optional[str] = None
    num_reduce_partitions: int = 10
    output_path: Optional[str] = None
    references: str = BRCA1
    spark_master: Optional[str] = None
    variant_set_id: List[str] = field(
        default_factory=lambda: [GoogleGenomicsPublicData.THOUSAND_GENOMES_PHASE_1]
    )
    source: str = "synthetic"
    input_files: Optional[List[str]] = None
    stream_chunk_bytes: Optional[int] = None
    ingest_workers: Optional[int] = None
    num_samples: int = 2504
    num_samples_per_set: Optional[List[int]] = None
    seed: int = 42
    heartbeat_seconds: float = 0.0
    metrics_json: Optional[str] = None
    trace_dir: Optional[str] = None
    gramian_checkpoint_dir: Optional[str] = None
    checkpoint_every_sites: Optional[int] = None
    resume_from: Optional[str] = None
    fault_plan: Optional[str] = None
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    @classmethod
    def parse(cls, argv: Sequence[str]) -> "GenomicsConf":
        parser = _build_base_parser(argparse.ArgumentParser())
        ns = parser.parse_args(list(argv))
        return cls._from_namespace(ns)

    def init_distributed(self) -> None:
        """Initialize multi-host JAX when the cluster flags are set (no-op
        otherwise) — call before any device use."""
        from spark_examples_tpu.parallel.mesh import distributed_init

        distributed_init(
            coordinator_address=self.coordinator_address,
            num_processes=self.num_processes,
            process_id=self.process_id,
        )

    @classmethod
    def _from_namespace(cls, ns: argparse.Namespace) -> "GenomicsConf":
        conf = cls()
        for f in conf.__dataclass_fields__:
            if hasattr(ns, f):
                setattr(conf, f, getattr(ns, f))
        if isinstance(conf.variant_set_id, str):
            conf.variant_set_id = [
                v for v in conf.variant_set_id.split(",") if v.strip()
            ]
        if isinstance(conf.input_files, str):
            conf.input_files = [
                p.strip() for p in conf.input_files.split(",") if p.strip()
            ]
        if isinstance(conf.num_samples, str):
            sizes = [
                int(s) for s in conf.num_samples.split(",") if s.strip()
            ]
            if not sizes:
                raise ValueError("--num-samples needs at least one value")
            conf.num_samples = sizes[0]
            conf.num_samples_per_set = sizes if len(sizes) > 1 else None
        if conf.heartbeat_seconds < 0:
            raise ValueError(
                f"--heartbeat-seconds must be >= 0 (0 = off), got "
                f"{conf.heartbeat_seconds}"
            )
        if conf.ingest_workers is not None and conf.ingest_workers < 0:
            raise ValueError(
                f"--ingest-workers must be >= 0 (0 = serial oracle path), "
                f"got {conf.ingest_workers}"
            )
        if (
            conf.checkpoint_every_sites is not None
            and conf.checkpoint_every_sites < 1
        ):
            raise ValueError(
                f"--checkpoint-every-sites must be >= 1, got "
                f"{conf.checkpoint_every_sites} (omit the flag for the "
                "default cadence)"
            )
        if conf.fault_plan is not None:
            # Fail at parse time with the grammar error, not mid-run: a
            # typo'd site name must not cost a whole ingest pass. Pure
            # stdlib (utils/faults.py imports no jax).
            from spark_examples_tpu.utils.faults import parse_plan

            parse_plan(conf.fault_plan)
        # --blocks-per-dispatch is PcaConf-only; validated here so every
        # parse path shares it. An explicit value must be positive: 0 is not
        # a documented auto spelling (leave the flag unset for auto), and
        # treating it as falsy-auto silently ignored the user's input.
        bpd = getattr(conf, "blocks_per_dispatch", None)
        if bpd is not None and bpd <= 0:
            raise ValueError(
                f"--blocks-per-dispatch must be a positive dispatch-group "
                f"length, got {bpd} (omit the flag for the auto rule)"
            )
        if conf.num_samples_per_set:
            if conf.source != "synthetic":
                # Cohort sizing only exists for the synthetic source; files
                # and APIs carry their own cohorts — silently ignoring the
                # flag would let users believe they sized the run.
                raise ValueError(
                    "per-set --num-samples is synthetic-source-only "
                    f"(--source {conf.source} reads its cohorts from the data)"
                )
            if len(set(conf.variant_set_id)) != len(conf.variant_set_id):
                # Per-set sizes are keyed by set id downstream; duplicate ids
                # would silently collapse to one size instead of the
                # positional sizes the flag documents.
                raise ValueError(
                    "per-set --num-samples requires distinct --variant-set-id "
                    "values (duplicate ids share one cohort)"
                )
        if conf.source == "file":
            if not conf.input_files:
                raise ValueError("--source file requires --input-files")
            from spark_examples_tpu.sources.files import file_set_ids

            ids = file_set_ids(conf.input_files)
            if conf.variant_set_id == [
                GoogleGenomicsPublicData.THOUSAND_GENOMES_PHASE_1
            ]:
                # The untouched default: every input file is one variant set.
                conf.variant_set_id = ids
            elif not set(conf.variant_set_id) <= set(ids):
                # An explicit id that matches no input must fail loudly, not
                # silently widen the run back to every file.
                raise ValueError(
                    f"--variant-set-id {conf.variant_set_id} not among the "
                    f"file-derived set ids {ids}"
                )
        return conf

    def get_references(self) -> List[List[Contig]]:
        """One contig list per variant set (``GenomicsConf.scala:59-63``).

        The reference passes one ``--references`` list per variant set in
        order; we use ';' to separate the per-variantset lists and ',' within
        a list, mirroring the documented grammar.
        """
        return [parse_contigs(spec) for spec in self.references.split(";")]


def build_pca_parser(
    parser: Optional[argparse.ArgumentParser] = None,
) -> argparse.ArgumentParser:
    """The full PCA flag surface on one parser — shared by
    :meth:`PcaConf.parse` and the device-free plan validator
    (``check/plan.py``), so ``graftcheck plan`` validates exactly the
    grammar the real run parses, never a drifted copy."""
    parser = _build_base_parser(parser or argparse.ArgumentParser())
    parser.add_argument(
        "--all-references",
        action="store_true",
        help=(
            "Use all references (except X and Y) to compute PCA "
            "(overrides --references)."
        ),
    )
    parser.add_argument("--debug-datasets", action="store_true")
    parser.add_argument("--min-allele-frequency", type=float, default=None)
    parser.add_argument("--num-pc", type=int, default=2)
    parser.add_argument(
        "--pca-backend",
        choices=["tpu", "host"],
        default="tpu",
        help="Similarity/PCA compute path: device pipeline or NumPy host path.",
    )
    parser.add_argument(
        "--mesh-shape",
        default=None,
        help="Device mesh as 'data,samples' (e.g. '4,2'). Default: all "
        "devices on the data axis, capped by --num-reduce-partitions.",
    )
    parser.add_argument(
        "--block-size",
        type=int,
        default=1024,
        help="Variants per device block in the Gramian accumulation.",
    )
    parser.add_argument(
        "--ingest",
        choices=["auto", "device", "packed", "wire"],
        default="auto",
        help=(
            "Genotype ingest path: 'device' generates the synthetic data "
            "plane on the TPU fused with the Gramian (fastest; synthetic "
            "source only), 'packed' builds dense blocks on host, 'wire' "
            "streams full JSON records through the dataset layer. 'auto' "
            "picks the fastest path valid for the configuration."
        ),
    )
    parser.add_argument(
        "--fused-jobs",
        type=int,
        default=None,
        metavar="K",
        help=(
            "Validate/audit the configuration as one lane of a K-job "
            "fused batch group (the serving daemon's stacked device "
            "program; ops/batched.py): `graftcheck plan` charges HBM "
            "for K stacked accumulators and rejects over-budget groups, "
            "and `graftcheck ir`/`ranges` audit the stacked kernel. "
            "Plan-time only — a batch run ignores it."
        ),
    )
    parser.add_argument(
        "--blocks-per-dispatch",
        type=int,
        default=None,
        help=(
            "Device-ingest blocks fused per dispatch (lax.scan length); "
            "higher amortizes per-dispatch host overhead. Default: auto — constant device work per "
            "dispatch, so small cohorts get longer scans "
            "(ops/devicegen.py:auto_blocks_per_dispatch)."
        ),
    )
    parser.add_argument(
        "--ring-pack-bits",
        choices=["auto", "on", "off"],
        default="auto",
        help=(
            "Sharded-ring wire format: circulate BIT-PACKED sample-column "
            "tiles over ICI (8 genotypes/byte — 8x less ring and "
            "host-to-device traffic) and unpack on device per ring step; "
            "the cohort pads to a multiple of 8x the samples axis (padded "
            "columns are all-zero and trimmed). 'off' keeps the unpacked "
            "uint8 wire as the bit-exact parity oracle; 'auto' (default) "
            "currently equals 'on'. Count-valued blocks (same-set joins) "
            "always ride the unpacked kernel regardless."
        ),
    )
    parser.add_argument(
        "--reduce-schedule",
        choices=["auto", "flat", "hier"],
        default="auto",
        help=(
            "Sharded-ring reduction schedule: 'flat' circulates tiles "
            "around ONE ring over the whole samples axis; 'hier' runs the "
            "two-level schedule (packed intra-host ring over ICI, "
            "inter-host ring over DCN — one DCN hop hides behind a whole "
            "inner ring) over the host-major factorization of the samples "
            "axis. 'auto' (default) = hier iff the samples axis spans "
            "more than one host. Same bytes, same results (byte-identical"
            ", CI-asserted); the split of bytes across link classes is "
            "what `graftcheck sched` proves per topology."
        ),
    )
    parser.add_argument(
        "--check-ranges",
        action="store_true",
        help=(
            "DEBUG: sample the max |accumulator entry| after every Gramian "
            "flush (one device fetch per flush — slow by design) into the "
            "gramian_entry_max gauge, next to the statically-projected "
            "gramian_static_entry_bound; the run manifest records the pair "
            "and CI asserts measured <= proven — the runtime half of the "
            "`graftcheck ranges` exactness contract. Host-fed accumulators "
            "only (packed/wire ingest); the fused device-generation path "
            "has no host flush to instrument."
        ),
    )
    parser.add_argument(
        "--exact-similarity",
        action="store_true",
        help=(
            "Force integer (int8xint8->int32) Gramian accumulation. By "
            "default the f32-accumulation MXU path is used unless the "
            "projected per-entry count approaches f32's 2^24 exact-integer "
            "limit, in which case the integer path is auto-selected."
        ),
    )
    parser.add_argument(
        "--similarity-strategy",
        choices=["auto", "dense", "sharded"],
        default="auto",
        help=(
            "Similarity accumulation strategy: 'dense' replicates the NxN "
            "Gramian per data-parallel device (VariantsPca.scala:210-231); "
            "'sharded' row-tile-shards it over the mesh samples axis (the "
            "memory-bounded analog of getSimilarityMatrixStream, "
            ":288-319). 'auto' picks by cohort size."
        ),
    )
    parser.add_argument(
        "--num-workers",
        type=int,
        default=8,
        help="Host threads for parallel shard streaming.",
    )
    parser.add_argument(
        "--profile-dir",
        default=None,
        help=(
            "Write a jax.profiler device trace (TensorBoard-loadable) "
            "here and print per-stage wall-clock timings — the Spark-UI "
            "stand-in (utils/tracing.py)."
        ),
    )
    parser.add_argument(
        "--save-variants",
        default=None,
        metavar="PATH",
        help=(
            "Materialize the ingested variants as a checkpoint directory "
            "at PATH while the analysis streams (one part file per "
            "shard), for later --input-path resume without re-ingesting. "
            "Wire ingest, single variant set (the writer the reference's "
            "objectFile resume never had, VariantsPca.scala:112-113)."
        ),
    )
    return parser


@dataclass
class PcaConf(GenomicsConf):
    """PCA flags (``GenomicsConf.scala:70-98``)."""

    all_references: bool = False
    debug_datasets: bool = False
    min_allele_frequency: Optional[float] = None
    num_pc: int = 2
    pca_backend: str = "tpu"
    mesh_shape: Optional[str] = None
    block_size: int = 1024
    ingest: str = "auto"
    fused_jobs: Optional[int] = None
    blocks_per_dispatch: Optional[int] = None
    ring_pack_bits: str = "auto"
    reduce_schedule: str = "auto"
    check_ranges: bool = False
    exact_similarity: bool = False
    similarity_strategy: str = "auto"
    num_workers: int = 8
    profile_dir: Optional[str] = None
    save_variants: Optional[str] = None

    EXCLUDE_XY = SexChromosomeFilter.EXCLUDE_XY

    @classmethod
    def parse(cls, argv: Sequence[str]) -> "PcaConf":
        ns = build_pca_parser().parse_args(list(argv))
        return cls._from_namespace(ns)

    def get_contigs(self, source, variant_set_ids: Sequence[str]) -> List[Contig]:
        """Contigs for all datasets (``GenomicsConf.scala:83-97``).

        ``--all-references`` asks the source for every contig in each variant
        set, excluding X/Y; otherwise the per-variantset ``--references``
        lists are parsed positionally.
        """
        print(f"Running PCA on {len(variant_set_ids)} datasets.")
        contigs: List[Contig] = []
        if self.all_references:
            for variant_set_id in variant_set_ids:
                print(f"Variantset: {variant_set_id}; All refs, exclude XY")
                contigs.extend(
                    source.get_contigs(variant_set_id, SexChromosomeFilter.EXCLUDE_XY)
                )
        else:
            # Scala zip semantics (``GenomicsConf.scala:91-95``): the
            # variantset list is zipped with the per-set reference lists and
            # TRUNCATED to the shorter — one --references list with two
            # variant sets contributes its contigs once, not per set.
            reference_lists = self.references.split(";")
            for variant_set_id, spec in zip(variant_set_ids, reference_lists):
                print(f"Variantset: {variant_set_id}; Refs: {spec}")
                contigs.extend(parse_contigs(spec))
        return contigs


# --------------------------------------------------------------------------
# Population-genetics analyses (analyses/): one conf per CLI verb, each a
# thin extension of the PCA flag surface — the analyses ride the same
# sources/mesh/block/telemetry flags, so everything the plan validator and
# the serve admission path already know keeps applying. The shared base
# parser means `graftcheck plan --analysis grm|ld|assoc` validates EXACTLY
# the grammar the real verbs parse, never a drifted copy.
# --------------------------------------------------------------------------


def build_grm_parser(
    parser: Optional[argparse.ArgumentParser] = None,
) -> argparse.ArgumentParser:
    """``grm`` verb flags: the PCA surface plus the kinship output path."""
    parser = build_pca_parser(parser)
    parser.add_argument(
        "--grm-out",
        default=None,
        metavar="PATH",
        help=(
            "Write the N×N VanRaden kinship matrix as a TSV (one row per "
            "sample: name, then N float64 values; atomic publish). Unset: "
            "only the summary is printed — the matrix never needs to "
            "leave the device path for summaries."
        ),
    )
    return parser


def build_ld_parser(
    parser: Optional[argparse.ArgumentParser] = None,
) -> argparse.ArgumentParser:
    """``ld-prune`` verb flags: windowed r² pruning over contig-ordered
    sites."""
    parser = build_pca_parser(parser)
    parser.add_argument(
        "--ld-r2-threshold",
        type=float,
        default=0.2,
        help=(
            "Prune a site whose r² with any previously-kept site in its "
            "window is STRICTLY greater than this (greedy, contig order; "
            "must be in [0, 1])."
        ),
    )
    parser.add_argument(
        "--ld-window-sites",
        type=int,
        default=256,
        help=(
            "Sites per pruning window (>= 2). Windows are contig-ordered "
            "and independent; the device computes one W×W co-carrier "
            "matrix per window, so host and HBM cost is O(W²), never O(M)."
        ),
    )
    parser.add_argument(
        "--ld-out",
        default=None,
        metavar="PATH",
        help=(
            "Write the per-site kept mask as a TSV (contig, pos, kept "
            "0/1), streamed window by window (bounded host memory, atomic "
            "publish). Unset: only the kept/tested counts are printed."
        ),
    )
    return parser


def build_assoc_parser(
    parser: Optional[argparse.ArgumentParser] = None,
) -> argparse.ArgumentParser:
    """``assoc-scan`` verb flags: per-site case/control chi-square."""
    parser = build_pca_parser(parser)
    parser.add_argument(
        "--phenotypes",
        default=None,
        metavar="TSV",
        help=(
            "REQUIRED: two-column TSV (sample name, status 0=control/"
            "1=case; '#' comment lines skipped) covering every cohort "
            "sample by its callset name."
        ),
    )
    parser.add_argument(
        "--assoc-out",
        default=None,
        metavar="PATH",
        help=(
            "Write the per-site scan as a TSV (contig, pos, case "
            "carriers, total carriers, chi2), streamed block by block "
            "(bounded host memory, atomic publish). Unset: only the "
            "top-ranked sites are printed."
        ),
    )
    parser.add_argument(
        "--assoc-top",
        type=int,
        default=10,
        help=(
            "How many top-chi² sites to print (and return) — a bounded "
            "heap, so the ranking never holds O(M) rows on host."
        ),
    )
    return parser


@dataclass
class GrmConf(PcaConf):
    """``grm`` flags: allele-frequency-standardized kinship (VanRaden)."""

    grm_out: Optional[str] = None

    @classmethod
    def parse(cls, argv: Sequence[str]) -> "GrmConf":
        ns = build_grm_parser().parse_args(list(argv))
        return cls._from_namespace(ns)


@dataclass
class LdConf(PcaConf):
    """``ld-prune`` flags: windowed LD r² pruning."""

    ld_r2_threshold: float = 0.2
    ld_window_sites: int = 256
    ld_out: Optional[str] = None

    @classmethod
    def parse(cls, argv: Sequence[str]) -> "LdConf":
        ns = build_ld_parser().parse_args(list(argv))
        return cls._from_namespace(ns)

    @classmethod
    def _from_namespace(cls, ns: argparse.Namespace) -> "LdConf":
        conf = super()._from_namespace(ns)
        # Parse-time rejects (the plan validator repeats these for
        # programmatic confs): a threshold outside [0,1] silently keeps or
        # prunes everything, a window below 2 has nothing to correlate.
        if not (0.0 <= conf.ld_r2_threshold <= 1.0):
            raise ValueError(
                f"--ld-r2-threshold must be in [0, 1], got "
                f"{conf.ld_r2_threshold}"
            )
        if conf.ld_window_sites < 2:
            raise ValueError(
                f"--ld-window-sites must be >= 2, got {conf.ld_window_sites}"
            )
        return conf


@dataclass
class AssocConf(PcaConf):
    """``assoc-scan`` flags: per-site case/control chi-square."""

    phenotypes: Optional[str] = None
    assoc_out: Optional[str] = None
    assoc_top: int = 10

    @classmethod
    def parse(cls, argv: Sequence[str]) -> "AssocConf":
        ns = build_assoc_parser().parse_args(list(argv))
        return cls._from_namespace(ns)

    @classmethod
    def _from_namespace(cls, ns: argparse.Namespace) -> "AssocConf":
        conf = super()._from_namespace(ns)
        if conf.assoc_top < 1:
            raise ValueError(
                f"--assoc-top must be >= 1, got {conf.assoc_top}"
            )
        return conf


__all__ = [
    "AssocConf",
    "GenomicsConf",
    "GrmConf",
    "LdConf",
    "PcaConf",
    "build_assoc_parser",
    "build_grm_parser",
    "build_ld_parser",
    "build_pca_parser",
]
