"""Device mesh construction and multi-host initialization.

This module replaces the one layer the reference borrowed wholesale: Spark's
distributed runtime (shuffle/broadcast/accumulators over TCP, SURVEY.md §2.3).
The TPU equivalent is a named-axis device mesh with XLA collectives over ICI
(intra-slice) and DCN (cross-host):

- ``data`` axis — the coordinate/variant dimension: genotype blocks from
  different contig windows land on different devices, per-device partial
  Gramians are summed once at finalize (the ``reduceByKey`` shuffle at
  ``VariantsPca.scala:230`` becomes a single ``psum``).
- ``samples`` axis — the cohort dimension: for cohorts too large for a
  replicated N×N similarity matrix (the reference's ~50K-samples/20GB
  guidance, ``VariantsPca.scala:216-217``), the Gramian is sharded by sample
  row-tiles across this axis.

The reference's ``--num-reduce-partitions`` ("set it to a number greater than
the number of cores", ``GenomicsConf.scala:35-38``) maps onto the data-axis
size, per the BASELINE.json north star.

Every Spark shuffle/broadcast/reduce in the reference's call stacks
(SURVEY.md §3) maps onto an XLA collective over this mesh, used directly by
the ops layer inside ``shard_map`` (named-axis primitives are already the
right API — no wrapper layer):

- ``reduceByKey`` partial-Gramian merge (``VariantsPca.scala:230``) →
  ``psum`` over ``data`` (``ops/gramian.py``: finalize reduction);
- ``sc.broadcast`` (``VariantsPca.scala:195,249``) → replication
  (jit constants / replicated shardings);
- ``collect`` to driver (``VariantsPca.scala:246``) → one ``device_get``
  after on-device reduction (``pipeline/pca_driver.py:compute_pca``);
- streaming pair-emission shuffle (``VariantsPca.scala:302-319``) →
  ``ppermute`` ring exchange of sample-column tiles
  (``ops/gramian.py:_ring_tiles``);
- row-sums collect + re-broadcast for centering (``VariantsPca.scala:
  246-249``) → ``psum`` of column sums (``ops/centering.py:
  gower_center_sharded``);
- driver-side eigendecomposition (``VariantsPca.scala:264-266``) →
  ``all_gather`` of the skinny subspace iterate
  (``ops/pca.py:principal_components_subspace_sharded``).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
SAMPLES_AXIS = "samples"
#: Outer axis of the hierarchical (two-level) reduction mesh: the samples
#: axis factored host-major into ``hosts x samples``, so the inner ring's
#: ``ppermute`` neighbors are intra-host (ICI) BY CONSTRUCTION and only the
#: outer ring crosses hosts (DCN). See :func:`hierarchical_mesh`.
HOST_AXIS = "hosts"

#: Test/rehearsal override for the hierarchical schedule's host factor
#: (``resolve_hier_hosts``): lets a single-process run with virtual CPU
#: devices exercise a REAL two-level schedule (e.g. 2 "hosts" x 2 devices
#: on 4 virtual devices — the ci.sh hier smoke).
HIER_HOSTS_ENV = "SPARK_EXAMPLES_TPU_HIER_HOSTS"

#: Genotypes per byte on the packed ring wire (np.packbits bit order). The
#: pack-width invariant follows from it: every device's local column width
#: must be a whole number of bytes, i.e. a multiple of this.
RING_PACK_MULTIPLE = 8


def padded_cohort(num_columns: int, samples_parallel: int, pack: bool = True) -> int:
    """Column count after cohort padding for the sharded ring Gramian.

    The cohort pads up to a multiple of the ``samples`` axis so every device
    owns an equal column tile; with the bit-packed ring wire format the tile
    additionally pads to a multiple of ``RING_PACK_MULTIPLE`` columns per
    device (a packed tile is a whole number of bytes, and a byte boundary
    must coincide with every shard boundary so each device's shard of the
    host-packed block is exactly its own columns). Pad columns are all-zero
    and contribute nothing to XᵀX; finalize trims them. ONE rule, shared by
    ``ops/gramian.py``, ``ops/devicegen.py`` and the device-free plan
    validator (``check/plan.py``) — the geometry the validator accepts is the
    geometry the accumulators build.
    """
    multiple = int(samples_parallel) * (RING_PACK_MULTIPLE if pack else 1)
    return -(-int(num_columns) // multiple) * multiple


def ring_permutes(samples_parallel: int, half: bool = False) -> int:
    """Tile ``ppermute``s per device in one ring pass: ``S - 1`` for the
    full ring, which brings every device every other device's column tile,
    and ``S // 2`` for the half ring (``ops/gramian.py:_half_ring_tiles``),
    which stops once symmetry gives the rest: G's block (i, j) is the
    transpose of block (j, i). The one count behind the half ring's step
    loop, ``ring_traffic_bytes`` and the IR audit's GI005/GI006."""
    s = int(samples_parallel)
    return s // 2 if half else s - 1


def half_ring_steps(samples_parallel: int) -> int:
    """Ring steps, so dots and step tiles per device, of the half ring:
    the device's own tile and one per permute."""
    return ring_permutes(samples_parallel, half=True) + 1


def ring_traffic_bytes(
    rows: int,
    samples_parallel: int,
    n_local: int,
    packed: bool,
    permutes: Optional[int] = None,
) -> int:
    """Total ICI bytes one ring pass moves for ``rows`` variant rows.

    Each of the ``samples_parallel`` devices sends its ``(rows, width)``
    column tile ``permutes`` times around the ring (:func:`ring_permutes`;
    ``samples_parallel - 1`` when not given, the full ring); ``width`` is
    ``n_local`` bytes unpacked or ``n_local / 8`` packed (``n_local % 8 == 0``
    under the pack-width invariant — :func:`padded_cohort`). ``rows`` summed
    over data-parallel slices gives the whole-mesh total (each slice runs its
    own ring). The one audited formula behind the ``gramian_ring_bytes``
    telemetry (``obs/metrics.py``) and the plan validator's traffic facts;
    ``graftcheck ir`` (``check/ir.py``) cross-validates it against the
    bytes the traced kernel jaxprs actually move (ppermute operand bytes x
    scan trip counts x devices) and fails CI on any divergence (GI005), so
    a wire-format or ring-schedule change can never silently decouple the
    reported traffic from the real traffic.
    """
    width = (
        int(n_local) // RING_PACK_MULTIPLE if packed else int(n_local)
    )
    if permutes is None:
        permutes = ring_permutes(samples_parallel)
    return int(rows) * int(samples_parallel) * int(permutes) * width


# --------------------------------------------------------------------------
# Topology & the hierarchical (two-level) reduction schedule.
# --------------------------------------------------------------------------

#: Default per-link bandwidths for the device-free schedule simulator
#: (``check/sched.py``). ICI: one v5e ring link sustains ~100 GB/s/chip
#: bidirectional (the packed ring moves one tile per step per link); DCN:
#: a v5e host NIC is ~25 GB/s aggregate and is SHARED by the host's chips.
#: Deliberately round, clearly-labeled planning numbers — the simulator's
#: job is comparing schedules and proving budgets, not cycle accuracy; a
#: ~2x bandwidth error never flips the flat-vs-hier ordering the GS rules
#: enforce (the byte SPLIT is exact, only seconds scale).
DEFAULT_ICI_BYTES_PER_S = 100 * 10**9
DEFAULT_DCN_BYTES_PER_S = 25 * 10**9


@dataclass(frozen=True)
class Topology:
    """A pod-shaped device fleet the schedule prover plans against:
    ``hosts`` machines x ``devices_per_host`` chips, intra-host links at
    ``ici_bytes_per_s`` per chip, one shared ``dcn_bytes_per_s`` NIC per
    host. Entirely declarative — a topology is proven against BEFORE the
    pod exists (``graftcheck sched --topology 32,8``), exactly like
    ``--plan-devices`` declares a device count the validator never
    queries."""

    hosts: int
    devices_per_host: int
    ici_bytes_per_s: int = DEFAULT_ICI_BYTES_PER_S
    dcn_bytes_per_s: int = DEFAULT_DCN_BYTES_PER_S

    def __post_init__(self) -> None:
        if self.hosts < 1 or self.devices_per_host < 1:
            raise ValueError(
                f"topology needs hosts >= 1 and devices_per_host >= 1, got "
                f"{self.hosts}x{self.devices_per_host}"
            )
        if self.ici_bytes_per_s <= 0 or self.dcn_bytes_per_s <= 0:
            raise ValueError("topology link bandwidths must be positive")

    @property
    def devices(self) -> int:
        return self.hosts * self.devices_per_host

    def describe(self) -> str:
        return f"{self.hosts}x{self.devices_per_host}"


def parse_topology(spec: str) -> Topology:
    """Parse the ``--topology`` flag: ``'hosts,devices_per_host'``
    (e.g. ``'32,8'`` for a v5e-256-class pod)."""
    parts = [p for p in spec.split(",") if p.strip()]
    if len(parts) != 2:
        raise ValueError(
            f"--topology expects 'hosts,devices_per_host', got {spec!r}"
        )
    try:
        hosts, per_host = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"--topology expects integer 'hosts,devices_per_host', got "
            f"{spec!r}"
        ) from None
    return Topology(hosts, per_host)


class LevelTraffic(NamedTuple):
    """Per-link-class bytes of one reduction schedule (whole mesh, one
    pass over ``rows``). ``ici_bytes`` ride intra-host links; ``dcn_bytes``
    ride the inter-host network. The split is the schedule's PROVABLE
    placement: bytes the schedule structure pins to a link class."""

    ici_bytes: int
    dcn_bytes: int

    @property
    def total(self) -> int:
        return self.ici_bytes + self.dcn_bytes


def hierarchical_traffic_bytes(
    rows: int,
    hosts: int,
    devices_per_host: int,
    n_local: int,
    packed: bool,
) -> LevelTraffic:
    """Per-level bytes of the two-level schedule — the sibling of
    :func:`ring_traffic_bytes`, split by link class.

    Per device and flush of ``rows`` rows: the inner packed ring sends the
    currently-held tile ``devices_per_host - 1`` times per outer step over
    ICI (``hosts`` outer steps, the seed included), and the outer ring
    sends it ``hosts - 1`` times over DCN — each host's columns cross DCN
    to every other host exactly ONCE, the information-theoretic floor for
    an all-to-all tile exchange. Total bytes equal the flat ring's
    (``S x (S-1)`` sends of the same tile, ``S = hosts x
    devices_per_host``): the hierarchical schedule moves the SAME bytes,
    it just proves where they ride. ``graftcheck sched`` (GS002)
    cross-validates both numbers against the bytes the traced kernel
    jaxprs actually move, per axis."""
    h, d = int(hosts), int(devices_per_host)
    width = int(n_local) // RING_PACK_MULTIPLE if packed else int(n_local)
    per_send = int(rows) * width
    devices = h * d
    return LevelTraffic(
        ici_bytes=per_send * devices * h * (d - 1),
        dcn_bytes=per_send * devices * (h - 1),
    )


def flat_traffic_split(
    rows: int,
    topology: Topology,
    n_local: int,
    packed: bool,
    permutes: Optional[int] = None,
) -> LevelTraffic:
    """The flat ring's provable per-level split on ``topology``.

    A flat ``ppermute`` over ONE mesh axis carries no host-boundary
    structure: which of its ``S - 1`` lockstep hops cross hosts is a
    property of the runtime device assignment, not of the schedule — so on
    a multi-host topology NO byte can be proven intra-host, and the sound
    bound attributes the whole circulation to the slow link. That
    unprovability is exactly what GS001 flags (and the hierarchical
    schedule fixes by construction: its inner axis is intra-host by the
    host-major mesh factorization). On one host everything is ICI."""
    total = ring_traffic_bytes(
        rows, topology.devices, n_local, packed, permutes
    )
    if topology.hosts == 1:
        return LevelTraffic(ici_bytes=total, dcn_bytes=0)
    return LevelTraffic(ici_bytes=0, dcn_bytes=total)


def resolve_reduce_schedule(spec: str, hosts: int) -> str:
    """``--reduce-schedule`` -> the schedule the run builds: ``flat`` (one
    ring over the whole samples axis), ``hier`` (packed intra-host ring
    over ICI + inter-host ring over DCN), or ``auto`` = ``hier`` iff the
    samples axis spans more than one host (single-host rings have no slow
    link to avoid — the flat ring IS the hierarchical schedule at
    hosts=1). ONE resolution rule, shared by the accumulator, the plan
    validator, and ``graftcheck sched``."""
    if spec not in ("auto", "flat", "hier"):
        raise ValueError(
            f"--reduce-schedule must be one of auto/flat/hier, got {spec!r}"
        )
    if spec == "auto":
        return "hier" if int(hosts) > 1 else "flat"
    return spec


def resolve_hier_hosts(
    samples_parallel: int, explicit: Optional[int] = None
) -> int:
    """The host factor of the hierarchical mesh factorization: explicit
    argument, else the :data:`HIER_HOSTS_ENV` rehearsal override, else the
    real process count. Must divide the samples axis (each host contributes
    an equal slice of the ring — the host-major factorization's invariant);
    a non-dividing factor fails loudly instead of silently skewing the
    schedule."""
    if explicit is None:
        env = os.environ.get(HIER_HOSTS_ENV)
        if env:
            explicit = int(env)
    hosts = int(explicit) if explicit is not None else jax.process_count()
    hosts = max(1, hosts)
    if int(samples_parallel) % hosts:
        raise ValueError(
            f"hierarchical schedule needs the host factor ({hosts}) to "
            f"divide the samples axis ({samples_parallel}); choose a mesh "
            "whose samples axis is a multiple of the host count"
        )
    return hosts


def hierarchical_mesh(mesh: Mesh, hosts: int) -> Mesh:
    """Factor a ``data x samples`` run mesh into the host-major
    ``data x hosts x samples`` hierarchical mesh (same devices, same
    order). The samples axis is the FAST axis of every run mesh
    (:func:`make_mesh` reshapes device-id order, which is process-major),
    so consecutive samples-axis slots are co-hosted and the reshape's
    outer factor groups whole hosts — the inner ring's neighbors stay
    intra-host by construction, which is the property the schedule prover
    certifies (``check/sched.py``)."""
    if SAMPLES_AXIS not in mesh.shape:
        raise ValueError(f"mesh must have a {SAMPLES_AXIS!r} axis")
    samples = mesh.shape[SAMPLES_AXIS]
    hosts = int(hosts)
    if samples % hosts:
        raise ValueError(
            f"host factor {hosts} does not divide samples axis {samples}"
        )
    data = mesh.shape.get(DATA_AXIS, 1)
    grid = np.asarray(mesh.devices).reshape(
        data, hosts, samples // hosts
    )
    return Mesh(grid, (DATA_AXIS, HOST_AXIS, SAMPLES_AXIS))


#: Fixed host-RSS overhead of the process itself — interpreter, jax/jaxlib
#: runtime, compiled executables, parser library — the constant term of
#: :func:`host_peak_bytes`. Deliberately generous: a CPU-backend process
#: idles around 0.3-0.6 GiB, and the TPU runtime maps a further ~2 GiB of
#: host memory at init (measured on the v5e-8 smoke). The formula's job
#: is to bound the DATA-DEPENDENT staging terms; an O(file) regression on
#: any real cohort dwarfs this constant long before the constant's slack
#: matters. Measured against reality on every build (ci.sh: manifest
#: ``host_memory.peak_rss_bytes`` <= the static bound).
HOST_RUNTIME_BASELINE_BYTES = 4 << 30


def host_peak_bytes(
    num_samples: int,
    block_size: int,
    data_axis: int = 1,
    ingest_workers: int = 0,
    chunk_bytes: int = 0,
    prefetch_depth: int = 0,
    pipeline_depth: int = 0,
    host_accumulator: bool = False,
    grm_finalize: bool = False,
    ld_window_sites: int = 0,
    num_hosts: int = 1,
    wire_table_bytes: int = 0,
    merge_join_bytes: int = 0,
    baseline_bytes: int = HOST_RUNTIME_BASELINE_BYTES,
) -> int:
    """Closed-form peak host-memory bound of one bounded-ingest run — the
    host-RAM sibling of :func:`ring_traffic_bytes`, and the ONE formula
    behind ``graftcheck plan --host-mem-budget``, the driver's
    ``host_static_bound_bytes`` gauge, and the manifest's ``host_memory``
    block (``check/hostmem.py:conf_host_peak_bytes`` resolves a parsed
    configuration into these arguments, so no caller re-derives them).

    Term by term (derivation in DESIGN.md §8.6):

    - **parse window** — ``(ingest_workers + 2) * 2 * chunk_bytes``: the
      order-preserving pool (``sources/files.py:_ordered_pool_map``) holds
      at most ``workers + 2`` chunks in flight, each present as raw text
      AND as its parsed arrays (has-variation bytes <= text bytes: one
      int8 per genotype vs >= 2 text chars per GT column, plus
      positions/ends/AF at ~20 bytes/row against ~60+ text bytes/row).
    - **prefetch queue** — ``prefetch_depth`` parsed blocks of
      ``block_size * num_samples`` uint8 waiting for the device feeder
      (``pipeline/datasets.py:PrefetchIterator``).
    - **accumulator staging** — the ``(data_axis * block_size,
      num_samples)`` uint8 staging buffer plus one flush copy (packed
      ``ceil(N/8)`` or the full-width counts copy — bound with the full
      width so count-valued joins stay inside the bound).
    - **flush in-flight** — ``pipeline_depth`` flush copies pinned on host
      while their transfers overlap compute (``ops/gramian.py``).
    - **host accumulator** — the ``--pca-backend host`` oracle's int64
      N x N matrix (+ its f64 centering copy), zero on the device path.
    - **GRM finalize** — ``21 * N * N``: the kinship close-out
      (``analyses/grm.py:grm_finalize`` + its summary) holds the fetched
      f32 Gramian (4 N²), EITHER the int64 working copy OR the summary's
      off-diagonal float64 extraction (8 N² — they never overlap), the
      float64 kinship itself (8 N²), and the off-diagonal bool mask
      (1 N²) simultaneously on host; zero for every other analysis.
    - **LD window** — ``56 * W² + W * N``: each flush fetches the W×W
      int32 co-carrier matrix and closes r² on host
      (``ops/ld.py:r2_from_counts`` holds up to seven 8-byte W×W working
      matrices — the int64 copy, cov, the variance outer product, the
      squared numerator and its cast temp, the r² result — next to the
      fetched int32 stats; 56 W² bounds the lot) plus the (W, N) uint8
      window buffer; zero when the run has no LD window.
    - **pod merge** — ``(num_hosts + 1) * 8 * N²`` when ``num_hosts > 1``:
      host-sharded ingest closes out by all-gathering every process's
      dense N×N partial Gramian onto each host and summing them exactly
      (``pipeline/pca_driver.py:_merge_host_partials``) — the gathered
      stack (``num_hosts`` partials) plus the 8-byte exact-sum working
      copy sit on host simultaneously. This is a PER-HOST bound: each
      process pays it locally, so the pod-wide peak is ``num_hosts``
      times this formula while each host stays within it. Zero for
      single-process runs.
    - **wire table** — ``wire_table_bytes``: the resolved residency of
      wire-mode ingest tables (spool index + decoded records + stream
      windows) or the packed columns' build/hand-off co-residency; the
      caller (``check/hostmem.py:conf_host_peak_bytes``) derives it from
      the bytes on disk via ``sources/stream.py:wire_rows_bound`` so the
      formula stays TOTAL across JSONL/SAM/REST/checkpoint-resume inputs.
    - **merge join** — ``merge_join_bytes``: the k-way streaming join's
      tracked-group working set, ``n_sets x 64 x record_bytes``
      (``sources/stream.py:merge_join`` holds at most the records of the
      current group key per stream; 64 is the accounted per-stream group
      ceiling its ``MergeJoinStats.peak_tracked`` gauge is asserted
      against). Zero for single-set runs.
    - **baseline** — :data:`HOST_RUNTIME_BASELINE_BYTES`.
    """
    n = int(num_samples)
    block_bytes = int(block_size) * n
    staging = int(data_axis) * block_bytes
    parse_window = (int(ingest_workers) + 2) * 2 * int(chunk_bytes)
    prefetch = int(prefetch_depth) * block_bytes
    flush_copies = (1 + int(pipeline_depth)) * staging
    host_matrix = 2 * n * n * 8 if host_accumulator else 0
    grm_term = 21 * n * n if grm_finalize else 0
    w = int(ld_window_sites)
    ld_term = 56 * w * w + w * n if w > 0 else 0
    hosts = int(num_hosts)
    merge_term = (hosts + 1) * 8 * n * n if hosts > 1 else 0
    return int(
        baseline_bytes
        + parse_window
        + prefetch
        + staging
        + flush_copies
        + host_matrix
        + grm_term
        + ld_term
        + merge_term
        + int(wire_table_bytes)
        + int(merge_join_bytes)
    )


def distributed_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    initialization_timeout: Optional[float] = None,
) -> None:
    """Initialize multi-host JAX (``jax.distributed``) when configured.

    A no-op for single-process runs. Cross-host arguments may come from flags
    or the standard cluster environment variables JAX already understands;
    this wrapper only exists so the driver has one seam for it (the analog of
    ``conf.newSparkContext``, ``GenomicsConf.scala:50-57``).
    """
    given = (coordinator_address, num_processes, process_id)
    if all(v is None for v in given):
        return
    # The CPU backend runs cross-process collectives only through an
    # explicit collectives implementation; without this the first
    # multi-process dispatch dies with "Multiprocess computations aren't
    # implemented on the CPU backend". TPU/GPU ignore the flag, and it must
    # land before the backend client exists — i.e. here, alongside the
    # rest of distributed init.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if coordinator_address is None or num_processes is None:
        # A partially-specified cluster launch must not silently fall back
        # to a single-process run over 1/N of the fleet.
        raise ValueError(
            "multi-host init needs --coordinator-address and --num-processes "
            f"(got coordinator_address={coordinator_address!r}, "
            f"num_processes={num_processes!r}, process_id={process_id!r})"
        )
    kwargs = {}
    if initialization_timeout is not None:
        kwargs["initialization_timeout"] = initialization_timeout
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )


@functools.lru_cache(maxsize=8)
def _replicator(mesh: Mesh):
    """Jitted identity that replicates onto every device of ``mesh`` —
    memoized per mesh so repeated ``host_value`` calls reuse one compiled
    program instead of retracing a fresh closure each time."""
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, PartitionSpec()))


def host_value(x) -> np.ndarray:
    """Host copy of a global array, valid in every process.

    Fully-addressable arrays (always the case single-process) and
    fully-replicated ones (every process holds a complete copy, even when
    other processes' replicas are non-addressable) fetch directly. An array
    sharded across non-addressable devices — the multi-controller case,
    where ``jax.device_get`` raises — is first replicated onto every device
    with a jitted identity (one ``all_gather`` over DCN), after which each
    process fetches its local replica. Verified by the 2-process run in
    ``parallel/multihost.py`` / ``tests/test_multihost.py``.
    """
    if getattr(x, "is_fully_addressable", True) or getattr(
        x, "is_fully_replicated", False
    ):
        return np.asarray(jax.device_get(x))
    from jax.sharding import NamedSharding

    sharding = x.sharding
    if not isinstance(sharding, NamedSharding):
        raise TypeError(
            "host_value needs a NamedSharding to replicate a "
            f"non-addressable array; got {type(sharding).__name__}"
        )
    return np.asarray(jax.device_get(_replicator(sharding.mesh)(x)))


@functools.lru_cache(maxsize=16)
def _packed_fetch_jit(mesh: Optional[Mesh]):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        return jax.jit(
            lambda *arrays: jnp.concatenate([a.reshape(-1) for a in arrays])
        )
    replicated = NamedSharding(mesh, PartitionSpec())

    def pack(*arrays):
        # Replicate EACH operand before the concatenate, not just the
        # output: lowering `concatenate(sharded...)` straight into a
        # replicated output makes the SPMD partitioner reshard via a
        # masked sum, and operands that are replicated along an unmentioned
        # mesh axis (e.g. P('data') counters on a data×samples mesh) get
        # every replica summed in — the fetched counters came back
        # multiplied by the samples-axis size. Per-operand replication
        # lowers to plain all-gathers, after which the concat is local.
        return jnp.concatenate(
            [
                jax.lax.with_sharding_constraint(a.reshape(-1), replicated)
                for a in arrays
            ]
        )

    return jax.jit(pack, out_shardings=replicated)


def packed_host_fetch(arrays, mesh: Optional[Mesh] = None) -> np.ndarray:
    """ONE host transfer for several device arrays: flatten + concatenate on
    device, fetch once, caller slices the flat result apart.

    Each synchronous fetch is a host↔device round trip, so end-of-run values (counters, components, scalars) should
    ride together — this helper is the one audited home for the pattern
    (replication for multi-controller fetches, x64 so int64 payloads are not
    canonicalized to int32 at the jit boundary). Pass ``mesh`` when any
    input may span non-addressable devices: the packed result is then
    replicated and every process reads its local copy. Arrays should share
    a dtype (mixed dtypes would silently promote).
    """
    with jax.enable_x64(True):
        return np.asarray(host_value(_packed_fetch_jit(mesh)(*arrays)))


def device_put_global(x, sharding):
    """``jax.device_put`` that stays valid when ``sharding`` spans
    non-addressable devices (multi-controller runs).

    This jax's ``device_put`` of a host array onto a non-addressable
    sharding first runs ``multihost_utils.assert_equal`` — a REAL collective
    that (a) costs a cross-process round trip per call and (b) is
    unimplemented on the CPU backend, so the multihost rehearsal
    (``parallel/multihost.py``) crashed before ever dispatching. The ingest
    paths are SPMD by construction — every process computes identical host
    operands — so the equality collective buys nothing:
    ``make_array_from_callback`` assembles the global array from each
    process's local copy directly. Fully-addressable shardings (and bare
    devices / None) keep the plain fast path."""
    if sharding is None or getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(x, sharding)
    x = np.asarray(x)
    return jax.make_array_from_callback(
        x.shape, sharding, lambda idx: x[idx]
    )


def local_shard(x) -> np.ndarray:
    """One addressable shard of a global array — a process-local synchronous
    fetch that works in single- and multi-controller modes alike (used for
    the early sync fetch, where only the sync matters, not the value)."""
    shards = x.addressable_shards
    return np.asarray(shards[0].data) if shards else np.asarray(x)


def make_mesh(
    shape: Dict[str, int],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named mesh, e.g. ``make_mesh({"data": 4, "samples": 2})``."""
    devices = list(devices if devices is not None else jax.devices())
    sizes = [max(1, int(n)) for n in shape.values()]
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {total} devices, have {len(devices)}"
        )
    grid = np.array(devices[:total]).reshape(sizes)
    return Mesh(grid, tuple(shape.keys()))


def default_mesh(
    num_reduce_partitions: Optional[int] = None,
    samples_axis: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """All available devices, data-major.

    ``num_reduce_partitions`` caps the data axis (the reference's reduce
    parallelism mapped onto the mesh); remaining devices are unused rather
    than silently changing semantics.
    """
    devices = list(devices if devices is not None else jax.devices())
    samples_axis = max(1, samples_axis)
    data = len(devices) // samples_axis
    if num_reduce_partitions is not None:
        data = max(1, min(data, num_reduce_partitions))
    return make_mesh({DATA_AXIS: data, SAMPLES_AXIS: samples_axis}, devices)


def parse_mesh_shape(spec: str) -> Dict[str, int]:
    """Parse the ``--mesh-shape`` flag: ``'data,samples'`` e.g. ``'4,2'``."""
    parts = [int(p) for p in spec.split(",")]
    if len(parts) == 1:
        parts.append(1)
    if len(parts) != 2:
        raise ValueError(f"--mesh-shape expects 'data,samples', got {spec!r}")
    return {DATA_AXIS: parts[0], SAMPLES_AXIS: parts[1]}


def resolve_run_mesh(
    mesh_shape: Optional[str] = None,
    num_reduce_partitions: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
):
    """The ONE run-mesh resolution rule (explicit ``--mesh-shape``, else
    all devices capped by ``--num-reduce-partitions``; ``None`` on one
    device) — shared by the PCA driver and the analyses so a change to
    the rule can never leave them resolving different meshes. ``devices``
    restricts the rule to a subset of the process's devices (an executor
    slice of the resident service — :func:`plan_executor_slices`); the
    default is every device, the historical behavior."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if mesh_shape:
        return make_mesh(parse_mesh_shape(mesh_shape), devices)
    if len(devices) == 1:
        return None
    return default_mesh(
        num_reduce_partitions=num_reduce_partitions, devices=devices
    )


# --------------------------------------------------------------------------
# Executor slices: partitioning one process's devices into independent
# sub-meshes (the resident service's concurrency unit).
# --------------------------------------------------------------------------

#: Job classes an executor slice may serve (the admission classes of
#: ``serve/queue.py``; spelled here so the device math has no serve import).
SLICE_SMALL = "small"
SLICE_LARGE = "large"


@dataclass(frozen=True)
class ExecutorSlice:
    """One independent executor: a contiguous range of the process's
    device list, its own mesh, its own worker thread, its own warm jit
    caches. Slices never share devices, so a whole-genome job on the
    large slice cannot head-block (or poison) a small-slice query — the
    isolation is by construction, not by scheduling discipline. Pure
    index arithmetic: the device-free plan validator reasons about slices
    without a backend, exactly like ``--plan-devices``."""

    name: str
    job_classes: Tuple[str, ...]
    device_start: int
    device_count: int

    def __post_init__(self) -> None:
        if self.device_count < 1:
            raise ValueError(
                f"slice {self.name!r} needs >= 1 device, got "
                f"{self.device_count}"
            )
        if not self.job_classes:
            raise ValueError(f"slice {self.name!r} serves no job class")

    def device_indices(self) -> Tuple[int, ...]:
        return tuple(
            range(self.device_start, self.device_start + self.device_count)
        )


def resolve_small_slices(spec, device_count: int) -> int:
    """The ``--executor-slices`` auto rule: ``'auto'`` (or ``None``) is one
    small slice whenever a device can be spared (>= 2 devices), zero on a
    single device (slicing one device buys nothing — the shared serial
    worker IS the right schedule there); an explicit integer passes
    through. ONE rule so the daemon and the load harness cannot drift."""
    if spec is None or spec == "auto":
        return 1 if int(device_count) >= 2 else 0
    count = int(spec)
    if count < 0:
        raise ValueError(f"--executor-slices must be >= 0, got {spec!r}")
    return count


def plan_executor_slices(
    device_count: int,
    small_slices: int = 0,
    small_slice_devices: int = 1,
) -> Tuple[ExecutorSlice, ...]:
    """Partition ``device_count`` devices into executor slices.

    ``small_slices == 0`` is the shared (historical) topology: ONE slice
    over every device serving both admission classes serially. Otherwise
    ``small_slices`` slices of ``small_slice_devices`` devices each are
    carved off the END of the device list for statically-bounded small
    jobs, and the remaining devices (at least one — a topology that
    starves the large class is an error, not a warning) form the large
    slice. Deterministic index math shared by the daemon (which maps
    indices onto ``jax.devices()``), admission (which validates each job
    against ITS slice's device count, not the whole pod's), and tests."""
    devices = int(device_count)
    small = int(small_slices)
    per_small = int(small_slice_devices)
    if devices < 1:
        raise ValueError(f"device_count must be >= 1, got {device_count}")
    if small < 0:
        raise ValueError(f"small_slices must be >= 0, got {small_slices}")
    if per_small < 1:
        raise ValueError(
            f"small_slice_devices must be >= 1, got {small_slice_devices}"
        )
    if small == 0:
        return (
            ExecutorSlice(
                name="shared",
                job_classes=(SLICE_SMALL, SLICE_LARGE),
                device_start=0,
                device_count=devices,
            ),
        )
    reserved = small * per_small
    if devices - reserved < 1:
        raise ValueError(
            f"{small} small slice(s) x {per_small} device(s) reserve "
            f"{reserved} of {devices} devices, leaving none for the large "
            "slice; shrink --executor-slices/--small-slice-devices or add "
            "devices"
        )
    slices = [
        ExecutorSlice(
            name="large",
            job_classes=(SLICE_LARGE,),
            device_start=0,
            device_count=devices - reserved,
        )
    ]
    for i in range(small):
        slices.append(
            ExecutorSlice(
                name=f"small-{i}",
                job_classes=(SLICE_SMALL,),
                device_start=devices - reserved + i * per_small,
                device_count=per_small,
            )
        )
    return tuple(slices)


__all__ = [
    "DATA_AXIS",
    "HOST_AXIS",
    "SAMPLES_AXIS",
    "HIER_HOSTS_ENV",
    "RING_PACK_MULTIPLE",
    "HOST_RUNTIME_BASELINE_BYTES",
    "DEFAULT_ICI_BYTES_PER_S",
    "DEFAULT_DCN_BYTES_PER_S",
    "LevelTraffic",
    "Topology",
    "parse_topology",
    "padded_cohort",
    "ring_permutes",
    "half_ring_steps",
    "ring_traffic_bytes",
    "hierarchical_traffic_bytes",
    "flat_traffic_split",
    "resolve_reduce_schedule",
    "resolve_hier_hosts",
    "hierarchical_mesh",
    "host_peak_bytes",
    "distributed_init",
    "host_value",
    "local_shard",
    "packed_host_fetch",
    "make_mesh",
    "default_mesh",
    "parse_mesh_shape",
    "resolve_run_mesh",
    "SLICE_SMALL",
    "SLICE_LARGE",
    "ExecutorSlice",
    "resolve_small_slices",
    "plan_executor_slices",
]
