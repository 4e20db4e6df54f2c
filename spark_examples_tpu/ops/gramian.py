"""Similarity-matrix (Gramian) accumulation on the MXU.

The reference computes sample-similarity counts with a per-variant pair loop
into a per-partition Breeze matrix, merged by a ``reduceByKey`` shuffle
(``VariantsPca.scala:222-231``), or a pair-emission streaming variant
(``VariantsPca.scala:302-319``). Both are equivalent to

    G = Xᵀ X,   X ∈ {0,1}^(V×N),  X[v, s] = sample s has variation at v

so the TPU formulation is blockwise matmul: pack variants into fixed-shape
``(B, N)`` {0,1} blocks, compute ``G += XᵀX`` on the MXU with bfloat16
operands and float32 accumulation (0/1 operands and integer partial sums are
exact in bf16×bf16→f32 up to 2^24 per entry; an int8→int32 path is available
for absolute exactness), and reduce across devices once at the end — the
shuffle becomes a single ``psum`` over ICI.

Variable-length host batches are staged into the fixed block and the final
partial block is padded with zero rows, which contribute nothing to XᵀX —
static shapes for jit with no masking.

Two strategies, mirroring the reference's in-memory/streaming duality:

- :class:`GramianAccumulator` ("dense", ``VariantsPca.scala:210-231``): one
  replicated N×N accumulator per data-parallel device. Right whenever N×N
  fits HBM comfortably (N=2,504 → 25 MB f32).
- :class:`ShardedGramianAccumulator` ("sharded", the analog of
  ``VariantsPca.scala:288-319``'s memory-bounded strategy): the Gramian lives
  row-tile-sharded over the ``samples`` mesh axis and each update runs a
  ring exchange (``ppermute``) of sample-column blocks, so no device ever
  materializes the full N×N — the ~50K-samples/~20GB regime
  (``VariantsPca.scala:216-217``) at TPU HBM sizes.

The ring wire format is BIT-PACKED by default (``--ring-pack-bits``): tiles
circulate as ``(B, n_local/8)`` uint8 (8 genotypes/byte — ⅛ the ICI traffic
of unpacked uint8) and are unpacked on device per step, and the ring loop is
double-buffered — the ``ppermute`` for step k+1 is issued before the dot of
step k consumes its tile, so XLA overlaps the ICI transfer with the MXU
matmul instead of alternating them (the decomposed collective-matmul
pattern; see DESIGN.md §7.4). ``--ring-pack-bits off`` keeps the unpacked
wire as the bit-exact parity oracle. Host staging packs the same way before
``device_put``, so host→device transfer shrinks 8× too (the dense path's
``np.packbits`` trick, applied to the sharded staging buffer).

At pod scale the ring grows a second SCHEDULE (``--reduce-schedule``): the
hierarchical two-level ring (:func:`build_hierarchical_update`) factors
the samples axis host-major into ``hosts x devices`` and runs a packed
intra-host ring over ICI inside an inter-host ring over DCN, so one slow
DCN hop hides behind a whole inner ring of ICI + MXU work and each host's
columns cross DCN exactly once per pass — same bytes, same results
(byte-identical, CI-asserted), provably-placed links. The schedule-level
contracts (per-link traffic, overlap, liveness, critical path) are
machine-proven device-free by ``graftcheck sched`` (``check/sched.py``,
DESIGN.md §8.8) on declared topologies up to 32x8 — no pod required.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_examples_tpu.ops.contracts import (
    EXACT_F32_LIMIT,
    flush_entry_increment,
)
from spark_examples_tpu.parallel.mesh import (
    DATA_AXIS,
    HOST_AXIS,
    SAMPLES_AXIS,
    device_put_global,
    half_ring_steps,
    hierarchical_mesh,
    hierarchical_traffic_bytes,
    padded_cohort,
    resolve_hier_hosts,
    resolve_reduce_schedule,
    ring_traffic_bytes,
)


def resolve_ring_pack(pack_bits: str) -> bool:
    """``--ring-pack-bits`` → whether the ring circulates packed tiles.

    ``off`` is the unpacked bit-exact oracle; ``on`` packs; ``auto`` (the
    default) currently equals ``on`` — the pack/unpack is a cheap VPU
    shift-and-mask on every backend while the 8× traffic cut always helps,
    so there is nothing for auto to decide yet (the spelling reserves room
    for a future platform-conditional rule without a flag migration).
    """
    if pack_bits not in ("auto", "on", "off"):
        raise ValueError(
            f"--ring-pack-bits must be one of auto/on/off, got {pack_bits!r}"
        )
    return pack_bits != "off"


def _operand_dtypes(exact_int: bool, mesh: Optional[Mesh] = None):
    if exact_int:
        return np.int8, jnp.int32
    # bf16 operands feed the MXU on TPU (and tensor cores on GPU); the CPU
    # thunk runtime cannot execute bf16×bf16→f32 dots for some shapes
    # (UNIMPLEMENTED DotThunk), and on CPU f32 is the fast path anyway.
    # Exactness is identical: 0/1 operands, integer partial sums exact to
    # 2^24 per entry either way. Decide from the devices that will actually
    # run the dot, not the process default.
    platform = (
        mesh.devices.flat[0].platform if mesh is not None else jax.default_backend()
    )
    if platform == "cpu":
        return np.float32, jnp.float32
    return ml_dtypes.bfloat16, jnp.float32


# f32 accumulation is exact for integers up to 2^24 (EXACT_F32_LIMIT, now
# defined with the rest of the dtype-window registry in ops/contracts.py and
# re-exported here); past a projected per-entry count of this limit the
# accumulators losslessly convert to the int8->int32 MXU path (all entries
# are still exact integers at the moment of conversion). SURVEY §7 hard-part
# 3: whole-genome diagonal counts (~12M) approach this, and merged-cohort
# configs exceed it. The projection itself is contracts.flush_entry_increment
# — the same callable `graftcheck ranges` GR005 proves conservative against
# the per-dispatch increment read off the traced kernel jaxprs.

# Dense vs sharded similarity strategy, decided from memory — the TPU
# restatement of the reference's guidance, which states its bound in GB ("a
# matrix which may be up to 20GB for ~50K samples",
# ``VariantsPca.scala:216-217,296-297``). The dense strategy holds about
# _DENSE_BUFFERS simultaneous N×N accumulator-dtype buffers per device at
# peak (G, its non-donated update, the centered copy, and eigensolve
# temporaries); it fits when that stays under DENSE_HBM_FRACTION of
# per-device memory. One rule, used by BOTH the driver's strategy resolution
# and the ingest-path eligibility check — no duplicated magic constants.
DENSE_HBM_FRACTION = 0.8
_DENSE_BUFFERS = 4
#: One v5e chip's HBM: the budget of offline plans (``check/``), which see
#: no device, and of CPU test devices, which report no memory limit.
_DEFAULT_DEVICE_BYTES = 16 << 30


def per_device_memory_bytes() -> int:
    """This process's per-device memory budget from ``memory_stats()``.

    CPU devices (the virtual test mesh) report no limit and are budgeted as
    one v5e chip. Any other device that reports none raises: sizing the
    dense Gramian against a guessed capacity would fail later, on the
    device, with a less useful error."""
    device = jax.local_devices()[0]
    stats = device.memory_stats()
    limit = int(stats.get("bytes_limit", 0)) if stats else 0
    if limit > 0:
        return limit
    if device.platform == "cpu":
        return _DEFAULT_DEVICE_BYTES
    raise RuntimeError(
        f"{device.device_kind} ({device.platform}) reports no memory_stats "
        "bytes_limit; cannot size the dense Gramian"
    )


def dense_strategy_fits(n_columns: int, accum_bytes: int = 4) -> bool:
    """Whether a replicated ``n_columns``² accumulator (plus working copies)
    fits per-device memory — the dense/sharded auto-switch predicate."""
    need = _DENSE_BUFFERS * int(n_columns) ** 2 * accum_bytes
    return need <= DENSE_HBM_FRACTION * per_device_memory_bytes()


def _maybe_switch_accumulator(acc, next_bound: int, out_shardings=None) -> bool:
    """Losslessly convert an f32 accumulator to int32 before any entry could
    cross the 2^24 exact-integer limit (entries are bounded by
    Σ rows × max-count², all still exact integers at conversion time).
    Returns True when a switch happened (callers may need to rebuild a
    dtype-closed update function)."""
    if acc.exact_int or acc.accum_dtype == jnp.int32:
        return False
    if next_bound <= EXACT_F32_LIMIT:
        return False
    # range: every entry is an exact integer <= EXACT_F32_LIMIT (2^24) at
    # conversion time — far inside int32's 2^31 window, so the cast is
    # lossless by the GR005-proven trigger (check/ranges.py).
    acc.G = jax.jit(
        lambda g: g.astype(jnp.int32), out_shardings=out_shardings
    )(acc.G)
    acc.operand_dtype, acc.accum_dtype = np.int8, jnp.int32
    return True


@functools.partial(jax.jit, static_argnames=("operand_dtype",))
def _dense_update_counts(G, X, operand_dtype):  # graftcheck: disable=GC005 -- G is not donated, same policy as _dense_update: the accumulator holds earlier G references (pipeline_depth), which donation would invalidate
    """G[d] += X[d]ᵀ X[d] for unpacked count-valued uint8 rows (the rare
    same-set-join case where a callset column appears more than once per
    variant — the reference's pair loop adds k² for k duplicates, which is
    exactly the outer product of count vectors)."""
    Xc = X.astype(operand_dtype)
    return G + jnp.einsum(
        "dbn,dbm->dnm", Xc, Xc, preferred_element_type=G.dtype
    )


@functools.partial(jax.jit, static_argnames=("operand_dtype", "num_samples"))
def _dense_update(G, X_packed, operand_dtype, num_samples):  # graftcheck: disable=GC005 -- deliberate: the accumulator holds earlier G references (pipeline_depth), which donation would invalidate (see docstring below)
    """G[d] += X[d]ᵀ X[d] — local per data-slice, no communication.

    X arrives BIT-PACKED (8 genotypes/byte over PCIe/DCN — ⅛ the traffic of
    uint8, 1/16 of bf16) and is unpacked + cast to the MXU operand dtype on
    device; the unpack is a cheap VPU shift-and-mask fused ahead of the
    matmul. Deliberately NOT donating G: :class:`GramianAccumulator` keeps
    the G of earlier flushes alive to bound the dispatch queue
    (``pipeline_depth``), which donation would invalidate. The price is one
    N×N buffer per update in flight.
    """
    # Materialize the unpacked operand once: fused into the dot, the
    # unpack+cast recomputes per output tile (same effect as the generation
    # chain in ops/devicegen.py, scaled to the unpack's ~2 ops — measured
    # ~5% on v5e).
    Xc = jax.lax.optimization_barrier(
        _unpack_bits(X_packed, num_samples).astype(operand_dtype)
    )
    return G + jnp.einsum(
        "dbn,dbm->dnm", Xc, Xc, preferred_element_type=G.dtype
    )


def data_axis_sum(G: jax.Array, out_shardings=None) -> jax.Array:
    """Cross-data-slice reduce of a ``(D, ...)`` stacked accumulator.

    With more than one slice, integer accumulators are promoted to int64 in
    the reduce: each slice's int32 accumulator is bounded by its own
    accumulated sites, but the TOTAL across D slices is not — it can pass
    2^31 while every slice stays under it. Traced under x64 so the requested
    dtype is honored regardless of the caller's config (outside x64 JAX
    silently canonicalizes int64 back to int32). Single-slice reduces keep
    the accumulator dtype — no promotion is needed where no cross-slice sum
    happens. Shared by every accumulator's finalize (here and
    ``ops/devicegen.py``) so the overflow policy lives in one place.
    """
    out_dtype = (
        jnp.int64
        if G.shape[0] > 1 and jnp.issubdtype(G.dtype, jnp.integer)
        else G.dtype
    )
    with jax.enable_x64(True):
        if out_shardings is not None:
            return jax.jit(
                lambda g: jnp.sum(g, axis=0, dtype=out_dtype),
                out_shardings=out_shardings,
            )(G)
        return jnp.sum(G, axis=0, dtype=out_dtype)


class _AccumulatorTelemetry:
    """Optional flush instrumentation shared by both accumulators.

    When a run registry is attached (the driver always attaches its own),
    every flush feeds ``gramian_flushes_total`` / ``gramian_rows_total``
    counters and the ``gramian_flush_seconds`` histogram (all labeled by
    strategy), and ``gramian_inflight_dispatches`` tracks the pipelined
    feed depth for the heartbeat. The sharded strategy additionally feeds
    the ``gramian_ring_bytes`` counter (total ICI bytes its ring exchanges
    moved — ``parallel/mesh.py:ring_traffic_bytes``, the number the packed
    wire format cuts 8×) and the per-flush ``gramian_ring_flush_seconds``
    histogram, both surfaced in the run manifest and the heartbeat. At
    finalize the accumulated host-side flush time attaches to the open span
    tree as a ``dispatch`` aggregate (one span, not one per flush — a
    whole-genome run has thousands) and the finalize reduce itself runs
    under a ``reduce-flush`` span.
    """

    def __init__(self, registry, spans, strategy: str):
        self.spans = spans
        self.flush_seconds_total = 0.0
        self._flushes = self._rows = self._seconds = self._inflight = None
        self._ring_bytes = self._ring_seconds = None
        self._entry_max = self._entry_bound_gauge = None
        self.entry_max_seen = 0.0
        self._registry = registry
        if registry is not None and strategy == "sharded":
            from spark_examples_tpu.obs.metrics import (
                GRAMIAN_RING_BYTES,
                GRAMIAN_RING_FLUSH_SECONDS,
                well_known_counter,
            )

            self._ring_bytes = well_known_counter(registry, GRAMIAN_RING_BYTES)
            self._ring_seconds = registry.histogram(
                GRAMIAN_RING_FLUSH_SECONDS,
                "Host-side seconds per ring-exchange flush "
                "(pack + device_put + ring dispatch).",
            )
        if registry is not None:
            labels = {"strategy": strategy}
            self._flushes = registry.counter(
                "gramian_flushes_total",
                "Device flushes (one dispatched G += XᵀX update each).",
                labelnames=("strategy",),
            ).labels(**labels)
            self._rows = registry.counter(
                "gramian_rows_total",
                "Variant rows accumulated into the Gramian.",
                labelnames=("strategy",),
            ).labels(**labels)
            self._seconds = registry.histogram(
                "gramian_flush_seconds",
                "Host-side time per flush (pack + device_put + dispatch).",
                labelnames=("strategy",),
            ).labels(**labels)
            from spark_examples_tpu.obs.metrics import (
                GRAMIAN_INFLIGHT_DISPATCHES,
                well_known_gauge,
            )

            self._inflight = well_known_gauge(
                registry, GRAMIAN_INFLIGHT_DISPATCHES
            )

    def record_flush(self, rows: int, seconds: float, in_flight: int) -> None:
        self.flush_seconds_total += seconds
        if self._flushes is not None:
            self._flushes.inc(1)
            self._rows.inc(rows)
            self._seconds.observe(seconds)
            self._inflight.set(in_flight)

    def record_ring(self, nbytes: int, seconds: float) -> None:
        if self._ring_bytes is not None:
            self._ring_bytes.inc(nbytes)
            self._ring_seconds.observe(seconds)

    def record_entry_sample(self, G, entry_bound: int) -> None:
        """``--check-ranges`` debug sampling: the measured max |entry| of
        the live accumulator next to the statically-projected bound
        (``contracts.flush_entry_increment`` accumulated over flushes) —
        the runtime half of the ``graftcheck ranges`` exactness contract,
        mirroring the hostmem measured-RSS/static-bound pair. The sampled
        pair lands in the ``gramian_entry_max`` / ``gramian_static_entry_bound``
        gauges and, from there, in the run manifest; the obs smoke asserts
        measured <= proven on every build."""
        sample = float(np.asarray(jax.device_get(jnp.max(jnp.abs(G)))))  # graftcheck: disable=GC001 -- deliberate per-flush device fetch: --check-ranges is an opt-in DEBUG mode whose whole point is sampling the live accumulator (off by default, documented in the flag help)
        self.entry_max_seen = max(self.entry_max_seen, sample)
        if self._registry is not None:
            if self._entry_max is None:
                from spark_examples_tpu.obs.metrics import (
                    GRAMIAN_ENTRY_MAX,
                    GRAMIAN_STATIC_ENTRY_BOUND,
                    well_known_gauge,
                )

                self._entry_max = well_known_gauge(
                    self._registry, GRAMIAN_ENTRY_MAX
                )
                self._entry_bound_gauge = well_known_gauge(
                    self._registry, GRAMIAN_STATIC_ENTRY_BOUND
                )
            self._entry_max.set(self.entry_max_seen)
            self._entry_bound_gauge.set(float(entry_bound))

    def finalize_span(self):
        """Context for the finalize reduce; also attaches the flush-time
        aggregate so the span tree reads ingest → dispatch → reduce-flush."""
        import contextlib

        if self.spans is None:
            return contextlib.nullcontext()
        self.spans.add("dispatch", self.flush_seconds_total)
        return self.spans.span("reduce-flush")


def _unpack_bits(packed: jax.Array, num_columns: int) -> jax.Array:
    """(..., ceil(N/8)) uint8 → (..., N) {0,1} uint8 (np.packbits big-endian
    bit order)."""
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (packed[..., None] >> shifts) & jnp.uint8(1)
    return bits.reshape(*packed.shape[:-1], packed.shape[-1] * 8)[
        ..., :num_columns
    ]


def _pack_bits_device(bits: jax.Array) -> jax.Array:
    """(..., N) {0,1} uint8 → (..., N/8) uint8, ``N % 8 == 0`` — the exact
    on-device inverse of :func:`_unpack_bits` (np.packbits big-endian bit
    order, verified against NumPy in tests). A cheap VPU shift-and-sum; the
    device-generation ring packs its generated columns with this before the
    first ``ppermute`` so the wire format matches the host-packed path."""
    *lead, n = bits.shape
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    # range: inputs are {0,1} membership bits (ops/contracts.py:HAS_VARIATION)
    # — uint8 holds them exactly, and the shifted disjoint-bit terms below
    # sum to at most 255.
    grouped = bits.reshape(*lead, n // 8, 8).astype(jnp.uint8) << shifts
    # Exact in uint8: 8 disjoint-bit terms sum to at most 255.
    return jnp.sum(grouped, axis=-1, dtype=jnp.uint8)


class GramianAccumulator:
    """Dense strategy: replicated N×N per data-parallel device.

    Feed host ``(b, N)`` uint8 has-variation rows with :meth:`add_rows`;
    :meth:`finalize` pads, flushes, and cross-device-reduces to a single
    float32 (or int32) N×N similarity matrix on host.
    """

    def __init__(
        self,
        num_samples: int,
        mesh: Optional[Mesh] = None,
        block_size: int = 1024,
        exact_int: bool = False,
        sync_every: int = 1,
        pipeline_depth: Optional[int] = None,
        registry=None,
        spans=None,
        check_ranges: bool = False,
    ):
        self.telemetry = _AccumulatorTelemetry(registry, spans, "dense")
        self.check_ranges = bool(check_ranges)
        self.num_samples = int(num_samples)
        self.mesh = mesh
        self.block_size = int(block_size)
        self.exact_int = bool(exact_int)
        self.operand_dtype, self.accum_dtype = _operand_dtypes(exact_int, mesh)
        self._entry_bound = 0  # conservative max over per-entry counts
        self.data_parallel = mesh.shape[DATA_AXIS] if mesh is not None else 1
        # Bound the async dispatch queue: every update in flight holds its
        # own N×N output (G is not donated), so an unbounded queue grows
        # device memory with its depth. Two policies:
        # - sync_every (legacy): block on the CURRENT G every few flushes —
        #   zero host/device overlap at the default of 1;
        # - pipeline_depth d: block on the G from d flushes AGO, so up to d
        #   updates stay in flight and flush k+1's pack + device_put overlap
        #   flush k's matmul — the double-buffered device feed of the
        #   chunk-parallel ingest engine (d=2 is classic double buffering).
        #   Updates do NOT donate G (see _dense_update), so holding the
        #   older references is safe.
        self.sync_every = max(1, int(sync_every))
        self.pipeline_depth = (
            None if pipeline_depth is None else max(1, int(pipeline_depth))
        )
        self._in_flight: list = []
        self._flushes = 0

        rows = self.data_parallel * self.block_size
        self._staging = np.zeros((rows, self.num_samples), dtype=np.uint8)
        self._fill = 0
        self.rows_seen = 0

        g_shape = (self.data_parallel, self.num_samples, self.num_samples)
        if mesh is not None:
            self._g_sharding = NamedSharding(mesh, P(DATA_AXIS, None, None))
            self._x_sharding = NamedSharding(mesh, P(DATA_AXIS, None, None))
            self.G = device_put_global(
                np.zeros(g_shape, dtype=np.dtype(self.accum_dtype)), self._g_sharding
            )
        else:
            self._g_sharding = None
            self._x_sharding = None
            self.G = jnp.zeros(g_shape, self.accum_dtype)

    def add_rows(self, rows: np.ndarray) -> None:
        """Stage host rows; flush full blocks to the device."""
        rows = np.asarray(rows, dtype=np.uint8)
        if rows.ndim != 2 or rows.shape[1] != self.num_samples:
            raise ValueError(
                f"expected (b, {self.num_samples}) rows, got {rows.shape}"
            )
        self.rows_seen += rows.shape[0]
        offset = 0
        capacity = self._staging.shape[0]
        while offset < rows.shape[0]:
            take = min(capacity - self._fill, rows.shape[0] - offset)
            self._staging[self._fill : self._fill + take] = rows[offset : offset + take]
            self._fill += take
            offset += take
            if self._fill == capacity:
                self._flush()

    def _flush(self) -> None:
        if self._fill == 0:
            return
        flush_rows, flush_start = self._fill, time.perf_counter()
        block = self._staging
        if self._fill < block.shape[0]:
            # Zero rows contribute nothing to XᵀX — pad instead of masking.
            block = block.copy()
            block[self._fill :] = 0
        max_count = int(block.max(initial=0))
        # The ONE projection formula (ops/contracts.py) — GR005 proves it
        # conservative w.r.t. the jaxpr-derived per-dispatch increment.
        increment = flush_entry_increment(self._fill, max_count)
        _maybe_switch_accumulator(
            self, self._entry_bound + increment, out_shardings=self._g_sharding
        )
        self._entry_bound += increment
        shaped = block.reshape(
            self.data_parallel, self.block_size, self.num_samples
        )
        if max_count > 1:
            # Count-valued rows (same-set joins) can't be bit-packed; ship
            # them unpacked through the counts kernel. Under pipeline_depth
            # the flush returns with the dispatch still in flight, and a
            # full-block `shaped` is a VIEW of the reused _staging buffer —
            # which jnp.asarray/device_put may alias zero-copy on the CPU
            # backend — so the next add_rows would overwrite an in-flight
            # operand; copy before shipping. (The bit-packed branch is safe:
            # np.packbits allocates fresh. The legacy sync-per-flush path is
            # safe: nothing is in flight when add_rows resumes.)
            if self.pipeline_depth is not None and block is self._staging:
                shaped = shaped.copy()
            Xd = (
                device_put_global(shaped, self._x_sharding)
                if self._x_sharding is not None
                else jnp.asarray(shaped)
            )
            self.G = _dense_update_counts(self.G, Xd, self.operand_dtype)
        else:
            X = np.packbits(shaped, axis=-1)
            Xd = (
                device_put_global(X, self._x_sharding)
                if self._x_sharding is not None
                else jnp.asarray(X)
            )
            self.G = _dense_update(
                self.G, Xd, self.operand_dtype, self.num_samples
            )
        self._fill = 0
        self._flushes += 1
        if self.pipeline_depth is not None:
            # Double-buffered feed: wait only for the update issued
            # `pipeline_depth` flushes ago, leaving the most recent
            # transfers/dispatches in flight behind this block's compute.
            self._in_flight.append(self.G)
            if len(self._in_flight) > self.pipeline_depth:
                jax.block_until_ready(self._in_flight.pop(0))
        elif self._flushes % self.sync_every == 0:
            jax.block_until_ready(self.G)
        if self.check_ranges:
            self.telemetry.record_entry_sample(self.G, self._entry_bound)
        self.telemetry.record_flush(
            flush_rows, time.perf_counter() - flush_start, len(self._in_flight)
        )

    def snapshot_state(self) -> dict:
        """Crash-consistent checkpoint state: flush staged rows, drain the
        dispatch pipeline, and fetch the partial Gramian with its
        dtype-ladder position — everything :meth:`restore_state` needs to
        rebuild this accumulator mid-stream on a fresh process. The fetch
        is deliberate and periodic (``--gramian-checkpoint-dir``), not a
        hot-path sync."""
        self._flush()
        jax.block_until_ready(self.G)  # graftcheck: disable=GC001 -- deliberate checkpoint barrier: the snapshot must capture a quiesced accumulator (no in-flight updates), at --checkpoint-every-sites cadence, not per flush
        self._in_flight.clear()
        G_host = np.asarray(jax.device_get(self.G))  # graftcheck: disable=GC001 -- deliberate periodic checkpoint fetch of the partial Gramian (the artifact payload); cadence is --checkpoint-every-sites, not the dispatch loop
        return {
            "strategy": "dense",
            "G": G_host,
            "accum_dtype": np.dtype(self.accum_dtype).name,
            "exact_int": self.exact_int,
            "entry_bound": self._entry_bound,
            "rows_seen": self.rows_seen,
            "flushes": self._flushes,
            "num_samples": self.num_samples,
            "data_parallel": self.data_parallel,
            "padded": self.num_samples,
        }

    def restore_state(self, checkpoint: dict) -> None:
        """Merge a persisted partial into this (fresh, empty) accumulator:
        adopt the saved dtype-ladder position, load the saved G, and
        restore the cursor bookkeeping. Geometry mismatches fail loudly —
        the conf fingerprint should have caught them already; this is the
        defense-in-depth shape check."""
        meta, G = checkpoint["meta"], checkpoint["G"]
        if meta["strategy"] != "dense":
            raise ValueError(
                f"checkpoint was written by the {meta['strategy']!r} "
                "strategy; this run resolved dense — the similarity "
                "strategy is part of the checkpoint geometry"
            )
        expect = (self.data_parallel, self.num_samples, self.num_samples)
        if tuple(G.shape) != expect:
            raise ValueError(
                f"checkpoint Gramian shape {tuple(G.shape)} != this run's "
                f"{expect} (cohort width or data-axis size changed)"
            )
        if meta["accum_dtype"] == "int32" and self.accum_dtype != jnp.int32:
            # The saved run had already climbed the dtype ladder; adopt
            # int32 before loading so the merge is exact by construction.
            self.operand_dtype, self.accum_dtype = np.int8, jnp.int32
        # range: checkpoint entries are exact integers within the saved
        # accumulator dtype (the GR005-proven invariant); casting to this
        # accumulator's (equal-or-wider) dtype is lossless.
        G = G.astype(np.dtype(self.accum_dtype))
        self.G = (
            device_put_global(G, self._g_sharding)
            if self._g_sharding is not None
            else jnp.asarray(G)
        )
        self._entry_bound = int(meta["entry_bound"])
        self.rows_seen = int(meta["rows_seen"])
        self._flushes = int(meta["flushes"])

    def finalize_device(self) -> jax.Array:
        """Reduce across the data axis (the one ``psum``); result stays on
        device. Downstream stages (centering, PCA) consume this directly: a
        device→host round-trip of the N×N matrix would only be copied
        back."""
        self._flush()
        self._in_flight.clear()  # release held buffers from the pipeline
        with self.telemetry.finalize_span():
            return data_axis_sum(self.G)

    def finalize(self) -> np.ndarray:
        """Host copy of :meth:`finalize_device` (tests / host backend)."""
        return np.asarray(jax.device_get(self.finalize_device())).astype(np.float64)


def _ring_tiles(G_local, X_cols, samples_axis: str, operand_dtype, packed=False):
    """One block's ring update, executed per device inside shard_map.

    ``G_local``: this device's accumulator state — its ``(N_local, N)``
    row tile of the Gramian (the full ring, below), or the half ring's
    step tiles stacked by rows, ``(S·N_local, N_local)`` with S =
    ⌊D/2⌋+1, which the device-generation ring carries and
    :func:`_half_ring_tiles` updates.
    ``X_cols``: this block's columns for this device's samples — ``(B,
    N_local)`` {0,1}/count uint8, or ``(B, N_local/8)`` bit-packed uint8
    when ``packed`` (np.packbits big-endian; ``N_local % 8 == 0``, the
    pack-width invariant ``parallel/mesh.py:padded_cohort`` guarantees).
    Packed tiles move ⅛ the bytes per ``ppermute`` and are unpacked on
    device per step (a cheap VPU shift-and-mask fused ahead of the dot).

    Double-buffered ring: the loop issues the ``ppermute`` for step k+1
    BEFORE the dot of step k consumes its tile, so the transfer and the
    matmul have no mutual dependency and XLA's async collectives overlap
    ICI with the MXU instead of alternating them; the last step's tile
    arrives while step D-2 computes and is consumed outside the loop — D-1
    permutes total (the old serialized loop paid D, one of them wasted on
    returning the tile to its owner).
    """
    D = axis_size(samples_axis)
    n_local = X_cols.shape[1] * 8 if packed else X_cols.shape[1]
    if D > 1 and G_local.shape == (half_ring_steps(D) * n_local, n_local):
        return _half_ring_tiles(
            G_local, X_cols, samples_axis, operand_dtype, packed=packed
        )
    i = lax.axis_index(samples_axis)

    def unpack(tile):
        return _unpack_bits(tile, n_local) if packed else tile

    x_mine_t = unpack(X_cols).astype(operand_dtype).T  # (N_local, B)
    if packed:
        # Materialize the unpacked own-operand once: it feeds all D dots,
        # and without the barrier XLA re-fuses the unpack+cast into each
        # dot's operand producers (same rationale as _dense_update).
        x_mine_t = lax.optimization_barrier(x_mine_t)

    def dot_into(G, tile, k):
        j = (i + k) % D  # owner of `tile`'s sample columns
        t = jnp.matmul(
            x_mine_t, unpack(tile).astype(operand_dtype),
            preferred_element_type=G.dtype,
        )  # (N_local, N_local)
        # Explicit int32 indices: under enable_x64 the literal 0 would
        # otherwise promote to int64 and mismatch the axis-index dtype.
        # range: j < D and j * n_local < padded cohort width << 2^31.
        col = (j * n_local).astype(jnp.int32)
        zero = jnp.int32(0)
        return lax.dynamic_update_slice(
            G,
            lax.dynamic_slice(G, (zero, col), (n_local, n_local)) + t,
            (zero, col),
        )

    if D == 1:
        return dot_into(G_local, X_cols, 0)
    perm = [((p + 1) % D, p) for p in range(D)]

    def body(k, carry):
        G, cur = carry
        # Issue step k+1's transfer first; the dot below shares no data
        # dependency with it, so the ICI permute runs behind the matmul.
        with jax.named_scope("ring_exchange"):
            nxt = lax.ppermute(cur, samples_axis, perm)
        return dot_into(G, cur, k), nxt

    G_local, last = lax.fori_loop(0, D - 1, body, (G_local, X_cols))
    return dot_into(G_local, last, D - 1)


def _half_ring_tiles(G_local, X_cols, samples_axis: str, operand_dtype, packed=False):
    """One block's HALF ring update, executed per device inside shard_map
    (through :func:`_ring_tiles`, which the device-generation ring calls).

    ``G_local``: this device i's S = ``half_ring_steps(D)`` step tiles
    stacked by rows, ``(S·N_local, N_local)``; rows ``[k·N_local,
    (k+1)·N_local)`` hold G's block (i, (i+k) mod D). ``X_cols`` as in
    :func:`_ring_tiles`. G = XᵀX is symmetric, so block (i, j) past the
    half is the transpose of block (j, i), which device j holds as its
    tile ``D - k``: the ring stops after step ⌊D/2⌋ — ⌊D/2⌋ permutes and
    ⌊D/2⌋+1 dots per block against the full ring's D-1 and D — and
    :func:`assemble_half_ring` mirrors the rest in once, at finalize. For
    even D, step D/2's block is computed on both its devices.

    The steps are unrolled, so each step's tile is static and its dot adds
    straight into it: where the caller keeps the tiles as separate buffers
    and stacks them only to call this (``ops/devicegen.py:_ring_update``),
    the compiler folds the stacking away, and each step is one dot-and-add
    whose output is its tile's own buffer. The full ring's device-dependent
    column offset costs a slice read and a slice write of the row tile per
    step instead. Double-buffered like :func:`_ring_tiles`: step k+1's
    ``ppermute`` is issued before step k's dot, which does not depend on
    it.
    """
    D = axis_size(samples_axis)
    n_local = X_cols.shape[1] * 8 if packed else X_cols.shape[1]

    def unpack(tile):
        return _unpack_bits(tile, n_local) if packed else tile

    x_mine_t = unpack(X_cols).astype(operand_dtype).T  # (N_local, B)
    if packed:
        # One materialization feeding every step's dot (see _ring_tiles).
        x_mine_t = lax.optimization_barrier(x_mine_t)
    perm = [((p + 1) % D, p) for p in range(D)]
    tiles = jnp.split(G_local, half_ring_steps(D))
    cur, out = X_cols, []
    for k, tile in enumerate(tiles):
        nxt = None
        if k + 1 < len(tiles):
            with jax.named_scope("ring_exchange"):
                nxt = lax.ppermute(cur, samples_axis, perm)
        out.append(
            tile
            + jnp.matmul(
                x_mine_t, unpack(cur).astype(operand_dtype),
                preferred_element_type=G_local.dtype,
            )
        )
        cur = nxt
    return jnp.concatenate(out)


def assemble_half_ring(tiles, samples_axis: str) -> jax.Array:
    """This device's ``(N_local, D·N_local)`` row tile of G from its half
    ring step tiles (:func:`_half_ring_tiles`), each ``(N_local,
    N_local)``, inside shard_map: tile k goes to column block (i+k) mod D,
    and each block (i, (i+k) mod D) for k past ⌊D/2⌋ is device (i+k) mod
    D's tile ``D - k``, taken with one ``ppermute`` and transposed.
    ``D - 1 - ⌊D/2⌋`` exchanges per device, once per job."""
    D = axis_size(samples_axis)
    i = lax.axis_index(samples_axis)
    blocks = list(tiles)
    for k in range(len(blocks), D):
        # Device p receives from device (p + k) mod D.
        perm = [((p + k) % D, p) for p in range(D)]
        with jax.named_scope("ring_mirror"):
            blocks.append(lax.ppermute(blocks[D - k], samples_axis, perm).T)
    row = jnp.zeros((tiles[0].shape[0], D * tiles[0].shape[0]), tiles[0].dtype)
    zero = jnp.int32(0)
    for k, block in enumerate(blocks):
        # range: the column block index is < D, its offset < padded << 2^31.
        col = (((i + k) % D) * block.shape[0]).astype(jnp.int32)
        row = lax.dynamic_update_slice(row, block, (zero, col))
    return row


def _hier_ring_tiles(
    G_local, X_cols, host_axis: str, device_axis: str, operand_dtype,
    packed=False,
):
    """One block's TWO-LEVEL ring update, executed per device inside
    shard_map — the pod-scale sibling of :func:`_ring_tiles`.

    The samples axis is factored host-major into ``hosts x devices``
    (``parallel/mesh.py:hierarchical_mesh``), so the inner ring's
    ``ppermute`` neighbors are intra-host (ICI) BY CONSTRUCTION and only
    the outer ring crosses hosts (DCN):

    - **inner ring** (per outer step): circulate the currently-held tile
      around the host's ``D`` devices over ICI, double-buffered exactly
      like the flat ring (permute for step j+1 issued before step j's dot);
    - **outer ring**: circulate each device's OWN tile around the ``H``
      hosts over DCN — issued BEFORE the inner ring consumes the current
      host block, so the slow DCN transfer overlaps a whole inner ring's
      ICI + MXU work, not one dot. Each host's columns cross DCN to every
      other host exactly once (``H - 1`` outer permutes), against the flat
      ring's ``S - 1`` lockstep steps each gated on its slowest edge.

    Total permutes stay ``S - 1`` (``(H-1) + H x (D-1)``) and total bytes
    stay ``ring_traffic_bytes``'s — the schedule moves the same data, it
    just proves which link every byte rides (``check/sched.py``). At the
    step (k, j) of the double loop this device holds the tile of device
    ``((h + k) mod H, (d + j) mod D)``; the flat owner index drives the
    same disjoint-slice accumulation the flat ring uses (one update per
    Gramian entry per pass — the two-radix form ``graftcheck ranges``
    proves disjoint).
    """
    H = axis_size(host_axis)
    D = axis_size(device_axis)
    h = lax.axis_index(host_axis)
    d = lax.axis_index(device_axis)
    n_local = X_cols.shape[1] * 8 if packed else X_cols.shape[1]

    def unpack(tile):
        return _unpack_bits(tile, n_local) if packed else tile

    x_mine_t = unpack(X_cols).astype(operand_dtype).T  # (N_local, B)
    if packed:
        # One materialization feeding all S dots (see _ring_tiles).
        x_mine_t = lax.optimization_barrier(x_mine_t)

    def dot_into(G, tile, k, j):
        # Owner of `tile`'s sample columns after k outer + j inner steps.
        owner = ((h + k) % H) * D + ((d + j) % D)
        t = jnp.matmul(
            x_mine_t, unpack(tile).astype(operand_dtype),
            preferred_element_type=G.dtype,
        )  # (N_local, N_local)
        # range: owner < H*D and owner * n_local < padded cohort << 2^31;
        # explicit int32 so x64 tracing cannot promote the slice indices.
        col = (owner * n_local).astype(jnp.int32)
        zero = jnp.int32(0)
        return lax.dynamic_update_slice(
            G,
            lax.dynamic_slice(G, (zero, col), (n_local, n_local)) + t,
            (zero, col),
        )

    perm_d = [((p + 1) % D, p) for p in range(D)]

    def inner_ring(G, block, k):
        if D == 1:
            return dot_into(G, block, k, 0)

        def body(j, carry):
            G, cur = carry
            # Step j+1's ICI transfer first; the dot shares no dependency.
            with jax.named_scope("ring_exchange"):
                nxt = lax.ppermute(cur, device_axis, perm_d)
            return dot_into(G, cur, k, j), nxt

        G, last = lax.fori_loop(0, D - 1, body, (G, block))
        return dot_into(G, last, k, D - 1)

    if H == 1:
        # Degenerate topology: the two-level schedule IS the flat ring.
        return inner_ring(G_local, X_cols, 0)
    perm_h = [((p + 1) % H, p) for p in range(H)]

    def outer_body(k, carry):
        G, cur = carry
        # Host block k+1's DCN transfer is issued before the inner ring
        # consumes block k — the whole inner ring hides one DCN hop.
        with jax.named_scope("ring_exchange"):
            nxt = lax.ppermute(cur, host_axis, perm_h)
        return inner_ring(G, cur, k), nxt

    G_local, last = lax.fori_loop(0, H - 1, outer_body, (G_local, X_cols))
    return inner_ring(G_local, last, H - 1)


def build_hierarchical_update(mesh, operand_dtype, packed: bool = False,
                              g_spec=None, x_spec=None):
    """The jitted two-level (ICI ring + DCN ring) Gramian update for a
    hierarchical ``data x hosts x samples`` mesh
    (``parallel/mesh.py:hierarchical_mesh``) — the runtime constructor the
    schedule prover (``check/sched.py``), the IR auditor, and the range
    prover all trace, exactly like :func:`build_sharded_update` for the
    flat ring. Works with a concrete ``Mesh`` or an ``AbstractMesh``.

    The default specs shard G rows (and X columns) over ``(hosts,
    samples)`` jointly — the SAME per-device layout as the flat ring's
    ``samples`` sharding over ``H x D`` devices, so a flat-ring
    accumulator can swap schedules without touching its staging,
    checkpoint, or finalize paths (byte-identical results, CI-asserted).
    """
    data_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
    if g_spec is None:
        g_spec = P(data_axis, (HOST_AXIS, SAMPLES_AXIS), None)
    if x_spec is None:
        x_spec = P(data_axis, None, (HOST_AXIS, SAMPLES_AXIS))

    @jax.jit
    def update(G, X):  # graftcheck: disable=GC005 -- same non-donation policy as build_sharded_update's update (measured ~10x throughput loss from donated-buffer serialization); graftcheck ir cross-checks this disable against the traced donated_invars (GI002).
        def per_slice(G_local, X_local):
            return _hier_ring_tiles(
                G_local[0], X_local[0], HOST_AXIS, SAMPLES_AXIS,
                operand_dtype, packed=packed,
            )[None]

        return shard_map(
            per_slice,
            mesh=mesh,
            in_specs=(g_spec, x_spec),
            out_specs=g_spec,
        )(G, X)

    return update


def build_sharded_update(mesh, operand_dtype, packed: bool = False,
                         g_spec=None, x_spec=None):
    """The jitted ring-exchange Gramian update for ``mesh``.

    ONE construction site shared by three callers so they can never drift:
    :class:`ShardedGramianAccumulator` (the runtime), the device-free plan
    validator (``check/plan.py``, over an ``AbstractMesh``), and the IR
    auditor (``check/ir.py``, which walks the traced jaxpr of exactly this
    function to prove the overlap/donation/dtype/traffic contracts). Works
    with a concrete ``Mesh`` or an ``AbstractMesh`` — nothing here touches
    a device.

    ``g_spec``/``x_spec`` default to the accumulator's shardings (data axis
    only when the mesh has one); pass them explicitly to match a
    pre-computed accumulator layout.
    """
    data_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
    if g_spec is None:
        g_spec = P(data_axis, SAMPLES_AXIS, None)
    if x_spec is None:
        x_spec = P(data_axis, None, SAMPLES_AXIS)

    @jax.jit
    def update(G, X):  # graftcheck: disable=GC005 -- same non-donation policy as _dense_update (measured ~10x throughput loss from donated-buffer serialization); the pipeline holds prior G references, which donation would invalidate. graftcheck ir cross-checks this disable against the traced donated_invars (GI002).
        def per_slice(G_local, X_local):
            # Leading data-axis dim is size 1 locally; drop it.
            return _ring_tiles(
                G_local[0], X_local[0], SAMPLES_AXIS, operand_dtype,
                packed=packed,
            )[None]

        return shard_map(
            per_slice,
            mesh=mesh,
            in_specs=(g_spec, x_spec),
            out_specs=g_spec,
        )(G, X)

    return update


class ShardedGramianAccumulator:
    """Sharded strategy: Gramian row-tiles over the ``samples`` axis, ring
    exchange per block, optional data-parallel axis on top.

    ``pack_bits`` selects the ring wire format (``--ring-pack-bits``):
    packed tiles move 8× fewer bytes per ``ppermute`` AND the host staging
    ships bit-packed (8× less host→device traffic); ``off`` keeps the
    unpacked uint8 wire as the bit-exact oracle. Count-valued blocks
    (same-set joins, entries > 1) cannot pack and transparently ride the
    unpacked kernel per flush — exactness never depends on the wire format.
    """

    def __init__(
        self,
        num_samples: int,
        mesh: Mesh,
        block_size: int = 1024,
        exact_int: bool = False,
        sync_every: int = 1,
        registry=None,
        spans=None,
        pack_bits: str = "auto",
        check_ranges: bool = False,
        reduce_schedule: str = "auto",
        hier_hosts: Optional[int] = None,
    ):
        self.telemetry = _AccumulatorTelemetry(registry, spans, "sharded")
        self.check_ranges = bool(check_ranges)
        self.sync_every = max(1, int(sync_every))
        self._flushes = 0
        if SAMPLES_AXIS not in mesh.shape:
            raise ValueError(f"mesh must have a {SAMPLES_AXIS!r} axis")
        self.mesh = mesh
        self.pack = resolve_ring_pack(pack_bits)
        self.samples_parallel = mesh.shape[SAMPLES_AXIS]
        self.data_parallel = mesh.shape.get(DATA_AXIS, 1)
        # --reduce-schedule: the flat ring, or the two-level hierarchical
        # schedule over the host-major factorization (auto = hier iff the
        # samples axis spans more than one host). Everything OUTSIDE the
        # update kernel — G, staging, checkpointing, finalize — is
        # schedule-independent: the hierarchical mesh shards the same rows
        # over the same devices in the same order, so swapping schedules
        # changes which links the tiles ride and nothing else
        # (byte-identical results, CI-asserted).
        resolve_reduce_schedule(reduce_schedule, 1)  # validate the spelling
        try:
            self.hier_hosts = resolve_hier_hosts(
                self.samples_parallel, hier_hosts
            )
        except ValueError:
            if reduce_schedule == "hier":
                raise  # an explicit hier request must not silently degrade
            # auto/flat: a non-dividing host factor just means no
            # hierarchical factorization exists — the flat ring runs.
            self.hier_hosts = 1
        self.reduce_schedule = resolve_reduce_schedule(
            reduce_schedule, self.hier_hosts
        )
        self._hier_mesh = (
            hierarchical_mesh(mesh, self.hier_hosts)
            if self.reduce_schedule == "hier"
            else None
        )
        # Cohort padding: a multiple of the samples axis (equal column tiles
        # per device) and, under the packed wire format, of 8× that (every
        # device's tile a whole number of bytes — the pack-width invariant).
        # Padded columns are all-zero and are trimmed in finalize().
        self._padded = padded_cohort(
            num_samples, self.samples_parallel, pack=self.pack
        )
        self.num_samples = int(num_samples)
        self.n_local = self._padded // self.samples_parallel
        self.block_size = int(block_size)
        self.exact_int = bool(exact_int)
        self.operand_dtype, self.accum_dtype = _operand_dtypes(exact_int, mesh)
        self._entry_bound = 0
        self.ring_bytes_total = 0

        rows = self.data_parallel * self.block_size
        self._staging = np.zeros((rows, self._padded), dtype=np.uint8)
        self._fill = 0
        self.rows_seen = 0

        data_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
        g_spec = P(data_axis, SAMPLES_AXIS, None)
        # One spec serves both wire formats: the packed block shards its
        # (padded/8)-wide byte dim over ``samples`` and the byte boundary
        # coincides with every shard boundary (pack-width invariant), so
        # each device's shard is exactly its own columns, packed.
        x_spec = P(data_axis, None, SAMPLES_AXIS)
        self._g_sharding = NamedSharding(mesh, g_spec)
        self._x_sharding = NamedSharding(mesh, x_spec)
        self.G = device_put_global(
            jnp.zeros(
                (self.data_parallel, self._padded, self._padded), self.accum_dtype
            ),
            self._g_sharding,
        )

        self._g_spec, self._x_spec = g_spec, x_spec
        self._update = self._build_update(self.operand_dtype)
        self._update_packed = (
            self._build_update(self.operand_dtype, packed=True)
            if self.pack
            else None
        )

    def _build_update(self, operand_dtype, packed: bool = False):
        if self._hier_mesh is not None:
            # The hierarchical specs name the factored axes; G/X keep their
            # flat-mesh shardings (identical device layout — the jit sees
            # the same HloSharding, so no reshard happens at the boundary).
            data_axis = (
                DATA_AXIS if DATA_AXIS in self._hier_mesh.shape else None
            )
            return build_hierarchical_update(
                self._hier_mesh,
                operand_dtype,
                packed,
                P(data_axis, (HOST_AXIS, SAMPLES_AXIS), None),
                P(data_axis, None, (HOST_AXIS, SAMPLES_AXIS)),
            )
        return build_sharded_update(
            self.mesh, operand_dtype, packed, self._g_spec, self._x_spec
        )

    def schedule_block(self) -> dict:
        """The run manifest's ``schedule`` block: which reduction schedule
        ran, its topology factorization, the STATIC per-flush projection of
        ring bytes next to the per-flush-accounted total — the
        predicted-vs-measured pair ``bench.py`` reports so BENCH rounds
        catch formula drift (a counts-fallback flush or a wire-format
        change moves ``measured`` away from ``predicted``)."""
        capacity_rows = self.data_parallel * self.block_size
        per_flush = ring_traffic_bytes(
            capacity_rows, self.samples_parallel, self.n_local, self.pack
        )
        predicted = per_flush * self._flushes
        if self.reduce_schedule == "hier":
            level = hierarchical_traffic_bytes(
                capacity_rows,
                self.hier_hosts,
                self.samples_parallel // self.hier_hosts,
                self.n_local,
                self.pack,
            )
            ici, dcn = (
                level.ici_bytes * self._flushes,
                level.dcn_bytes * self._flushes,
            )
        elif self.hier_hosts == 1:
            ici, dcn = predicted, 0
        else:
            # Flat ring spanning hosts: no byte is provably intra-host
            # (parallel/mesh.py:flat_traffic_split) — the GS001 premise.
            ici, dcn = 0, predicted
        return {
            "kind": self.reduce_schedule,
            "hosts": int(self.hier_hosts),
            "devices_per_host": int(
                self.samples_parallel // self.hier_hosts
            ),
            "predicted_ring_bytes": int(predicted),
            "measured_ring_bytes": int(self.ring_bytes_total),
            "predicted_ici_bytes": int(ici),
            "predicted_dcn_bytes": int(dcn),
        }

    def add_rows(self, rows: np.ndarray) -> None:
        rows = np.asarray(rows, dtype=np.uint8)
        if rows.ndim != 2 or rows.shape[1] != self.num_samples:
            raise ValueError(
                f"expected (b, {self.num_samples}) rows, got {rows.shape}"
            )
        self.rows_seen += rows.shape[0]
        offset = 0
        capacity = self._staging.shape[0]
        while offset < rows.shape[0]:
            take = min(capacity - self._fill, rows.shape[0] - offset)
            self._staging[
                self._fill : self._fill + take, : self.num_samples
            ] = rows[offset : offset + take]
            self._fill += take
            offset += take
            if self._fill == capacity:
                self._flush()

    def _flush(self) -> None:
        if self._fill == 0:
            return
        flush_rows, flush_start = self._fill, time.perf_counter()
        block = self._staging
        if self._fill < block.shape[0]:
            block = block.copy()
            block[self._fill :] = 0
        max_count = int(block.max(initial=0))
        # Same shared projection formula as the dense path (GR005).
        next_bound = self._entry_bound + flush_entry_increment(
            self._fill, max_count
        )
        if _maybe_switch_accumulator(
            self, next_bound, out_shardings=self._g_sharding
        ):
            # The scanned update closes over the operand dtype — rebuild it.
            self._update = self._build_update(self.operand_dtype)
            if self.pack:
                self._update_packed = self._build_update(
                    self.operand_dtype, packed=True
                )
        self._entry_bound = next_bound
        X = block.reshape(self.data_parallel, self.block_size, self._padded)
        # Count-valued rows (same-set joins) cannot bit-pack; they ride the
        # unpacked kernel for this flush — same geometry, same result.
        use_packed = self.pack and max_count <= 1
        if use_packed:
            # Host staging ships packed: ⅛ the host→device bytes, and the
            # ring circulates the packed tiles as-is (np.packbits allocates
            # fresh, so the reused staging buffer is never in flight).
            Xd = device_put_global(np.packbits(X, axis=-1), self._x_sharding)
            self.G = self._update_packed(self.G, Xd)
        else:
            self.G = self._update(self.G, device_put_global(X, self._x_sharding))
        self._fill = 0
        self._flushes += 1
        if self._flushes % self.sync_every == 0:
            jax.block_until_ready(self.G)
        if self.check_ranges:
            self.telemetry.record_entry_sample(self.G, self._entry_bound)
        flush_seconds = time.perf_counter() - flush_start
        flush_ring_bytes = ring_traffic_bytes(
            self.data_parallel * self.block_size,
            self.samples_parallel,
            self.n_local,
            use_packed,
        )
        self.ring_bytes_total += flush_ring_bytes
        self.telemetry.record_ring(flush_ring_bytes, flush_seconds)
        self.telemetry.record_flush(flush_rows, flush_seconds, 0)

    def snapshot_state(self) -> dict:
        """Checkpoint state of the sharded strategy: the row-tile-sharded
        (padded) partial Gramian fetched whole, plus the dtype-ladder
        position and cursor — see ``GramianAccumulator.snapshot_state``."""
        self._flush()
        jax.block_until_ready(self.G)  # graftcheck: disable=GC001 -- deliberate checkpoint barrier at --checkpoint-every-sites cadence (see the dense accumulator's snapshot_state)
        G_host = np.asarray(jax.device_get(self.G))  # graftcheck: disable=GC001 -- deliberate periodic checkpoint fetch (the artifact payload), not a hot-path sync
        return {
            "strategy": "sharded",
            "G": G_host,
            "accum_dtype": np.dtype(self.accum_dtype).name,
            "exact_int": self.exact_int,
            "entry_bound": self._entry_bound,
            "rows_seen": self.rows_seen,
            "flushes": self._flushes,
            "num_samples": self.num_samples,
            "data_parallel": self.data_parallel,
            "padded": self._padded,
            # Ring accounting rides along so a resumed run's manifest
            # schedule block keeps predicted == measured (both count the
            # pre-crash flushes); absent in old artifacts -> 0.
            "ring_bytes_total": self.ring_bytes_total,
        }

    def restore_state(self, checkpoint: dict) -> None:
        """Sharded counterpart of ``GramianAccumulator.restore_state``:
        shape/strategy checks, dtype-ladder adoption (including the
        dtype-closed ring update rebuild), then the sharded device load."""
        meta, G = checkpoint["meta"], checkpoint["G"]
        if meta["strategy"] != "sharded":
            raise ValueError(
                f"checkpoint was written by the {meta['strategy']!r} "
                "strategy; this run resolved sharded — the similarity "
                "strategy is part of the checkpoint geometry"
            )
        expect = (self.data_parallel, self._padded, self._padded)
        if tuple(G.shape) != expect:
            raise ValueError(
                f"checkpoint Gramian shape {tuple(G.shape)} != this run's "
                f"{expect} (cohort width, padding, mesh data axis, or the "
                "samples-axis tile count changed)"
            )
        if meta["accum_dtype"] == "int32" and self.accum_dtype != jnp.int32:
            self.operand_dtype, self.accum_dtype = np.int8, jnp.int32
            # The scanned updates close over the operand dtype — rebuild.
            self._update = self._build_update(self.operand_dtype)
            if self.pack:
                self._update_packed = self._build_update(
                    self.operand_dtype, packed=True
                )
        # range: checkpoint entries are exact integers within the saved
        # dtype (GR005 invariant); the equal-or-wider target is lossless.
        G = G.astype(np.dtype(self.accum_dtype))
        self.G = device_put_global(G, self._g_sharding)
        self._entry_bound = int(meta["entry_bound"])
        self.rows_seen = int(meta["rows_seen"])
        self._flushes = int(meta["flushes"])
        self.ring_bytes_total = int(meta.get("ring_bytes_total", 0))

    def finalize(self) -> np.ndarray:
        self._flush()
        with self.telemetry.finalize_span():
            total = data_axis_sum(self.G)
        full = np.asarray(jax.device_get(total)).astype(np.float64)
        return full[: self.num_samples, : self.num_samples]

    def finalize_device_padded(self) -> jax.Array:
        """Device-resident reduce over the data axis; includes cohort padding
        columns/rows (all zero). See :meth:`finalize_sharded` for the
        samples-sharded variant."""
        self._flush()
        with self.telemetry.finalize_span():
            return data_axis_sum(self.G)

    def finalize_sharded(self) -> jax.Array:
        """Device-resident finalize: (padded N, padded N) row-sharded over
        ``samples`` — for cohorts where the host copy is undesirable."""
        self._flush()
        with self.telemetry.finalize_span():
            return data_axis_sum(
                self.G,
                out_shardings=NamedSharding(self.mesh, P(SAMPLES_AXIS, None)),
            )


def accumulate_index_rows(
    acc,
    call_rows,
    num_columns: int,
    block_size: int,
    accumulate_duplicates: bool = False,
) -> None:
    """Stage per-variant column-index rows into dense uint8 blocks and feed
    an accumulator — the one shared row-staging loop (driver and public API).

    ``accumulate_duplicates`` switches to unbuffered accumulation so a column
    appearing k times contributes k² per entry (the reference's pair-loop
    multiplicity, ``VariantsPca.scala:224-229`` — needed when a variant set
    is joined with itself); the default fast path sets membership bits.
    """
    staging: list = []

    def flush():
        if not staging:
            return
        rows = np.zeros((len(staging), num_columns), dtype=np.uint8)
        for i, row in enumerate(staging):
            if accumulate_duplicates:
                np.add.at(rows[i], np.asarray(list(row), dtype=np.int64), 1)
            else:
                rows[i, list(row)] = 1
        acc.add_rows(rows)
        staging.clear()

    for row in call_rows:
        staging.append(row)
        if len(staging) >= block_size:
            flush()
    flush()


def gramian_reference(rows: np.ndarray) -> np.ndarray:
    """Host NumPy oracle: the pair-counting semantics of
    ``VariantsPca.scala:224-229`` (for each variant, +1 for every ordered
    pair of varying samples), vectorized."""
    X = np.asarray(rows, dtype=np.int64)
    return X.T @ X


__all__ = [
    "GramianAccumulator",
    "ShardedGramianAccumulator",
    "assemble_half_ring",
    "build_hierarchical_update",
    "build_sharded_update",
    "data_axis_sum",
    "gramian_reference",
    "resolve_ring_pack",
]
