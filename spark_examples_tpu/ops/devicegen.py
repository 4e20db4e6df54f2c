"""On-device synthetic ingest: site metadata + genotype generation fused
with Gramian accumulation.

The reference's runtime is dominated by ingest: executors stream variant
pages from the Genomics API and the similarity pass consumes them
(``VariantsRDD.scala:198-225`` feeding ``VariantsPca.scala:222-231``). The
synthetic source stands in for that ingest, and its entire data plane is
counter-based u64 hashing (splitmix64) plus fixed-point arithmetic
(``sources/synthetic.py``) — all trivially jittable. This module moves the
whole ingest onto the TPU:

- per dispatch the host sends TWO SCALARS (a site-grid offset and a valid
  count); the device reconstructs positions, recomputes the per-site
  metadata (ref-block drops, Q32 allele frequencies, per-population
  genotype thresholds, the ``--min-allele-frequency`` filter) bit-identically
  to the host source, generates the (block, samples) genotype matrix with
  the exact same splitmix64 draws, and accumulates ``G += XᵀX`` on the MXU —
  one scanned XLA program per dispatch group;
- there is no per-site host→device traffic at all, so throughput is pure
  device compute, independent of host→device bandwidth; fused scanning
  keeps dispatches in the hundreds for a whole-genome run (158 at the
  2,504-sample whole-genome geometry).

Exactness of the host↔device correspondence is trivial by construction:
both sides draw the same uint32 allele pair (``_allele_pair`` here,
``_genotype_draw_pair`` on host) and compare against the same Q32 integer
thresholds — pure integer arithmetic, no floating point anywhere in the
data plane. The AF filter compares micro-units (``round(af·1e6)``,
half-even) against ``floor(threshold·1e6)`` (exact via Fraction) — the same
rule every host path uses (``sources/synthetic.py:af_passes``).
"""

from __future__ import annotations

import collections
import functools
import re
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_examples_tpu.parallel.mesh import device_put_global

# splitmix64 constants — must match sources/synthetic.py exactly.
_P1 = 0x9E3779B97F4A7C15
_P2 = 0xC2B2AE3D27D4EB4F
_P3 = 0x165667B19E3779F9
_P4 = 0xD6E8FEB86659FD93
_M1 = 0xBF58476D1CE4E5B9
_M2 = 0x94D049BB133111EB
_MASK64 = (1 << 64) - 1
# Draw-stream tags (sources/synthetic.py).
_S_REF_BLOCK = 1
_S_AF = 2
_S_POP_BASE = 3
_S_GENOTYPE = 100


def _c64(value: int) -> jax.Array:
    """uint64 constant, wrapped mod 2^64 (Python ints over 2^63 would
    overflow the default int path)."""
    return jnp.asarray(np.uint64(value & _MASK64))


def mix64(x: jax.Array) -> jax.Array:
    """splitmix64 finalizer on uint64 arrays — bitwise-identical to
    ``sources/synthetic.py:_mix`` (tested)."""
    x = (x + _c64(_P1)).astype(jnp.uint64)
    x = ((x ^ (x >> jnp.uint64(30))) * _c64(_M1)).astype(jnp.uint64)
    x = ((x ^ (x >> jnp.uint64(27))) * _c64(_M2)).astype(jnp.uint64)
    return (x ^ (x >> jnp.uint64(31))).astype(jnp.uint64)


def _u64_stream(key: jax.Array, pos_term: jax.Array, stream: int) -> jax.Array:
    """``sources/synthetic.py:_u64(key, pos, stream)`` with default
    sample/allele — four chained mixes (the zero sample/allele terms still
    mix)."""
    h = mix64(key ^ pos_term)
    h = mix64(h ^ _c64(stream * _P3))
    h = mix64(h)  # sample = 0
    return mix64(h)  # allele = 0


def site_thresholds_on_device(
    site_key: jax.Array,  # scalar uint64
    positions: jax.Array,  # (B,) int64
    valid: jax.Array,  # (B,) bool
    n_pops: int,
    ref_block_fraction: float,
    min_af_micro: Optional[int],
) -> jax.Array:
    """(B, P) uint64 Q32 genotype thresholds (``af_pop_q32``), zeroed for
    ref-block sites, AF-filtered sites, and invalid (padding) rows —
    bit-identical to the host's ``_site_fields_q`` metadata / the
    ``site_threshold_plan`` values (``sources/synthetic.py``)."""
    from spark_examples_tpu.sources.synthetic import (
        _AF_BASE_Q32,
        _AF_SPAN_Q16,
        _POP_BASE_Q16,
        _POP_HI_Q32,
        _POP_LO_Q32,
        _POP_SPAN_Q17,
    )
    import math

    pos_term = positions.astype(jnp.uint64) * _c64(_P2)
    ref_thresh = math.ceil(ref_block_fraction * 2.0**53)
    is_ref = (
        _u64_stream(site_key, pos_term, _S_REF_BLOCK) >> jnp.uint64(11)
    ) < _c64(ref_thresh)
    u_af = _u64_stream(site_key, pos_term, _S_AF) >> jnp.uint64(48)  # Q16
    af_q32 = _c64(_AF_BASE_Q32) + ((u_af * u_af * _c64(_AF_SPAN_Q16)) >> jnp.uint64(16))
    keep = valid & ~is_ref
    if min_af_micro is not None:
        # round-half-even(af_q32 · 1e6 / 2^32) > floor(threshold · 1e6):
        # the canonical micro-unit AF rule (sources/synthetic.py:af_passes).
        x = af_q32 * _c64(1_000_000)
        q = x >> jnp.uint64(32)
        frac = x & _c64((1 << 32) - 1)
        half = _c64(1 << 31)
        r = q + ((frac > half) | ((frac == half) & ((q & jnp.uint64(1)) == 1))).astype(jnp.uint64)
        keep = keep & (r > _c64(min_af_micro))
    pops = []
    for p in range(n_pops):
        u_p = _u64_stream(site_key, pos_term, _S_POP_BASE + p) >> jnp.uint64(48)
        factor = _c64(_POP_BASE_Q16) + ((u_p * _c64(_POP_SPAN_Q17)) >> jnp.uint64(16))
        af_pop = jnp.clip(
            (af_q32 * factor) >> jnp.uint64(16),
            _c64(_POP_LO_Q32),
            _c64(_POP_HI_Q32),
        )
        pops.append(af_pop)  # Q32 threshold
    T = jnp.stack(pops, axis=1)  # (B, P)
    return jnp.where(keep[:, None], T, jnp.uint64(0))


def fmix32(x: jax.Array) -> jax.Array:
    """murmur3 fmix32 finalizer on uint32 arrays — bitwise-identical to
    ``sources/synthetic.py:_fmix32`` (tested)."""
    x = (x ^ (x >> jnp.uint32(16))) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> jnp.uint32(13))) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))


def _pop_segments(pops_np: np.ndarray) -> Optional[list]:
    """``[(pop, start, stop)]`` run-length segments of a non-decreasing
    population vector, or ``None`` when the vector is not contiguous (or too
    fragmented to be worth unrolling). The synthetic source assigns
    contiguous population blocks by construction, which lets the kernel
    pick each column's threshold by a static select over the segment stops
    (:func:`_segment_thresholds`) instead of a (B, N) gather."""
    if pops_np.ndim != 1 or len(pops_np) == 0:
        return None
    diffs = np.diff(pops_np)
    if np.any(diffs < 0):
        return None
    boundaries = np.flatnonzero(diffs) + 1
    if len(boundaries) > 15:
        return None
    if len(pops_np) < 128 * (len(boundaries) + 1):
        # Narrow segments waste VPU lanes (each pads to the 128-lane
        # register width): a 17-sample deep-call cohort is ~2.5× FASTER
        # through the single gathered compare (a pre-chip claim).
        return None
    starts = np.concatenate([[0], boundaries])
    stops = np.concatenate([boundaries, [len(pops_np)]])
    return [
        (int(pops_np[s]), int(s), int(e)) for s, e in zip(starts, stops)
    ]


def _set_segments(
    pops_np: np.ndarray, n_sets: int, set_sizes: Optional[Tuple[int, ...]]
) -> list:
    """Per variant set, its :func:`_pop_segments` (``None`` where the set
    takes the gather). ``pops_np`` is the concatenation of the per-set
    population vectors when ``set_sizes`` is given, else the one cohort
    every set shares."""
    if set_sizes is None:
        return [_pop_segments(pops_np)] * n_sets
    cum = np.concatenate([[0], np.cumsum(set_sizes)])
    return [_pop_segments(pops_np[cum[s] : cum[s + 1]]) for s in range(n_sets)]


def _segment_thresholds(Tq32: jax.Array, segments: list) -> jax.Array:
    """(B, n) per-column Q32 thresholds of one set's contiguous segments: a
    static select chain over the segment stops on a column iota, so the
    threshold broadcast fuses into the hash that compares against it."""
    n = segments[-1][2]
    cols = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    tf = Tq32[:, segments[-1][0] : segments[-1][0] + 1]
    for pop, _, stop in reversed(segments[:-1]):
        tf = jnp.where(cols < stop, Tq32[:, pop : pop + 1], tf)
    return tf


def _allele_pair(h2_col: jax.Array, samples_u64: jax.Array):
    """The two (B, n) uint32 allele draws from the per-site genotype state —
    the device half of ``sources/synthetic.py:_genotype_draw_pair``: xor the
    sample term into the 64-bit state, fold to 32 bits, one fmix32, and a
    multiplicative re-mix for the second allele. One u64 xor + three u32
    multiplies per (site, sample) — the ingest hot loop (DESIGN.md
    "single-chip ingest roofline")."""
    x64 = h2_col ^ samples_u64
    # range: deliberate 64→32 bit FOLD (high xor low) — the draw is defined
    # on u32; truncation is the hash, not a lost value (DESIGN.md §7 step 1).
    x32 = ((x64 >> jnp.uint64(32)) ^ x64).astype(jnp.uint32)
    d1 = fmix32(x32)
    d2 = (d1 * jnp.uint32(0x9E3779B9)) ^ jnp.uint32(0x85EBCA6B)
    return d1, d2


def generate_has_variation(
    positions: jax.Array,  # (B,) int64
    thresholds: jax.Array,  # (B, P) uint64 Q32 thresholds, 0 = dropped
    vs_keys: jax.Array,  # (S,) uint64: per-variant-set genotype stream keys
    pops: jax.Array,  # (N_total,) int32: per-set cohorts' sample → population
    set_sizes: Optional[Tuple[int, ...]] = None,  # per-set cohort sizes
) -> jax.Array:
    """(B, ΣNₛ) {0,1} has-variation rows, bitwise-equal to the host packed
    path (``sources/synthetic.py:genotype_blocks``) for kept sites; rows
    whose thresholds are zeroed come out all-zero (contribute nothing to
    XᵀX).

    Multi-dataset: synthetic variant sets share the site grid (site identity
    is keyed by position only — ``sources/synthetic.py:_site_fields``), so
    the reference's 2-set join and ≥3-set merge-intersect
    (``VariantsPca.scala:155-188``) both reduce to column concatenation of
    per-set genotype matrices; ``vs_keys`` carries one stream per set.
    Cohorts may differ per set (the 1KG × Platinum scenario): ``pops`` is
    the concatenation of each set's population vector and ``set_sizes``
    splits it. With ``set_sizes`` omitted, every set shares the one cohort
    ``pops`` describes.

    When ``pops`` is a concrete array (always the case from the memoized
    update builders, which close over it), a set of contiguous population
    blocks picks each column's threshold by a static select chain
    (:func:`_segment_thresholds`) — no (B, N) gather, and one hash over all
    of the set's columns; a traced or non-contiguous ``pops`` falls back to
    the gather.
    """
    n_sets = vs_keys.shape[0]
    try:
        pops_np: Optional[np.ndarray] = np.asarray(pops)
    except Exception:  # a tracer: no static view available
        pops_np = None
    if set_sizes is None:
        sizes = (pops.shape[0],) * n_sets
        offsets = [0] * n_sets
        pops_dyn = [pops] * n_sets
    else:
        sizes = tuple(int(s) for s in set_sizes)
        cum = np.concatenate([[0], np.cumsum(sizes)])
        offsets = [int(c) for c in cum[:-1]]
        pops_dyn = [
            lax.slice_in_dim(pops, offsets[s], offsets[s] + sizes[s])
            for s in range(n_sets)
        ]
    segments = (
        _set_segments(pops_np, n_sets, set_sizes)
        if pops_np is not None
        else [None] * n_sets
    )
    # range: Q32 thresholds are < 2^32 by construction (clipped at
    # _POP_HI_Q32, sources/synthetic.py) — uint32 holds them exactly.
    Tq32 = thresholds.astype(jnp.uint32)
    pos_term = positions.astype(jnp.uint64) * _c64(_P2)
    parts = []
    for s in range(n_sets):
        h1 = mix64(vs_keys[s] ^ pos_term)  # (B,)
        h2 = mix64(h1 ^ _c64(_S_GENOTYPE * _P3))[:, None]
        samples = (jnp.arange(sizes[s], dtype=jnp.uint64) * _c64(_P4))[None, :]
        d1, d2 = _allele_pair(h2, samples)
        if segments[s] is not None:
            tf = _segment_thresholds(Tq32, segments[s])  # (B, N_s)
        else:
            tf = jnp.take(Tq32, pops_dyn[s], axis=1)  # (B, N_s)
        parts.append((d1 < tf) | (d2 < tf))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def generate_column_block(
    positions: jax.Array,  # (B,) int64
    thresholds: jax.Array,  # (B, P) uint64 Q32 thresholds, 0 = dropped
    vs_key: jax.Array,  # (scalar | (S,)) uint64 genotype stream key(s)
    pops_local: jax.Array,  # (N_local,) int32: this slice's column pops
    col_start: jax.Array,  # scalar int: first GLOBAL column index
    num_samples: int,  # total columns (Σ per-set sizes for multi-set)
    set_sizes: Optional[Tuple[int, ...]] = None,
) -> jax.Array:
    """(B, N_local) {0,1} has-variation for one COLUMN slice: the genotype
    draw is keyed by the set-local sample index, so a slice can generate
    exactly its own columns of the cohort matrix (bitwise-equal to the
    corresponding columns of :func:`generate_has_variation`); padded
    columns past ``num_samples`` come out all-zero. ``pops_local`` is traced
    (sliced by axis index inside shard_map), so this path keeps the
    threshold gather.

    Multi-set joint cohorts (``set_sizes`` + an (S,) ``vs_key`` array, the
    reference's join/merge scenario ``VariantsPca.scala:155-188``): the
    global column space is the concatenation of per-set cohorts, and a
    slice's columns may span set boundaries — each set's draw plane is
    computed for the whole slice and masked to its own columns (S× the
    per-column u32 work; S is 2–3 in practice, and the alternative is the
    orders-of-magnitude-slower host wire ingest). ``pops_local`` is then a
    slice of the CONCATENATED per-set population vector."""
    n_local = pops_local.shape[0]
    cols = col_start + jnp.arange(n_local, dtype=jnp.int64)
    pos_term = positions.astype(jnp.uint64) * _c64(_P2)
    # range: Q32 thresholds < 2^32 by construction (clipped at _POP_HI_Q32).
    t_full = jnp.take(thresholds, pops_local, axis=1).astype(jnp.uint32)
    t_full = jnp.where((cols < num_samples)[None, :], t_full, jnp.uint32(0))
    if set_sizes is None:
        samples = (cols.astype(jnp.uint64) * _c64(_P4))[None, :]
        h1 = mix64(vs_key ^ pos_term)  # (B,)
        h2 = mix64(h1 ^ _c64(_S_GENOTYPE * _P3))[:, None]
        d1, d2 = _allele_pair(h2, samples)
        return (d1 < t_full) | (d2 < t_full)
    offsets = np.concatenate([[0], np.cumsum(set_sizes)])
    hv = jnp.zeros((positions.shape[0], n_local), dtype=bool)
    for s, size in enumerate(set_sizes):
        lo, hi = int(offsets[s]), int(offsets[s + 1])
        mask = (cols >= lo) & (cols < hi)
        # Set-local sample index; clamped outside the mask so the uint64
        # cast never sees a negative value.
        local_idx = jnp.clip(cols - lo, 0, max(size - 1, 0))
        samples = (local_idx.astype(jnp.uint64) * _c64(_P4))[None, :]
        h1 = mix64(vs_key[s] ^ pos_term)
        h2 = mix64(h1 ^ _c64(_S_GENOTYPE * _P3))[:, None]
        d1, d2 = _allele_pair(h2, samples)
        hv = hv | (mask[None, :] & ((d1 < t_full) | (d2 < t_full)))
    return hv


# Measured v5e sweet spot: 524,288-site dispatch groups at 2,504 columns
# (~40 ms of device work per dispatch). Per-dispatch overhead (the host
# loop) is fixed, so the per-dispatch SITE budget scales inversely with
# the cohort's column count: the 17-column deep-call cohort ran ~2× faster
# at K=512 than at the large-N optimum K=32 (platinum whole-genome
# 1.03 → 0.53 s, measured before PR 1 — DESIGN.md §7.3); past ~512
# the gain plateaus, and at ≥2,504 columns larger K measurably regresses
# (tail padding × 22 contigs).
_TARGET_COLUMN_SITES = 524_288 * 2504


def auto_blocks_per_dispatch(total_columns: int, block_size: int) -> int:
    """Dispatch-group length (``lax.scan`` steps) for a cohort: constant
    device work per dispatch across cohort sizes, clamped to the measured
    [32, 512] sweet range and rounded to a multiple of 8 (the tail program
    is K/8 blocks)."""
    k = _TARGET_COLUMN_SITES // max(int(total_columns), 1)
    k //= max(int(block_size), 1)
    return int(min(512, max(32, (k // 8) * 8)))


#: Share of one device's memory the copies of G a dispatch loop keeps
#: live may take, where one queued dispatch leaves room: half of the dense
#: rule's 80% (``ops/gramian.py:DENSE_HBM_FRACTION``), the other half left
#: to the finalize (the result and its centred copy) and to what the
#: caller holds.
LOOP_HBM_FRACTION = 0.4


def dispatch_depth(gramian_bytes: int, device_bytes: int) -> int:
    """The most dispatches a device-generation loop leaves queued once it
    has handed the next one over: after each dispatch it waits for the one
    ``depth`` back. The update does not donate G, so the copies this keeps
    live (:func:`gramian_copies_max`, ``depth + 2``) are to fit
    :data:`LOOP_HBM_FRACTION` of the device; but at least one dispatch
    stays queued behind the running one, so the device never waits for the
    host between dispatches. A 2,504-sample G (25 MB) gets a depth of
    hundreds, past the 158 dispatches of a whole-genome job, and never
    waits; a 50,000-sample ring tile (2.5 GB) gets 1, three copies."""
    copies = int(LOOP_HBM_FRACTION * device_bytes) // max(1, int(gramian_bytes))
    return max(1, copies - 2)


def gramian_copies_max(dispatches: int, depth: int) -> int:
    """The most G buffers per device a loop of ``dispatches`` dispatches
    can keep live when it waits, after each, for the one ``depth`` back:
    just before that wait ``depth + 1`` dispatches may be queued, each
    writing its own output, and the oldest still reads its input. The loop
    reports it (the driver's ``ingest`` span) and ``check/plan.py`` sizes a
    ring's device memory with it."""
    return min(int(dispatches), int(depth) + 1) + 1


def _shard_bytes(array: jax.Array) -> int:
    """Bytes of one device's shard of ``array``."""
    shape = array.sharding.shard_shape(array.shape)
    return int(np.prod(shape, dtype=np.int64)) * array.dtype.itemsize


#: Named scopes of the update programs' scan body: site metadata, the
#: genotype hash and the operand cast (``generate``), the MXU dot and its
#: accumulate (``int8_dot``), the kept/variant-row reductions (``count``),
#: and, in the ring program only, the tile ``ppermute`` (``ring_exchange``,
#: set in ``ops/gramian.py``).
UPDATE_SCOPES = ("generate", "int8_dot", "count", "ring_exchange")

#: The update programs this process has dispatched, by XLA module name:
#: ``(jitted program, its arguments' abstract shapes)`` of the latest one
#: under that name, noted at each dispatch (an identity check) so
#: :func:`update_op_scopes` can rebuild the compiled text later.
_DISPATCHED: Dict[str, tuple] = {}

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _note_dispatch(update, args) -> None:
    module = "jit_" + update.__name__
    entry = _DISPATCHED.get(module)
    if entry is None or entry[0] is not update:
        _DISPATCHED[module] = (
            update,
            jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                tuple(args),
            ),
        )


def _innermost_scope(op_name: str) -> Optional[str]:
    for part in reversed(op_name.split("/")):
        if part in UPDATE_SCOPES:
            return part
    return None


def hlo_op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{HLO instruction name: scope}`` for the instructions of a compiled
    module (``compiled.as_text()``) whose ``op_name`` metadata passes
    through one of :data:`UPDATE_SCOPES` (the innermost wins).

    A fusion takes the scope that most of its fused instructions carry
    (ties go to its own metadata): XLA gives a fusion the metadata of one
    of its roots, and a multi-output fusion that computes the genotype hash
    and also emits the per-row ``count`` reduction would otherwise be
    charged to ``count``."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, collections.Counter] = {}
    computation: collections.Counter = collections.Counter()
    for line in hlo_text.splitlines():
        text = line.strip()
        if " = " not in text:
            if text.endswith("{"):
                words = text.split()
                name = words[1 if words[0] == "ENTRY" else 0].lstrip("%")
                computation = members.setdefault(name, collections.Counter())
            continue
        lhs = text.split(" = ", 1)[0]
        name = lhs.removeprefix("ROOT ").lstrip("%")
        found = _OP_NAME.search(text)
        scope = _innermost_scope(found.group(1)) if found else None
        own[name] = scope
        if scope is not None:
            computation[scope] += 1
        called = _CALLS.search(text)
        if called:
            calls[name] = called.group(1)
    scopes: Dict[str, str] = {}
    for name, scope in own.items():
        counts = members.get(calls.get(name, ""))
        if counts:
            most = max(counts.values())
            if counts.get(scope) != most:
                scope = next(s for s in UPDATE_SCOPES if counts.get(s) == most)
        if scope is not None:
            scopes[name] = scope
    return scopes


@functools.lru_cache(maxsize=8)
def _compiled_text(update, shapes) -> str:
    with jax.enable_x64(True):
        return update.lower(*shapes).compile().as_text()


def update_op_scopes() -> Dict[str, Dict[str, str]]:
    """``{XLA module name: {HLO instruction name: scope}}`` for the update
    programs this process has dispatched (``jit_devicegen_update``,
    ``jit_devicegen_update_tail``, ``jit_devicegen_ring_update``).

    The profiler's device-op events carry the instruction's name (``%fusion.3
    = ...``) but neither its scope nor its metadata, so a trace reader maps
    them through this. Each program is lowered and compiled again from the
    abstract shapes of its first dispatch (a compile-cache hit where the
    persistent cache is on): call it after the measured window, never on the
    hot path."""
    return {
        module: hlo_op_scopes(_compiled_text(update, shapes))
        for module, (update, shapes) in list(_DISPATCHED.items())
    }


@functools.lru_cache(maxsize=32)
def _fused_update(
    vs_keys: Tuple[int, ...],
    pops_bytes: bytes,
    site_key: int,
    spacing: int,
    ref_block_fraction: float,
    min_af_micro: Optional[int],
    block_size: int,
    blocks_per_dispatch: int,
    operand_name: str,
    accum_name: str,
    n_pops: int,
    set_sizes: Optional[Tuple[int, ...]] = None,
    tail: bool = False,
):
    """Build (and memoize) the scanned generate→accumulate program for one
    static configuration. Memoizing at module level means every accumulator
    with the same configuration — e.g. a warmup instance and a measured
    instance — shares one traced/compiled program instead of re-tracing per
    instance.

    ``n_pops`` is the SOURCE's population count, passed explicitly rather
    than inferred as ``pops.max()+1``: for a cohort smaller than the
    population count the device must still compute every population's
    threshold stream to stay bit-identical with the host path by
    construction, not by accident.

    ``set_sizes`` carries per-variant-set cohort sizes for asymmetric
    joint-cohort configurations (``pops_bytes`` is then the concatenation of
    each set's population vector); ``None`` means every set shares the one
    cohort ``pops_bytes`` describes.

    The program is named ``devicegen_update`` (``devicegen_update_tail``
    with ``tail``), so its XLA module is ``jit_devicegen_update[_tail]``,
    and its scan body carries the :data:`UPDATE_SCOPES` named scopes."""
    operand_dtype = np.dtype(operand_name)
    accum_dtype = np.dtype(accum_name)
    K, B = blocks_per_dispatch, block_size
    column_splits = (
        [int(x) for x in np.cumsum(set_sizes)[:-1]]
        if set_sizes is not None
        else None
    )

    with jax.enable_x64(True):
        vs_keys_arr = jnp.asarray(
            np.array([k & _MASK64 for k in vs_keys], dtype=np.uint64)
        )
        pops_np = np.frombuffer(pops_bytes, dtype=np.int32)
        pops_arr = jnp.asarray(pops_np)
        site_key_arr = _c64(site_key)
        segmented = any(_set_segments(pops_np, len(vs_keys), set_sizes))

        def count_rows(rows_count, rows):
            """Add each set's rows with variation in any of its columns."""
            with jax.named_scope("count"):
                if column_splits is None:
                    per_set_any = jnp.any(
                        rows.reshape(rows.shape[0], rows_count.shape[0], -1),
                        axis=2,
                    )
                else:
                    per_set_any = jnp.stack(
                        [
                            jnp.any(part, axis=1)
                            for part in jnp.split(rows, column_splits, axis=1)
                        ],
                        axis=1,
                    )
                return rows_count + jnp.sum(per_set_any, axis=0).astype(
                    rows_count.dtype
                )

        def scan_update(G, rows_count, kept_count, grid_offset, n_valid):
            block_idx = jnp.arange(K * B, dtype=jnp.int64).reshape(K, B)

            def body(carry, idx):
                G, rows_count, kept_count = carry
                with jax.named_scope("generate"):
                    index = grid_offset + idx  # (B,) grid indices
                    positions = index * spacing
                    valid = idx < n_valid
                    T = site_thresholds_on_device(
                        site_key_arr,
                        positions,
                        valid,
                        n_pops,
                        ref_block_fraction,
                        min_af_micro,
                    )
                with jax.named_scope("count"):
                    kept_count += jnp.sum(jnp.any(T > 0, axis=1)).astype(
                        kept_count.dtype
                    )
                with jax.named_scope("generate"):
                    hv = generate_has_variation(
                        positions, T, vs_keys_arr, pops_arr, set_sizes
                    )
                if not segmented:
                    rows_count = count_rows(rows_count, hv)
                with jax.named_scope("generate"):
                    # The barrier forces X to MATERIALIZE once: without it
                    # XLA fuses the whole u32 generation chain into the
                    # dot's operand producers and recomputes it per output
                    # tile — measured 4.43 s → 3.14 s whole-genome, 8.85 s
                    # → 5.46 s large-cohort on v5e (it must sit on the int8
                    # cast; a barrier on the bool lets the cast re-fuse and
                    # drag generation with it). With contiguous population
                    # segments the hash, the per-column threshold select and
                    # the cast are one fusion that writes X in its final
                    # layout: joining per-segment results instead cost a
                    # standalone copy and cast (475 + 78 ms per whole-genome
                    # job at 2,504 columns, PERF.md).
                    X = lax.optimization_barrier(hv.astype(operand_dtype))
                if segmented:
                    # Read back from X: counting from hv would make a
                    # second fusion that recomputes the hash (the gathered
                    # path's hash fusion emits the count beside its rows).
                    rows_count = count_rows(rows_count, X)
                with jax.named_scope("int8_dot"):
                    G = G + jnp.einsum(
                        "bn,bm->nm", X, X, preferred_element_type=accum_dtype
                    )
                return (G, rows_count, kept_count), None

            (G, rows_count, kept_count), _ = lax.scan(
                body, (G, rows_count, kept_count), block_idx
            )
            return G, rows_count, kept_count

        if tail:

            @jax.jit
            def devicegen_update_tail(G, rows_count, kept_count, grid_offset, n_valid):  # graftcheck: disable=GC005 -- G is not donated, as in devicegen_update below
                return scan_update(G, rows_count, kept_count, grid_offset, n_valid)

            return devicegen_update_tail

        @jax.jit
        def devicegen_update(G, rows_count, kept_count, grid_offset, n_valid):  # graftcheck: disable=GC005 -- G is not donated, same policy as ops/gramian.py:_dense_update; G here is the scan carry, and each queued dispatch holds its own output G
            return scan_update(G, rows_count, kept_count, grid_offset, n_valid)

        return devicegen_update


@functools.lru_cache(maxsize=32)
def _fused_update_mesh(
    vs_keys: Tuple[int, ...],
    pops_bytes: bytes,
    site_key: int,
    spacing: int,
    ref_block_fraction: float,
    min_af_micro: Optional[int],
    block_size: int,
    blocks_per_dispatch: int,
    operand_name: str,
    accum_name: str,
    n_pops: int,
    set_sizes: Optional[Tuple[int, ...]],
    mesh,
    tail: bool = False,
):
    """The data-parallel (shard_map) wrapper of :func:`_fused_update`,
    memoized on (config, mesh) so warmup and measured accumulators share one
    traced/compiled program, like the single-slice path; named like it."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from spark_examples_tpu.parallel.mesh import DATA_AXIS

    update = _fused_update(
        vs_keys,
        pops_bytes,
        site_key,
        spacing,
        ref_block_fraction,
        min_af_micro,
        block_size,
        blocks_per_dispatch,
        operand_name,
        accum_name,
        n_pops,
        set_sizes,
        tail,
    )
    g_spec = P(DATA_AXIS, None, None)
    r_spec = P(DATA_AXIS, None)
    s_spec = P(DATA_AXIS)

    def per_slice(g, r, k, o, v):
        g1, r1, k1 = update(g[0], r[0], k[0], o[0], v[0])
        return g1[None], r1[None], k1[None]

    per_slice.__name__ = per_slice.__qualname__ = update.__name__
    return jax.jit(
        shard_map(
            per_slice,
            mesh=mesh,
            in_specs=(g_spec, r_spec, s_spec, s_spec, s_spec),
            out_specs=(g_spec, r_spec, s_spec),
        )
    )


class _GridDispatchAccumulator:
    """Shared dispatch machinery for the device-generation accumulators:
    validated (grid_offset, n_valid) group dispatch, data-axis round-robin,
    and the bound on queued dispatches. Subclasses provide ``_update``
    with signature ``(G, variant_rows, kept_sites, offsets, valids)`` plus
    the ``data_parallel`` / ``sites_per_dispatch`` / ``_scalar_sharding``
    attributes."""

    #: dispatched site-grid CAPACITY (summed over data slices — every slice
    #: executes the full scan, padding included) vs the VALID sites inside
    #: it. Their gap is the dispatch padding waste (the benchmark's
    #: ``dispatch_padding_share``; at small regions the fixed tail-group
    #: padding dominates wall-clock), and capacity × per-site ring traffic
    #: gives ``ring_bytes_total`` for the ring accumulator.
    sites_capacity = 0
    sites_valid = 0
    #: population segments whose columns the update generates in one pass
    #: against a selected per-column threshold (:func:`_set_segments`,
    #: summed over variant sets); 0 where every set gathers its thresholds.
    pop_segments = 0
    #: host nanoseconds spent handing dispatches to the runtime: operand
    #: uploads and the program call, where the host waits while the
    #: device's queue is full.
    dispatch_ns = 0
    #: bytes of one device's shard of the finished G, set where G is made.
    gramian_bytes_per_device = 0
    #: bytes of one device's accumulator state, of which each queued
    #: dispatch holds its own copy: G's shard, or the half ring's step
    #: tiles (:class:`DeviceGenRingGramianAccumulator`).
    state_bytes_per_device = 0
    #: :func:`dispatch_depth` of this loop, set at its first dispatch.
    depth = None

    @property
    def gramian_copies_max(self) -> int:
        """The most G buffers this loop can have kept live per device so
        far (:func:`gramian_copies_max`)."""
        if self.depth is None:
            return 1
        return gramian_copies_max(self.dispatches, self.depth)

    def _bound_queue(self) -> None:
        """After a dispatch: wait for the one ``depth`` dispatches back, so
        no more than ``depth`` stay queued and the state's live copies stay
        within :func:`gramian_copies_max`. The loop keeps each dispatch's
        ``kept_sites`` output, a scalar per slice that is ready once that
        dispatch has run, never its G."""
        if self.depth is None:
            from spark_examples_tpu.ops.gramian import per_device_memory_bytes

            self.depth = dispatch_depth(
                self.state_bytes_per_device, per_device_memory_bytes()
            )
            self._in_flight = collections.deque()
        self._in_flight.append(self.kept_sites)
        if len(self._in_flight) > self.depth:
            jax.block_until_ready(self._in_flight.popleft())

    def add_ranges(self, grid_offsets: np.ndarray, n_valids: np.ndarray) -> None:
        """Data-parallel dispatch: slice d processes grid indices
        ``[grid_offsets[d], grid_offsets[d] + n_valids[d])`` (``n_valids[d]
        == 0`` means an idle slice this round)."""
        self._dispatch_ranges(
            self._update, self.sites_per_dispatch, grid_offsets, n_valids
        )

    def _dispatch_ranges(self, update, cap, grid_offsets, n_valids) -> None:
        D = self.data_parallel
        grid_offsets = np.asarray(grid_offsets, dtype=np.int64)
        n_valids = np.asarray(n_valids, dtype=np.int64)
        if grid_offsets.shape != (D,) or n_valids.shape != (D,):
            raise ValueError(f"expected ({D},) offsets/valids")
        if n_valids.min(initial=0) < 0 or n_valids.max(initial=0) > cap:
            raise ValueError(f"n_valids must be in [0, {cap}]")
        if (grid_offsets < 0).any():
            # Negative grid indices would wrap to garbage uint64 positions on
            # device and silently corrupt the Gramian.
            raise ValueError("grid_offsets must be non-negative")
        start = time.perf_counter_ns()
        with jax.enable_x64(True):
            args = (
                self.G,
                self.variant_rows,
                self.kept_sites,
                device_put_global(grid_offsets, self._scalar_sharding),
                device_put_global(n_valids, self._scalar_sharding),
            )
            _note_dispatch(update, args)
            self.G, self.variant_rows, self.kept_sites = update(*args)
        self._bound_queue()
        self.dispatch_ns += time.perf_counter_ns() - start
        self.dispatches += 1
        self.sites_capacity += int(cap) * D
        self.sites_valid += int(n_valids.sum())

    #: position of ``blocks_per_dispatch`` in both subclasses' update-key
    #: tuples (``_fused_update`` and ``_ring_update`` share the prefix
    #: ``(..., block_size, blocks_per_dispatch, ...)``).
    _TAIL_KEY_INDEX = 7

    def _compile_update(self, key):
        """Build the update program for a (possibly tail-modified) key;
        subclasses with a tail program override this."""
        return None

    def _tail_spec(self):
        """(tail_update, tail_sites) — a ~K/8-length program for grid
        remainders, or ``(None, 0)`` for accumulators without one (the
        remainder then pads a full group, the pre-tail behavior)."""
        if getattr(self, "_update_key", None) is None:
            return None, 0
        if self._update_tail is None:
            i = self._TAIL_KEY_INDEX
            key = (
                self._update_key[:i]
                + (self._tail_blocks,)
                + self._update_key[i + 1 :]
            )
            self._update_tail = self._compile_update(key)
        return self._update_tail, self.block_size * self._tail_blocks

    def _round_robin(self, update, cap, starts, last_index: int) -> None:
        D = self.data_parallel
        for i in range(0, len(starts), D):
            offsets = np.zeros(D, dtype=np.int64)
            valids = np.zeros(D, dtype=np.int64)
            for d, off in enumerate(starts[i : i + D]):
                offsets[d] = off
                valids[d] = min(cap, last_index - off)
            self._dispatch_ranges(update, cap, offsets, valids)

    def add_grid(self, first_index: int, last_index: int) -> None:
        """Dispatch all groups for a contiguous grid index range
        ``[first_index, last_index)``, round-robining groups over the data
        axis; the remainder after the full groups runs through the tail
        program when the subclass provides one (padding waste bounded by one
        tail group instead of one full group per contig)."""
        step = self.sites_per_dispatch
        total = max(0, last_index - first_index)
        n_main = total // step
        rem_start = first_index + n_main * step
        self._round_robin(
            self._update,
            step,
            [first_index + i * step for i in range(n_main)],
            last_index,
        )
        if rem_start >= last_index:
            return
        tail_update, tail_sites = self._tail_spec()
        if tail_update is None:
            self._round_robin(self._update, step, [rem_start], last_index)
            return
        self._round_robin(
            tail_update,
            tail_sites,
            list(range(rem_start, last_index, tail_sites)),
            last_index,
        )

    def sync(self) -> None:
        """Block until the whole ingest chain has executed: one synchronous
        fetch of a value that depends on every dispatch (``kept_sites``
        threads through the scan carry). The cheap alternative to
        :meth:`ingest_counters` when the counter VALUES aren't needed —
        stage timing stays honest at half the fetch round-trips."""
        from spark_examples_tpu.parallel.mesh import host_value

        with jax.enable_x64(True):
            host_value(self.kept_sites)

    def ingest_counters(self) -> Tuple[np.ndarray, int]:
        """``(per-set variant-row totals, kept-site total)``, synchronously
        fetched — valid in every process of a multi-controller run
        (``host_value`` replicates before fetching). Blocks until the whole
        ingest chain has executed, so calling this at the end of the ingest
        stage also makes the stage's wall-clock honest on asynchronous
        backends (``utils/tracing.py``).

        Both counters ride ONE transfer (``parallel/mesh.py:
        packed_host_fetch``): each synchronous fetch is a host↔device round
        trip, and small regions are dominated by fixed costs."""
        from spark_examples_tpu.parallel.mesh import packed_host_fetch

        rows_shape = tuple(self.variant_rows.shape)
        rows_size = int(np.prod(rows_shape)) if rows_shape else 1
        flat = packed_host_fetch(
            [self.variant_rows, self.kept_sites],
            self.mesh if self._scalar_sharding is not None else None,
        )
        rows = flat[:rows_size].reshape(rows_shape)
        kept = flat[rows_size:]
        return self._reduce_row_counts(rows), int(np.sum(kept))


class DeviceGenGramianAccumulator(_GridDispatchAccumulator):
    """Fully fused on-device ingest+similarity for the synthetic source.

    The host walks the site grid in fixed-size dispatch groups and sends only
    ``(grid_offset, valid_count)`` scalars; the device reconstructs
    positions (``index · spacing``), recomputes site metadata, generates
    genotypes, and accumulates. Carries the Gramian, a kept-site counter,
    and per-set variant-row counters through chained scanned dispatches;
    nothing is fetched until finalize. ``exact_int`` accumulates
    int8×int8→int32 on the MXU (always exact; whole-genome diagonal counts
    ~12M would sit uncomfortably close to f32's 2^24 integer limit — SURVEY
    §7 hard-part 3).
    """

    def __init__(
        self,
        num_samples: int,
        vs_keys: Sequence[int],
        pops: np.ndarray,
        site_key: int,
        spacing: int,
        ref_block_fraction: float,
        min_af_micro: Optional[int] = None,
        block_size: int = 2048,
        blocks_per_dispatch: int = 32,
        exact_int: bool = True,
        mesh=None,
        n_pops: Optional[int] = None,
        set_sizes: Optional[Sequence[int]] = None,
        pops_per_set: Optional[Sequence[np.ndarray]] = None,
    ):
        from spark_examples_tpu.ops.gramian import _operand_dtypes
        from spark_examples_tpu.parallel.mesh import DATA_AXIS

        self.num_samples = int(num_samples)
        self.n_sets = len(vs_keys)
        # Asymmetric joint cohorts (the 1KG × Platinum scenario): per-set
        # sizes with per-set population vectors; symmetric configurations
        # share the one (num_samples,) cohort.
        if set_sizes is not None:
            self.set_sizes: Optional[Tuple[int, ...]] = tuple(
                int(s) for s in set_sizes
            )
            if len(self.set_sizes) != self.n_sets:
                raise ValueError(
                    f"set_sizes has {len(self.set_sizes)} entries for "
                    f"{self.n_sets} variant sets"
                )
            if pops_per_set is None or len(pops_per_set) != self.n_sets:
                raise ValueError("set_sizes needs matching pops_per_set")
            if any(
                len(p) != s for p, s in zip(pops_per_set, self.set_sizes)
            ):
                raise ValueError("pops_per_set lengths must match set_sizes")
            pops = np.concatenate(
                [np.asarray(p, dtype=np.int32) for p in pops_per_set]
            )
            self.total_columns = sum(self.set_sizes)
        else:
            self.set_sizes = None
            self.total_columns = self.num_samples * self.n_sets
        self.block_size = int(block_size)
        self.blocks_per_dispatch = int(blocks_per_dispatch)
        self.sites_per_dispatch = self.block_size * self.blocks_per_dispatch
        self.spacing = int(spacing)
        self.mesh = mesh
        self.data_parallel = (
            mesh.shape.get(DATA_AXIS, 1) if mesh is not None else 1
        )
        # Shared dtype policy: int8→int32 when exact, bf16 on TPU / f32 on
        # CPU otherwise (the CPU thunk runtime lacks some bf16 dot shapes).
        operand_dtype, accum_dtype = _operand_dtypes(exact_int, mesh)
        self.accum_dtype = accum_dtype
        self.dispatches = 0

        pops32 = np.asarray(pops, dtype=np.int32)
        self.pop_segments = sum(
            len(segments)
            for segments in _set_segments(pops32, self.n_sets, self.set_sizes)
            if segments
        )
        update_key = (
            tuple(int(k) for k in vs_keys),
            pops32.tobytes(),
            int(site_key),
            self.spacing,
            float(ref_block_fraction),
            min_af_micro,
            self.block_size,
            self.blocks_per_dispatch,
            np.dtype(operand_dtype).name,
            np.dtype(accum_dtype).name,
            # Source-authoritative population count (falls back to inference
            # for callers that predate the parameter).
            int(n_pops) if n_pops is not None else int(pops32.max()) + 1,
            self.set_sizes,
        )

        D = self.data_parallel
        with jax.enable_x64(True):
            if D == 1:
                self.G = jnp.zeros(
                    (self.total_columns, self.total_columns), accum_dtype
                )
                # Per-set counts of rows with variation in that set's columns
                # — matches the wire path's per-dataset record accounting.
                self.variant_rows = jnp.zeros((self.n_sets,), jnp.int64)
                self.kept_sites = jnp.zeros((), jnp.int64)
                self._update = _fused_update(*update_key)
                self._scalar_sharding = None
            else:
                # Data-parallel ingest: each data slice generates and
                # accumulates a DIFFERENT span of the site grid (its own
                # (grid_offset, n_valid) pair) into its own replica of G —
                # the Spark-executor analog; finalize is the one psum.
                from jax.sharding import NamedSharding, PartitionSpec as P

                g_spec = P(DATA_AXIS, None, None)
                r_spec = P(DATA_AXIS, None)
                s_spec = P(DATA_AXIS)
                self._scalar_sharding = NamedSharding(mesh, s_spec)
                self.G = device_put_global(
                    np.zeros(
                        (D, self.total_columns, self.total_columns),
                        np.dtype(accum_dtype),
                    ),
                    NamedSharding(mesh, g_spec),
                )
                self.variant_rows = device_put_global(
                    np.zeros((D, self.n_sets), np.int64),
                    NamedSharding(mesh, r_spec),
                )
                self.kept_sites = device_put_global(
                    np.zeros((D,), np.int64), NamedSharding(mesh, s_spec)
                )
                self._update = _fused_update_mesh(*update_key, mesh)
        self.gramian_bytes_per_device = _shard_bytes(self.G)
        self.state_bytes_per_device = self.gramian_bytes_per_device
        # Tail program: a ~K/8-length variant of the same scanned update for
        # contig remainders. Large dispatch groups amortize per-dispatch
        # overhead, but a whole-genome run has 22 contig tails — padding
        # each to the full group would waste up to (group-1) sites of
        # compute per contig (>50% at the tuned 16K×32 group size). Built
        # lazily: only runs that produce remainders pay its compile.
        self._update_key = update_key
        self._tail_blocks = max(1, self.blocks_per_dispatch // 8)
        self._update_tail = None

    def _compile_update(self, key):
        # Only the tail program is built through here.
        return (
            _fused_update_mesh(*key, self.mesh, tail=True)
            if self.data_parallel > 1
            else _fused_update(*key, tail=True)
        )

    def _reduce_row_counts(self, rows: np.ndarray) -> np.ndarray:
        """(n_sets,) per-set totals: data-parallel slices each hold partial
        per-set counts (disjoint grid spans) that sum elementwise."""
        return rows.sum(axis=0) if rows.ndim > 1 else rows

    def add_range(self, grid_offset: int, n_valid: int) -> None:
        """Dispatch one group covering grid indices
        ``[grid_offset, grid_offset + n_valid)`` (positions ``index ·
        spacing``); indices past ``n_valid`` are padding. Single-slice form;
        data-parallel accumulators use :meth:`add_ranges`."""
        if not 0 < n_valid <= self.sites_per_dispatch:
            raise ValueError(
                f"n_valid must be in (0, {self.sites_per_dispatch}], got {n_valid}"
            )
        if grid_offset < 0:
            raise ValueError("grid_offset must be non-negative")
        if self.data_parallel > 1:
            offsets = np.zeros(self.data_parallel, dtype=np.int64)
            valids = np.zeros(self.data_parallel, dtype=np.int64)
            offsets[0], valids[0] = grid_offset, n_valid
            self.add_ranges(offsets, valids)
            return
        self._dispatch_single(self._update, grid_offset, n_valid)

    def _dispatch_single(
        self, update, grid_offset: int, n_valid: int, cap: Optional[int] = None
    ) -> None:
        start = time.perf_counter_ns()
        with jax.enable_x64(True):
            args = (
                self.G,
                self.variant_rows,
                self.kept_sites,
                jnp.asarray(np.int64(grid_offset)),
                jnp.asarray(np.int64(n_valid)),
            )
            _note_dispatch(update, args)
            self.G, self.variant_rows, self.kept_sites = update(*args)
        self._bound_queue()
        self.dispatch_ns += time.perf_counter_ns() - start
        self.dispatches += 1
        self.sites_capacity += int(
            self.sites_per_dispatch if cap is None else cap
        )
        self.sites_valid += int(n_valid)

    def add_grid(self, first_index: int, last_index: int) -> None:
        """Single-slice fast path keeps scalar dispatches; data-parallel
        instances use the shared round-robin (both with the tail program
        for remainders, bounding padding waste per contig to under one tail
        group)."""
        if self.data_parallel > 1:
            super().add_grid(first_index, last_index)
            return
        main = self.sites_per_dispatch
        off = first_index
        while last_index - off >= main:
            self.add_range(off, main)
            off += main
        if off < last_index:
            tail_update, tail = self._tail_spec()
            while off < last_index:
                self._dispatch_single(
                    tail_update, off, min(tail, last_index - off), cap=tail
                )
                off += tail

    def finalize_device(self) -> jax.Array:
        """The accumulated Gramian, still on device; for data-parallel
        accumulators this is the one cross-slice reduce (the Spark
        ``reduceByKey`` shuffle become a single ``psum`` over ICI,
        ``VariantsPca.scala:230``)."""
        from spark_examples_tpu.ops.gramian import data_axis_sum

        if self.data_parallel > 1:
            if not self.G.is_fully_addressable:
                # Multi-controller: replicate so every process can fetch.
                # The result spans other processes' devices (so it is fully
                # *replicated*, not fully *addressable*); host_value
                # short-circuits on is_fully_replicated, so downstream
                # fetches read the local replica without a second gather.
                from jax.sharding import NamedSharding, PartitionSpec

                return data_axis_sum(
                    self.G,
                    out_shardings=NamedSharding(self.mesh, PartitionSpec()),
                )
            return data_axis_sum(self.G)
        return self.G

    def finalize(self) -> np.ndarray:
        from spark_examples_tpu.parallel.mesh import host_value

        with jax.enable_x64(True):
            return host_value(self.finalize_device()).astype(np.float64)


@functools.lru_cache(maxsize=32)
def _ring_update(
    vs_keys: Tuple[int, ...],
    pops_bytes: bytes,
    site_key: int,
    spacing: int,
    ref_block_fraction: float,
    min_af_micro: Optional[int],
    block_size: int,
    blocks_per_dispatch: int,
    operand_name: str,
    num_samples: int,
    padded: int,
    n_pops: int,
    mesh,
    set_sizes: Optional[Tuple[int, ...]] = None,
    pack: bool = False,
    tail: bool = False,
):
    """Memoized scanned generate→ring-accumulate program for one static
    configuration (warmup and measured accumulators share one compiled
    program, like :func:`_fused_update`). Signature of the returned jit:
    ``(G, variant_rows, kept_sites, offsets, valids)``, where G is the
    state of :class:`DeviceGenRingGramianAccumulator`: on a flat mesh the
    half ring's S step tiles (``ops/gramian.py:_half_ring_tiles``), a
    tuple of ``(data, padded, n_local)`` arrays; on a hierarchical mesh
    the ``(data, padded, padded)`` row-tiled G. G is not donated, so every
    queued dispatch holds its own output state; the dispatch
    loop bounds how many are queued (:func:`dispatch_depth`). ``n_pops`` is the
    source's population count (see :func:`_fused_update`). ``set_sizes``
    makes the column space a multi-set concatenation
    (:func:`generate_column_block`); ``variant_rows`` is then per set —
    a row counts for set s when ANY of set s's columns vary. ``pack``
    selects the bit-packed ring wire format: generated columns are packed
    on device (8 genotypes/byte) before the first ``ppermute``, so the ring
    moves ⅛ the ICI bytes; requires ``padded`` to satisfy the pack-width
    invariant (local width a multiple of 8 —
    ``parallel/mesh.py:padded_cohort``). Passing a hierarchical
    ``data x hosts x samples`` mesh selects the two-level reduction
    schedule (``ops/gramian.py:_hier_ring_tiles``): generation is
    schedule-independent (each device still generates its flat column
    slot) and only the tile circulation changes, so flat and hier runs are
    byte-identical (CI-asserted). The program's XLA module is
    ``jit_devicegen_ring_update`` (``jit_devicegen_ring_update_tail`` with
    ``tail``); its scan body carries the :data:`UPDATE_SCOPES`."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_examples_tpu.ops.gramian import (
        _hier_ring_tiles,
        _pack_bits_device,
        _ring_tiles,
    )
    from spark_examples_tpu.parallel.mesh import (
        DATA_AXIS,
        HOST_AXIS,
        SAMPLES_AXIS,
        half_ring_steps,
    )

    operand_dtype = np.dtype(operand_name)
    pops_padded = np.frombuffer(pops_bytes, dtype=np.int32)
    # A hierarchical (data x hosts x samples) mesh selects the two-level
    # schedule: the host-major factorization IS the schedule choice
    # (parallel/mesh.py:hierarchical_mesh), exactly as in
    # ops/gramian.py:build_hierarchical_update — no extra flag, and the
    # memo key stays this same positional tuple.
    hier = HOST_AXIS in mesh.shape
    hier_hosts = mesh.shape[HOST_AXIS] if hier else 1
    inner_devices = mesh.shape[SAMPLES_AXIS]
    n_local = padded // (hier_hosts * inner_devices)
    K, B = blocks_per_dispatch, block_size
    data_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
    sample_axes = (HOST_AXIS, SAMPLES_AXIS) if hier else SAMPLES_AXIS
    g_spec = P(data_axis, sample_axes, None)
    s_spec = P(data_axis)
    r_spec = P(data_axis, None)
    n_sets = len(vs_keys)
    set_bounds = (
        np.concatenate([[0], np.cumsum(set_sizes)])
        if set_sizes is not None
        else np.array([0, num_samples])
    )

    with jax.enable_x64(True):
        vs_keys_arr = jnp.asarray(
            np.array([k & _MASK64 for k in vs_keys], dtype=np.uint64)
        )
        site_key_arr = _c64(site_key)
        pops_all = jnp.asarray(pops_padded)

        def devicegen_ring_update(g, rows, kept, offset, n_valid):
            # g: the S step tiles, each (1, n_local, n_local), or under hier
            # the (1, n_local, padded) row tile; offset/n_valid/kept: (1,);
            # rows: (1, n_sets)
            s_idx = jax.lax.axis_index(SAMPLES_AXIS)
            if hier:
                # Flat slot of this device in the host-major factorization:
                # it owns the same column tile the flat ring would give it
                # (hierarchical_mesh reshapes without reordering devices),
                # so generation is schedule-independent by construction.
                s_idx = jax.lax.axis_index(HOST_AXIS) * inner_devices + s_idx
            col_start = (s_idx * n_local).astype(jnp.int64)
            cols = col_start + jnp.arange(n_local, dtype=jnp.int64)
            pops_local = jax.lax.dynamic_slice(
                pops_all, (s_idx * n_local,), (n_local,)
            )
            block_idx = jnp.arange(K * B, dtype=jnp.int64).reshape(K, B)

            def body(carry, idx):
                g_l, rows_l, kept_l = carry
                with jax.named_scope("generate"):
                    positions = (offset[0] + idx) * spacing
                    valid = idx < n_valid[0]
                    T = site_thresholds_on_device(
                        site_key_arr,
                        positions,
                        valid,
                        n_pops,
                        ref_block_fraction,
                        min_af_micro,
                    )
                with jax.named_scope("count"):
                    kept_l += jnp.sum(jnp.any(T > 0, axis=1)).astype(
                        kept_l.dtype
                    )
                with jax.named_scope("generate"):
                    hv = generate_column_block(
                        positions,
                        T,
                        vs_keys_arr if set_sizes is not None else vs_keys_arr[0],
                        pops_local,
                        col_start,
                        num_samples,
                        set_sizes,
                    )
                with jax.named_scope("count"):
                    # A row "has variation" for set s if ANY of set s's
                    # columns do, across every slice (matches the dense
                    # accumulator's per-set accounting).
                    # range: bool any() → {0,1} per row, exact in int32.
                    per_set_local = jnp.stack(
                        [
                            jnp.any(
                                hv
                                & (
                                    (cols >= int(set_bounds[s]))
                                    & (cols < int(set_bounds[s + 1]))
                                )[None, :],
                                axis=1,
                            ).astype(jnp.int32)
                            for s in range(n_sets)
                        ],
                        axis=1,
                    )  # (B, n_sets)
                    total_any = jax.lax.psum(per_set_local, sample_axes)
                    rows_l += jnp.sum(total_any > 0, axis=0).astype(rows_l.dtype)
                with jax.named_scope("generate"):
                    # Same materialization barrier as the dense update: the
                    # ring exchange dots the local column block against
                    # every rotated tile, so a fused generation chain would
                    # recompute per tile AND per ring step. Under the packed
                    # wire format the barrier sits on the PACKED tile — the
                    # ⅛-size buffer is what the ring circulates, and packing
                    # right after generation keeps the u32 chain
                    # materialized exactly once.
                    if pack:
                        # range: hv is {0,1} (ops/contracts.py:HAS_VARIATION)
                        # — exact in uint8 for the bit pack.
                        x_cols = jax.lax.optimization_barrier(
                            _pack_bits_device(hv.astype(jnp.uint8))
                        )
                    else:
                        x_cols = jax.lax.optimization_barrier(
                            hv.astype(operand_dtype)
                        )
                # The tile ppermutes inside are scoped ``ring_exchange``.
                with jax.named_scope("int8_dot"):
                    if hier:
                        g_l = _hier_ring_tiles(
                            g_l, x_cols, HOST_AXIS, SAMPLES_AXIS,
                            operand_dtype, packed=pack,
                        )
                    else:
                        # The half ring's tiles, stacked by rows for the one
                        # ring entry point and split again: the compiler
                        # folds both away, so each tile stays its own
                        # buffer and each dot adds straight into it.
                        g_l = tuple(
                            jnp.split(
                                _ring_tiles(
                                    jnp.concatenate(g_l), x_cols, SAMPLES_AXIS,
                                    operand_dtype, packed=pack,
                                ),
                                len(g_l),
                            )
                        )
                return (g_l, rows_l, kept_l), None

            g_in = g[0] if hier else tuple(t[0] for t in g)
            (g_l, rows_l, kept_l), _ = jax.lax.scan(
                body, (g_in, rows[0], kept[0]), block_idx
            )
            g_out = g_l[None] if hier else tuple(t[None] for t in g_l)
            return g_out, rows_l[None], kept_l[None]

        if tail:
            devicegen_ring_update.__name__ = "devicegen_ring_update_tail"
            devicegen_ring_update.__qualname__ = "devicegen_ring_update_tail"
        # Each step tile shards its rows like the row tile: device i holds
        # its own (n_local, n_local) block of every tile.
        state_spec = g_spec if hier else (g_spec,) * half_ring_steps(inner_devices)
        # The outputs keep the shardings the accumulator made its zeros
        # with: left to the compiler, a unit data axis comes back as
        # ``P()``, and the next dispatch would lower the program again.
        out_shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            (state_spec, r_spec, s_spec),
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.jit(  # graftcheck: disable=GC005 -- G is not donated: a caller may hold an earlier G, which donation would delete; the dispatch loop bounds the queued copies instead (dispatch_depth); graftcheck ir cross-checks this disable against the traced donated_invars (GI002)
            shard_map(
                devicegen_ring_update,
                mesh=mesh,
                in_specs=(state_spec, r_spec, s_spec, s_spec, s_spec),
                out_specs=(state_spec, r_spec, s_spec),
                # kept/rows are samples-replicated by construction
                # (identical metadata / psum'd flags on every slice).
                check_vma=False,
            ),
            out_shardings=out_shardings,
        )


@functools.lru_cache(maxsize=8)
def _device_zeros(shape: Tuple[int, ...], dtype_name: str, sharding):
    """A program that writes a zero ``shape`` array straight into its
    shards on the devices of ``sharding``."""

    def devicegen_ring_zeros():
        return jnp.zeros(shape, dtype_name)

    return jax.jit(devicegen_ring_zeros, out_shardings=sharding)


@functools.lru_cache(maxsize=8)
def _single_slice_result(mesh):
    """``(1, P, P) → (P, P)`` row-sharded over ``samples``, written into the
    donated input's buffer (the shapes differ only by the unit data axis)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS

    def devicegen_ring_result(g):
        return g.reshape(g.shape[1:])

    return jax.jit(
        devicegen_ring_result,
        donate_argnums=(0,),
        out_shardings=NamedSharding(mesh, P(SAMPLES_AXIS, None)),
    )


@functools.lru_cache(maxsize=8)
def _half_ring_result(mesh):
    """The half ring's step tiles, each ``(data, P, n_local)`` →
    ``(P, P)`` row-sharded over ``samples``: each summed over the data axis
    (``ops/gramian.py:data_axis_sum``), then each device's row tile
    assembled, the blocks past the half mirrored in from the devices that
    hold their transposes (``ops/gramian.py:assemble_half_ring``). Trace
    it under x64, so that a data axis promotes the sum to int64. The tiles
    are not donated: no output has their shape, so XLA could not reuse
    their buffers; a caller that drops them frees them once this has run."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_examples_tpu.ops.gramian import assemble_half_ring, data_axis_sum
    from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS

    def assemble(*tiles):
        return assemble_half_ring(tiles, SAMPLES_AXIS)

    def devicegen_ring_result(tiles):
        return shard_map(
            assemble,
            mesh=mesh,
            in_specs=(P(SAMPLES_AXIS, None),) * len(tiles),
            out_specs=P(SAMPLES_AXIS, None),
        )(*(data_axis_sum(t) for t in tiles))

    return jax.jit(
        devicegen_ring_result,
        out_shardings=NamedSharding(mesh, P(SAMPLES_AXIS, None)),
    )


class DeviceGenRingGramianAccumulator(_GridDispatchAccumulator):
    """Sharded large-N device ingest: the composition of on-device
    generation with the ring-exchange Gramian.

    Each ``samples``-axis slice generates ONLY its own sample-column block
    of the cohort matrix (``generate_column_block``) and the half ring
    (``ops/gramian.py:_half_ring_tiles``) accumulates the ⌊D/2⌋+1 blocks
    of its row tile that symmetry needs, one step tile each; the finalize
    mirrors the rest in (the two-level schedule keeps the whole row tile
    and the full ring, ``_hier_ring_tiles``) — so for a 50K+
    cohort (the reference's ~20 GB in-memory warning,
    ``VariantsPca.scala:216-217``) no device ever materializes the full
    N×N, no host→device data traffic exists at all, and the optional
    ``data`` axis adds Spark-executor-style grid parallelism on top.

    Multi-set joint cohorts (``set_sizes`` + ``pops_per_set`` + a list
    ``vs_key``) concatenate per-set column blocks exactly like the dense
    accumulator — the join/merge scenario past the dense HBM rule
    (``VariantsPca.scala:155-188``) stays on device instead of falling
    back to host wire ingest.
    """

    def __init__(
        self,
        num_samples: int,
        vs_key,
        pops: np.ndarray,
        site_key: int,
        spacing: int,
        ref_block_fraction: float,
        mesh,
        min_af_micro: Optional[int] = None,
        block_size: int = 1024,
        blocks_per_dispatch: int = 8,
        exact_int: bool = True,
        n_pops: Optional[int] = None,
        set_sizes: Optional[Sequence[int]] = None,
        pops_per_set: Optional[Sequence[np.ndarray]] = None,
        pack_bits: str = "auto",
        reduce_schedule: str = "auto",
        hier_hosts: Optional[int] = None,
    ):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from spark_examples_tpu.ops.gramian import (
            _operand_dtypes,
            resolve_ring_pack,
        )
        from spark_examples_tpu.parallel.mesh import (
            DATA_AXIS,
            SAMPLES_AXIS,
            hierarchical_mesh,
            padded_cohort,
            resolve_hier_hosts,
            resolve_reduce_schedule,
        )

        if SAMPLES_AXIS not in mesh.shape or mesh.shape[SAMPLES_AXIS] < 2:
            raise ValueError("ring device ingest needs a samples axis >= 2")
        self.mesh = mesh
        self.pack = resolve_ring_pack(pack_bits)
        self.num_samples = int(num_samples)
        vs_keys = (
            tuple(int(k) for k in vs_key)
            if isinstance(vs_key, (list, tuple))
            else (int(vs_key),)
        )
        self.n_sets = len(vs_keys)
        if set_sizes is not None:
            self.set_sizes: Optional[Tuple[int, ...]] = tuple(
                int(s) for s in set_sizes
            )
            if len(self.set_sizes) != self.n_sets:
                raise ValueError(
                    f"set_sizes has {len(self.set_sizes)} entries for "
                    f"{self.n_sets} variant sets"
                )
            if pops_per_set is None or len(pops_per_set) != self.n_sets:
                raise ValueError("set_sizes needs matching pops_per_set")
            if any(
                len(p) != s for p, s in zip(pops_per_set, self.set_sizes)
            ):
                raise ValueError("pops_per_set lengths must match set_sizes")
            pops = np.concatenate(
                [np.asarray(p, dtype=np.int32) for p in pops_per_set]
            )
            self.total_columns = sum(self.set_sizes)
        elif self.n_sets > 1:
            # Symmetric multi-set: every set shares the one cohort.
            self.set_sizes = (self.num_samples,) * self.n_sets
            pops = np.concatenate(
                [np.asarray(pops, dtype=np.int32)] * self.n_sets
            )
            self.total_columns = self.num_samples * self.n_sets
        else:
            self.set_sizes = None
            self.total_columns = self.num_samples
        self.samples_parallel = mesh.shape[SAMPLES_AXIS]
        self.data_parallel = mesh.shape.get(DATA_AXIS, 1)
        # --reduce-schedule on the fused generation ring: the SAME
        # resolution rule as the host-fed accumulator
        # (ops/gramian.py:ShardedGramianAccumulator) — auto = hier iff the
        # samples axis spans more than one host, explicit hier with a
        # non-dividing host factor fails loudly. Everything outside the
        # tile circulation — G, generation, finalize — is
        # schedule-independent, so flat and hier are byte-identical.
        resolve_reduce_schedule(reduce_schedule, 1)  # validate the spelling
        try:
            self.hier_hosts = resolve_hier_hosts(
                self.samples_parallel, hier_hosts
            )
        except ValueError:
            if reduce_schedule == "hier":
                raise  # an explicit hier request must not silently degrade
            self.hier_hosts = 1
        self.reduce_schedule = resolve_reduce_schedule(
            reduce_schedule, self.hier_hosts
        )
        self._hier_mesh = (
            hierarchical_mesh(mesh, self.hier_hosts)
            if self.reduce_schedule == "hier"
            else None
        )
        # The flat ring runs the half ring over step tiles; the two-level
        # schedule keeps the row tile and the full ring.
        self.half_ring = self._hier_mesh is None
        # Packed wire format pads the column space to 8× the samples axis
        # (pack-width invariant); pad columns generate all-zero and finalize
        # trims them, exactly like the plain samples-axis padding.
        self.padded = padded_cohort(
            self.total_columns, self.samples_parallel, pack=self.pack
        )
        self.n_local = self.padded // self.samples_parallel
        self.block_size = int(block_size)
        self.blocks_per_dispatch = int(blocks_per_dispatch)
        self.sites_per_dispatch = self.block_size * self.blocks_per_dispatch
        self.spacing = int(spacing)
        self.dispatches = 0
        operand_dtype, accum_dtype = _operand_dtypes(exact_int, mesh)
        self.accum_dtype = accum_dtype

        D = self.data_parallel
        pops_padded = np.zeros(self.padded, dtype=np.int32)
        pops_padded[: self.total_columns] = np.asarray(pops, dtype=np.int32)
        data_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
        g_spec = P(data_axis, SAMPLES_AXIS, None)
        self._scalar_sharding = NamedSharding(mesh, P(data_axis))

        g_sharding = NamedSharding(mesh, g_spec)
        accum_name = np.dtype(accum_dtype).name
        with jax.enable_x64(True):
            # Zeroed on the devices: a host zeros array would be the whole
            # (padded, padded) Gramian, 10 GB at 50,000 samples, sent over
            # PCIe at every job (20.8 s of a 27.5 s job on four v5e chips).
            if self.half_ring:
                zeros = _device_zeros(
                    (D, self.padded, self.n_local), accum_name, g_sharding
                )
                self.G = tuple(zeros() for _ in range(self.ring_dots_per_block))
            else:
                self.G = _device_zeros(
                    (D, self.padded, self.padded), accum_name, g_sharding
                )()
            self.kept_sites = device_put_global(
                np.zeros((D,), np.int64), self._scalar_sharding
            )
            self.variant_rows = device_put_global(
                np.zeros((D, self.n_sets), np.int64),
                NamedSharding(mesh, P(data_axis, None)),
            )
        self.gramian_bytes_per_device = (
            self.n_local * self.padded * np.dtype(accum_dtype).itemsize
        )
        self.state_bytes_per_device = sum(
            _shard_bytes(t) for t in jax.tree.leaves(self.G)
        )
        self._update_key = (
            vs_keys,
            pops_padded.tobytes(),
            int(site_key),
            self.spacing,
            float(ref_block_fraction),
            min_af_micro,
            self.block_size,
            self.blocks_per_dispatch,
            np.dtype(operand_dtype).name,
            self.total_columns,
            self.padded,
            int(n_pops)
            if n_pops is not None
            else int(np.asarray(pops, dtype=np.int32).max()) + 1,
            # The mesh in the memo key selects the schedule: the
            # hierarchical factorization shards the same rows over the same
            # devices in the same order (identical HloSharding), so G and
            # the scalar operands need no reshard at the jit boundary.
            self._hier_mesh if self._hier_mesh is not None else mesh,
            self.set_sizes,
            self.pack,
        )
        self._update = _ring_update(*self._update_key)
        self._tail_blocks = max(1, self.blocks_per_dispatch // 8)
        self._update_tail = None

    def _compile_update(self, key):
        # Only the tail program is built through here.
        return _ring_update(*key, tail=True)

    @property
    def ring_dots_per_block(self) -> int:
        """Int8 dots per block on each device: ⌊D/2⌋+1 on the half ring,
        D on the full ring of the two-level schedule."""
        from spark_examples_tpu.parallel.mesh import half_ring_steps

        if self.half_ring:
            return half_ring_steps(self.samples_parallel)
        return self.samples_parallel

    @property
    def ring_mirrored_tiles(self) -> int:
        """Blocks per device that the finalize takes from another device
        and transposes: D-1-⌊D/2⌋ on the half ring, none on the full."""
        return self.samples_parallel - self.ring_dots_per_block

    @property
    def ring_bytes_total(self) -> int:
        """Total ICI bytes the ring exchanges have moved so far: every
        dispatched site (padding included — padded rows ride the ring too)
        costs one circulation of its row's column tiles, ``ring_permutes``
        steps (``parallel/mesh.py:ring_traffic_bytes``). Deterministic
        host-side arithmetic, published as ``gramian_ring_bytes`` by the
        driver."""
        from spark_examples_tpu.parallel.mesh import (
            ring_permutes,
            ring_traffic_bytes,
        )

        return ring_traffic_bytes(
            self.sites_capacity,
            self.samples_parallel,
            self.n_local,
            self.pack,
            ring_permutes(self.samples_parallel, half=self.half_ring),
        )

    def schedule_block(self) -> dict:
        """The manifest ``schedule`` block for the fused device-generation
        ring: which reduction schedule ran (flat, or the two-level
        hierarchical schedule over the host-major factorization) and its
        provable per-link-class byte split. Unlike the host-fed
        accumulator, this path has no independent per-flush accounting:
        ``ring_bytes_total`` IS the closed-form projection over dispatched
        capacity, so predicted == measured here by construction and the
        pair's drift signal lives on the host-fed side
        (``ShardedGramianAccumulator.schedule_block``)."""
        from spark_examples_tpu.parallel.mesh import (
            hierarchical_traffic_bytes,
        )

        predicted = int(self.ring_bytes_total)
        if self.reduce_schedule == "hier":
            level = hierarchical_traffic_bytes(
                self.sites_capacity,
                self.hier_hosts,
                self.samples_parallel // self.hier_hosts,
                self.n_local,
                self.pack,
            )
            ici, dcn = int(level.ici_bytes), int(level.dcn_bytes)
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
            "predicted_ring_bytes": predicted,
            "measured_ring_bytes": predicted,
            "predicted_ici_bytes": ici,
            "predicted_dcn_bytes": dcn,
        }

    def finalize_sharded(self, donate: bool = False) -> jax.Array:
        """(padded, padded) Gramian, row-sharded over ``samples`` — feeds
        the sharded centering/eigensolve without ever gathering N×N.

        The cross-data-slice sum promotes integer accumulators to int64
        (``ops/gramian.py:data_axis_sum`` — the per-slice int32 accumulators
        are each bounded by their own kept sites, but the total across
        slices is not). The half ring's step tiles are assembled into the
        row tiles here (:func:`_half_ring_result`). With ``donate`` the
        accumulator is spent: it lets go of its state, so the step tiles
        are freed once the result is written, and a single data slice's row
        tile becomes the result in its own buffer; a job never holds its
        Gramian twice on a device."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from spark_examples_tpu.ops.gramian import data_axis_sum
        from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS

        G = self.G
        if donate:
            self.G = None
        if self.half_ring:
            with jax.enable_x64(True):
                return _half_ring_result(self.mesh)(G)
        if donate and G.shape[0] == 1:
            return _single_slice_result(self.mesh)(G)
        return data_axis_sum(
            G, out_shardings=NamedSharding(self.mesh, P(SAMPLES_AXIS, None))
        )

    def _reduce_row_counts(self, rows: np.ndarray) -> np.ndarray:
        """(n_sets,) per-set totals: data-parallel slices hold partial
        per-set counts (disjoint grid spans, already samples-replicated
        inside the shard_map) that sum elementwise."""
        return rows.sum(axis=0) if rows.ndim > 1 else np.asarray([rows.sum()])

    def finalize(self) -> np.ndarray:
        from spark_examples_tpu.parallel.mesh import host_value

        with jax.enable_x64(True):
            full = host_value(self.finalize_sharded())
        return full[: self.total_columns, : self.total_columns].astype(
            np.float64
        )


__all__ = [
    "UPDATE_SCOPES",
    "DeviceGenGramianAccumulator",
    "DeviceGenRingGramianAccumulator",
    "auto_blocks_per_dispatch",
    "generate_column_block",
    "generate_has_variation",
    "hlo_op_scopes",
    "mix64",
    "site_thresholds_on_device",
    "update_op_scopes",
]
