"""Plain reference of a PCoA job, independent of the program.

It imports nothing of ``spark_examples_tpu`` and takes nothing the program
made: from the program it reads only the Gramian it checks and the row
ranges of that array's shards. From the cohort's published definition (the
configuration file: seed, cohort size, populations, reference-block
fraction) and a job's site grid it regenerates the has-variation genotypes,
forms the exact integer Gramian ``G = XᵀX`` in row tiles, one per shard of
the checked array and on that shard's device, double-centres it in float64
and takes the top principal components in float64.

The genotype definition is the synthetic cohort's, written out here from its
specification: counter-based splitmix64 site streams give each grid site a
reference-block flag and per-population Q32 allele frequencies; each
(site, sample) pair folds a 64-bit state to 32 bits and finalises it with
murmur3's fmix32, and a sample has variation when either of its two allele
draws falls under its population's threshold. Draws are keyed by position
only, so a grid index that several contigs cover contributes once per
contig: the Gramian is ``Σ_k m(k)·x_k x_kᵀ`` with ``m(k)`` the number of the
job's contigs that hold grid index ``k``.

Exactness: the operands are {0, 1} times a multiplicity of at most a few
dozen, exact in bfloat16; each block's product accumulates in float32 below
2^24 and is added into an int32 tile whose totals stay far below 2^31, so
the Gramian is exact. The comparison runs on the devices and fetches one
scalar per shard. The eigenpairs come from a dense float64 ``eigh`` up to
``DENSE_EIGH_MAX_N`` samples and from Lanczos over the centred Gramian as
an operator above it (``CentredGramian``: exact integer products, float64
centring, no N² float64 array); each pair's residual is held to
``RESIDUAL_BOUND``.

The control of ``PERF.md`` is the same job one rung down the precision
ladder at every stage: the Gramian carried in bfloat16
(``gramian_tiles(..., precision="control")``), the centring in float32, and
the program's own algorithm, subspace iteration, with float8 matmul operands
where the program's float32 matmuls take bfloat16 ones on the TPU
(``control_pcs``), all over the same row tiles.
"""

from __future__ import annotations

import functools
import math
import time
from typing import Iterable, List, NamedTuple, Sequence, Tuple

import numpy as np

_MASK64 = (1 << 64) - 1
_P1 = 0x9E3779B97F4A7C15
_P2 = 0xC2B2AE3D27D4EB4F
_P3 = 0x165667B19E3779F9
_P4 = 0xD6E8FEB86659FD93
_M1 = 0xBF58476D1CE4E5B9
_M2 = 0x94D049BB133111EB
_S_REF_BLOCK = 1
_S_AF = 2
_S_POP_BASE = 3
_S_GENOTYPE = 100
_AF_BASE_Q32 = round(0.01 * 2**32)
_AF_SPAN_Q16 = round(0.49 * 2**16)
_POP_BASE_Q16 = round(0.25 * 2**16)
_POP_SPAN_Q17 = round(1.5 * 2**16)
_POP_LO_Q32 = round(0.002 * 2**32)
_POP_HI_Q32 = round(0.95 * 2**32)
_GOLD32 = 0x9E3779B9
_FMIX_C1 = 0x85EBCA6B
_FMIX_C2 = 0xC2B2AE35

# ------------------------------------------------------------ cohort keys


def _mix_int(x: int) -> int:
    x = (x + _P1) & _MASK64
    x = ((x ^ (x >> 30)) * _M1) & _MASK64
    x = ((x ^ (x >> 27)) * _M2) & _MASK64
    return x ^ (x >> 31)


def _rotl(x: int, r: int) -> int:
    return ((x << r) | (x >> (64 - r))) & _MASK64


def _fmix64(k: int) -> int:
    k ^= k >> 33
    k = (k * 0xFF51AFD7ED558CCD) & _MASK64
    k ^= k >> 33
    k = (k * 0xC4CEB9FE1A85EC53) & _MASK64
    return k ^ (k >> 33)


def murmur3_h1(data: bytes) -> int:
    """The first 64-bit word of MurmurHash3 x64-128 (seed 0)."""
    c1, c2 = 0x87C37B91114253D5, 0x4CF5AD432745937F
    h1 = h2 = 0
    n = len(data) // 16
    for i in range(n):
        k1 = int.from_bytes(data[16 * i : 16 * i + 8], "little")
        k2 = int.from_bytes(data[16 * i + 8 : 16 * i + 16], "little")
        h1 ^= (_rotl((k1 * c1) & _MASK64, 31) * c2) & _MASK64
        h1 = (((_rotl(h1, 27) + h2) & _MASK64) * 5 + 0x52DCE729) & _MASK64
        h2 ^= (_rotl((k2 * c2) & _MASK64, 33) * c1) & _MASK64
        h2 = (((_rotl(h2, 31) + h1) & _MASK64) * 5 + 0x38495AB5) & _MASK64
    tail = data[16 * n :]
    if len(tail) > 8:
        k2 = int.from_bytes(tail[8:], "little")
        h2 ^= (_rotl((k2 * c2) & _MASK64, 33) * c1) & _MASK64
    if tail:
        k1 = int.from_bytes(tail[:8], "little")
        h1 ^= (_rotl((k1 * c1) & _MASK64, 31) * c2) & _MASK64
    h1 ^= len(data)
    h2 ^= len(data)
    h1 = (h1 + h2) & _MASK64
    h2 = (h2 + h1) & _MASK64
    h1, h2 = _fmix64(h1), _fmix64(h2)
    return (h1 + h2) & _MASK64


def cohort_keys(seed: int, variant_set_id: str) -> Tuple[int, int]:
    """(site key, genotype key) of a cohort."""
    seed64 = seed & _MASK64
    site_key = _mix_int(seed64)
    vs_key = _mix_int(seed64 ^ murmur3_h1(variant_set_id.encode("utf-8")))
    return site_key, vs_key


def populations(num_samples: int, n_pops: int) -> np.ndarray:
    """Contiguous population blocks: sample s belongs to s·P // N."""
    return (np.arange(num_samples, dtype=np.int64) * n_pops) // max(1, num_samples)


# ------------------------------------------------------------ site grid


def grid_range(start: int, end: int, spacing: int) -> Tuple[int, int]:
    """Grid indices k with k·spacing in [start, end)."""
    k0 = -(-max(start, 0) // spacing)
    k1 = -(-end // spacing)
    return k0, max(k0, k1)


def weighted_segments(ranges: Iterable[Tuple[int, int]]) -> List[Tuple[int, int, int]]:
    """Disjoint ``(a, b, m)`` segments: grid indices in [a, b) lie in ``m``
    of the given ranges (only segments with m > 0)."""
    edges = sorted({x for r in ranges for x in r})
    ranges = list(ranges)
    out = []
    for a, b in zip(edges, edges[1:]):
        m = sum(1 for k0, k1 in ranges if k0 <= a and b <= k1)
        if m:
            out.append((a, b, m))
    return out


# ------------------------------------------------------------ device blocks


def block_sites(num_samples: int) -> int:
    """Sites per reference block: about 2^26 genotypes, a power of two."""
    b = 1 << max(10, (2**26 // max(1, num_samples)).bit_length() - 1)
    return min(b, 65536)


@functools.lru_cache(maxsize=None)
def _block_program(
    num_samples: int, n_pops: int, ref_block_fraction: float, block: int, rows: int, carrier: str
):
    """``G += xw[:, r0:r0+rows]ᵀ · x`` for one block of grid sites: the
    ``rows`` rows of the Gramian from sample ``r0`` on, over all columns."""
    import jax
    import jax.numpy as jnp

    u64, u32 = jnp.uint64, jnp.uint32

    def c(v):
        return jnp.asarray(np.uint64(v & _MASK64))

    def mix(x):
        x = x + c(_P1)
        x = (x ^ (x >> u64(30))) * c(_M1)
        x = (x ^ (x >> u64(27))) * c(_M2)
        return x ^ (x >> u64(31))

    def stream(key, pos_term, s):
        # splitmix64 chain over (position, stream, sample 0, allele 0).
        return mix(mix(mix(mix(key ^ pos_term) ^ c(s * _P3))))

    def fmix32(x):
        x = (x ^ (x >> u32(16))) * u32(_FMIX_C1)
        x = (x ^ (x >> u32(13))) * u32(_FMIX_C2)
        return x ^ (x >> u32(16))

    ref_thresh = math.ceil(ref_block_fraction * 2.0**53)

    def run(G, site_key, vs_key, pops, start, n_valid, spacing, weight, r0):
        i = jnp.arange(block, dtype=jnp.int64)
        pos_term = ((start + i) * spacing).astype(u64) * c(_P2)
        is_ref = (stream(site_key, pos_term, _S_REF_BLOCK) >> u64(11)) < c(ref_thresh)
        u_af = stream(site_key, pos_term, _S_AF) >> u64(48)
        af = c(_AF_BASE_Q32) + ((u_af * u_af * c(_AF_SPAN_Q16)) >> u64(16))
        # Each sample's population threshold, (B, N): a select over the few
        # populations (the same values as a gather, without one).
        thresholds = None
        for p in range(n_pops):
            u_p = stream(site_key, pos_term, _S_POP_BASE + p) >> u64(48)
            factor = c(_POP_BASE_Q16) + ((u_p * c(_POP_SPAN_Q17)) >> u64(16))
            t_p = jnp.clip((af * factor) >> u64(16), c(_POP_LO_Q32), c(_POP_HI_Q32))
            t_p = t_p.astype(u32)[:, None]
            thresholds = t_p if thresholds is None else jnp.where(pops[None, :] == p, t_p, thresholds)
        h2 = mix(mix(vs_key ^ pos_term) ^ c(_S_GENOTYPE * _P3))
        sample_term = jnp.arange(num_samples, dtype=jnp.int64).astype(u64) * c(_P4)
        x64 = h2[:, None] ^ sample_term[None, :]
        d1 = fmix32(((x64 >> u64(32)) ^ x64).astype(u32))
        d2 = (d1 * u32(_GOLD32)) ^ u32(_FMIX_C1)
        keep = (i < n_valid) & ~is_ref
        has = keep[:, None] & ((d1 < thresholds) | (d2 < thresholds))
        x = has.astype(jnp.bfloat16)
        xw = jax.lax.dynamic_slice_in_dim(has, r0, rows, axis=1)
        xw = (xw.astype(jnp.int32) * weight).astype(jnp.bfloat16)
        # Written once: fused into the dot, the hash would be recomputed for
        # every tile of the product.
        x, xw = jax.lax.optimization_barrier((x, xw))
        part = jnp.dot(xw.T, x, preferred_element_type=jnp.float32)
        if carrier == "bfloat16":
            return (G.astype(jnp.float32) + part).astype(jnp.bfloat16)
        return G + part.astype(jnp.int32)

    return jax.jit(run, donate_argnums=0)


# ------------------------------------------------------------ row tiles


class Tile(NamedTuple):
    """Rows ``r0:r1`` of a job's Gramian, all columns, on ``device``."""

    r0: int
    r1: int
    device: object
    data: object


def row_layout(n: int, devices) -> List[Tuple[int, int, object]]:
    """``n`` rows split into equal ``(r0, r1, device)`` ranges, one per
    device (the last one shorter)."""
    per = -(-n // len(devices))
    return [(r0, min(n, r0 + per), d) for r0, d in zip(range(0, n, per), devices)]


def shard_layout(S, n: int) -> List[Tuple[int, int, object]]:
    """The row ranges of ``S``'s addressable shards that hold rows below
    ``n`` (padding left out), each once, on the first device holding it."""
    layout = {}
    for shard in S.addressable_shards:
        r0, r1, _ = shard.index[0].indices(S.shape[0])
        if min(r1, n) > r0:
            layout.setdefault((r0, min(r1, n)), shard.device)
    return [(r0, r1, d) for (r0, r1), d in sorted(layout.items())]


def gramian_tiles(
    cohort: dict,
    ranges: Sequence[Tuple[int, int]],
    spacing: int,
    layout: Sequence[Tuple[int, int, object]],
    precision: str = "exact",
) -> List[Tile]:
    """The job's Gramian over grid-index ``ranges`` (one per contig), one
    tile of rows per ``(r0, r1, device)`` of ``layout``, each computed on its
    device: exact integers (int32, whose totals stay far below 2^31), or the
    control's bfloat16 carrier. Every tile sees the same blocks in the same
    order, so a tile is exactly those rows of the whole matrix."""
    import jax
    import jax.numpy as jnp

    n = int(cohort["num_samples"])
    block = block_sites(n)
    carrier = "bfloat16" if precision == "control" else "int32"
    dtype = jnp.bfloat16 if carrier == "bfloat16" else jnp.int32
    site_key, vs_key = cohort_keys(int(cohort["cohort_seed"]), cohort["variant_set_id"])
    pops = populations(n, int(cohort["n_pops"])).astype(np.int32)

    def program(rows):
        return _block_program(
            n, int(cohort["n_pops"]), float(cohort["ref_block_fraction"]), block, rows, carrier
        )

    with jax.enable_x64(True):
        consts = {
            d: jax.device_put((np.uint64(site_key), np.uint64(vs_key), pops), d)
            for _, _, d in layout
        }
        tiles = [jnp.zeros((r1 - r0, n), dtype=dtype, device=d) for r0, r1, d in layout]
        for a, b, m in weighted_segments(ranges):
            for start in range(a, b, block):
                for t, (r0, r1, d) in enumerate(layout):
                    tiles[t] = program(r1 - r0)(
                        tiles[t], *consts[d], np.int64(start), np.int64(min(block, b - start)),
                        np.int64(spacing), np.int32(m), np.int64(r0),
                    )
    return [Tile(r0, r1, d, g) for (r0, r1, d), g in zip(layout, tiles)]


def host_gramian(tiles: Sequence[Tile]) -> np.ndarray:
    """The whole matrix in float64 on the host, from its tiles (small
    cohorts only: the dense eigensolve and tests)."""
    parts = [np.asarray(t.data) for t in sorted(tiles, key=lambda t: t.r0)]
    return np.concatenate(parts).astype(np.float64)


@functools.lru_cache(maxsize=None)
def _tile_gap(rows: int, n: int):
    import jax
    import jax.numpy as jnp

    def gap(s, t):
        return jnp.max(jnp.abs(s[:rows, :n].astype(jnp.int64) - t.astype(jnp.int64)))

    return jax.jit(gap)


def max_abs_diff(S, tiles: Sequence[Tile], n: int) -> float:
    """``max |S − G|`` over the ``n`` × ``n`` block, compared tile by tile
    on the devices that hold ``S``'s shards (every replica of a shard), one
    scalar fetched per shard. ``S`` may be padded past ``n``; a smaller one,
    or a shard that holds only part of its rows' columns, reads infinite."""
    import jax

    if S.shape[0] < n or S.shape[1] < n:
        return math.inf
    by_rows = {(t.r0, t.r1): t for t in tiles}
    gaps = []
    with jax.enable_x64(True):
        for shard in S.addressable_shards:
            r0, r1, _ = shard.index[0].indices(S.shape[0])
            c0, c1, _ = shard.index[1].indices(S.shape[1])
            if min(r1, n) <= r0:
                continue
            if c0 != 0 or c1 < n:
                return math.inf
            tile = by_rows[(r0, min(r1, n))]
            data = tile.data if tile.device == shard.device else jax.device_put(tile.data, shard.device)
            gaps.append(_tile_gap(tile.r1 - tile.r0, n)(shard.data, data))
        return float(max(int(g) for g in gaps))


def tiles_max_abs_diff(a: Sequence[Tile], b: Sequence[Tile]) -> float:
    """``max |A − B|`` of two tilings over the same layout (the control's
    Gramian against the exact one), one scalar fetched per tile."""
    import jax

    with jax.enable_x64(True):
        gaps = [_tile_gap(x.r1 - x.r0, x.data.shape[1])(x.data, y.data) for x, y in zip(a, b)]
        return float(max(int(g) for g in gaps))


# ------------------------------------------------------------ finalize


def center(G: np.ndarray) -> np.ndarray:
    """Gower double centring in float64: v − rowMean − colMean + matrixMean."""
    S = G.astype(np.float64)
    row = S.mean(axis=1, keepdims=True)
    col = S.mean(axis=0, keepdims=True)
    return S - row - col + S.mean()


def eigenpairs(B: np.ndarray, count: int) -> tuple:
    """The ``count`` largest eigenpairs of ``B`` in float64, descending. The
    centred Gramian J·XᵀWX·J is positive semi-definite, so these are also
    the largest-|λ| pairs the program's eigensolve looks for."""
    import scipy.linalg

    B = (B.astype(np.float64) + B.T.astype(np.float64)) * 0.5
    n = B.shape[0]
    count = min(count, n)
    vals, vecs = scipy.linalg.eigh(B, subset_by_index=[n - count, n - 1])
    return vals[::-1], vecs[:, ::-1]


#: Consecutive eigenvalues closer than this share of the larger one form
#: one cluster: their eigenvectors are defined only as a subspace.
CLUSTER_REL_GAP = 0.01


def eigenspace_gap(V: np.ndarray, vals: np.ndarray, vecs: np.ndarray) -> float:
    """How far the components ``V`` (N, k) lie from the reference's
    eigenspaces: for component i, the largest coordinate of its part
    outside the span of the reference eigenvectors whose eigenvalues form
    the cluster of λ_i. With well separated eigenvalues this is the
    sign-free difference of each component from its eigenvector; inside a
    cluster of (nearly) equal eigenvalues any rotation is an equally right
    answer and is not counted."""
    k = V.shape[1]
    mags = np.abs(vals)
    gap = 0.0
    for i in range(k):
        lo = i
        while lo > 0 and mags[lo - 1] - mags[lo] < CLUSTER_REL_GAP * mags[lo - 1]:
            lo -= 1
        hi = i + 1
        while hi < len(mags) and mags[hi - 1] - mags[hi] < CLUSTER_REL_GAP * mags[hi - 1]:
            hi += 1
        U = vecs[:, lo:hi]
        v = V[:, i] / np.linalg.norm(V[:, i])
        gap = max(gap, float(np.abs(v - U @ (U.T @ v)).max()))
    return gap


def _round_fp8(x):
    """Round to float8 e4m3 with one scale for the whole array (its largest
    magnitude maps near the format's top), as an fp8 matmul takes it."""
    import jax.numpy as jnp

    return _round_fp8_scaled(x, jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 256.0)


def _round_fp8_scaled(x, scale):
    import jax.numpy as jnp

    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _control_programs():
    """Jitted pieces of the control's eigensolve: the float32 centring of a
    row tile (``G − (r_i + r_j) + m``, symmetric by construction), its
    largest magnitude, its fp8 rounding under the whole matrix's scale, and
    the fp8-operand products."""
    import jax
    import jax.numpy as jnp

    def centred(g, r_rows, r, m):
        return g.astype(jnp.float32) - (r_rows[:, None] + r[None, :]) + m

    def row_means(g):
        return jnp.mean(g.astype(jnp.float32), axis=1)

    def absmax(g, r_rows, r, m):
        return jnp.max(jnp.abs(centred(g, r_rows, r, m)))

    def rounded(g, r_rows, r, m, scale):
        return _round_fp8_scaled(centred(g, r_rows, r, m), scale)

    def rows_times(bq, v):
        return jnp.dot(bq, _round_fp8(v), precision="highest")

    def mm(a, b):
        return jnp.dot(_round_fp8(a), _round_fp8(b), precision="highest")

    def qr(w):
        return jnp.linalg.qr(w)[0]

    return {name: jax.jit(f) for name, f in (
        ("row_means", row_means), ("absmax", absmax), ("rounded", rounded),
        ("rows_times", rows_times), ("mm", mm), ("qr", qr),
    )}


def control_components(tiles: Sequence[Tile], num_pc: int, iterations: int = 80, oversample: int = 8):
    """The control's eigensolve over row tiles of its bfloat16-carried
    Gramian: the centring in float32, then the program's algorithm
    (subspace iteration and Rayleigh-Ritz) one rung below the precision its
    float32 matmuls run at on the TPU by default (bfloat16 operands,
    float32 sums): every matmul operand rounded to float8 e4m3 with one
    scale for the whole array, sums and the QR in float32. Each product
    ``B·V`` is the tiles' row blocks, each on its own device; the QR and
    the small products run on the first tile's device."""
    import jax

    f = _control_programs()
    tiles = sorted(tiles, key=lambda t: t.r0)
    n = tiles[0].data.shape[1]
    home = tiles[0].device
    r = np.concatenate([np.asarray(f["row_means"](t.data)) for t in tiles]).astype(np.float32)
    m = r.mean(dtype=np.float32)
    args = [(t, jax.device_put(r[t.r0 : t.r1], t.device), jax.device_put(r, t.device)) for t in tiles]
    top = max(float(f["absmax"](t.data, rr, ra, m)) for t, rr, ra in args)
    scale = np.float32(max(top, 1e-30) / 256.0)
    Bq = [(t.device, f["rounded"](t.data, rr, ra, m, scale)) for t, rr, ra in args]
    del args

    def times_b(V):
        parts = [f["rows_times"](bq, jax.device_put(V, d)) for d, bq in Bq]
        return jax.device_put(np.concatenate([np.asarray(p) for p in parts]), home)

    k = min(num_pc + oversample, n)
    V0 = np.linalg.qr(np.random.default_rng(0).standard_normal((n, k)))[0]
    V = jax.device_put(V0.astype(np.float32), home)
    for _ in range(iterations):
        V = f["qr"](times_b(V))
    T = np.asarray(f["mm"](V.T, times_b(V)))
    evals, Wk = np.linalg.eigh((T + T.T) * np.float32(0.5))
    order = np.argsort(-np.abs(evals))[:num_pc]
    return np.asarray(f["mm"](V, jax.device_put(Wk[:, order], home))).astype(np.float64)


def control_pcs(cohort: dict, tiles_control: Sequence[Tile]) -> np.ndarray:
    """The control's components from its own (bfloat16-carried) Gramian."""
    return control_components(tiles_control, int(cohort["num_pc"]))


# ------------------------------------------------------------ eigen reference

#: Largest cohort whose reference eigenpairs come from a dense float64
#: eigensolve of the whole centred matrix on the host (both batch cells and
#: the served mix); above it, Lanczos over ``CentredGramian``.
DENSE_EIGH_MAX_N = 4096

#: Every reference eigenpair's residual ``‖Bu − λu‖₂ / λ₁`` is at most this.
RESIDUAL_BOUND = 1e-9

#: ARPACK's stopping tolerance: each Ritz pair's residual under this share
#: of its eigenvalue, far inside ``RESIDUAL_BOUND``.
LANCZOS_TOL = 1e-12

#: Base-128 digits of a vector's fixed-point form: 2^-54 of its largest
#: entry, finer than float64's own rounding.
_VECTOR_DIGITS = 8


@functools.lru_cache(maxsize=None)
def _digit_programs(digits: int, rows: int):
    import jax
    import jax.numpy as jnp

    def planes(g):
        g = jnp.pad(g, ((0, rows - g.shape[0]), (0, 0)))
        return jnp.stack([((g >> (7 * i)) & 127).astype(jnp.int8) for i in range(digits)])

    def times(p, d):
        return jnp.einsum("prn,nc->prc", p, d, preferred_element_type=jnp.int32)

    return jax.jit(planes), jax.jit(times)


class CentredGramian:
    """The Gower-centred exact Gramian ``B = JGJ`` as an operator, applied
    in float64 without forming B: ``Bv = Gv − r(1ᵀv) − 1(rᵀv) + m(1ᵀv)1``,
    with ``r`` the row means (row sums exact in int64), ``m`` the mean, and
    G symmetric, so its column means are ``r`` too.

    ``Gv`` is exact integer arithmetic on the tiles' devices: each tile is
    held as base-128 digit planes in int8 (padded with zero rows to the
    longest tile, one array sharded by rows over the tiles' devices), ``v``
    as a power-of-two scale per column and eight signed base-128 digits of
    its fixed-point form, and each plane by digit product is an int8 matmul
    with exact int32 sums (at most N·127·64 < 2^31), put together on the
    host in float64: one dispatch and one fetch per product."""

    def __init__(self, tiles: Sequence[Tile]):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        tiles = sorted(tiles, key=lambda t: t.r0)
        self.n = n = tiles[0].data.shape[1]
        if [t.r0 for t in tiles] != [0] + [t.r1 for t in tiles[:-1]] or tiles[-1].r1 != n:
            raise ValueError("the tiles do not cover the rows 0..N once each")
        if n * 127 * 64 >= 2**31:
            raise ValueError(f"N = {n} is too large for exact int32 digit sums")
        with jax.enable_x64(True):
            sums = [jnp.sum(t.data, axis=1, dtype=jnp.int64) for t in tiles]
            low = min(int(jnp.min(t.data)) for t in tiles)
            top = max(int(jnp.max(t.data)) for t in tiles)
            row_sums = np.concatenate([np.asarray(s) for s in sums])
        if low < 0:
            raise ValueError("a Gramian of counts has no negative entry")
        self.digits = max(1, -(-top.bit_length() // 7))
        rows = max(t.r1 - t.r0 for t in tiles)
        planes, self._times = _digit_programs(self.digits, rows)
        mesh = Mesh(np.array([t.device for t in tiles]), ("rows",))
        self.planes = jax.make_array_from_single_device_arrays(
            (self.digits, rows * len(tiles), n),
            NamedSharding(mesh, P(None, "rows", None)),
            [planes(t.data) for t in tiles],
        )
        self._replicated = NamedSharding(mesh, P())
        self._valid = np.concatenate([i * rows + np.arange(t.r1 - t.r0) for i, t in enumerate(tiles)])
        self.r = row_sums / n
        self.m = float(int(row_sums.sum())) / n / n
        self.products = 0
        self.device_seconds = 0.0

    def gv(self, V: np.ndarray) -> np.ndarray:
        """``G·V`` for an (N, b) float64 ``V``, to float64 rounding."""
        import jax

        b = V.shape[1]
        # A power of two at or over each column's largest entry, so that
        # scaling rounds nothing.
        scale = np.ldexp(1.0, np.frexp(np.abs(V).max(axis=0))[1])
        Y = np.rint(V / scale * 2.0**54).astype(np.int64)
        D = np.empty((self.n, _VECTOR_DIGITS, b), np.int8)
        for j in range(_VECTOR_DIGITS):
            d = ((Y + 64) & 127) - 64
            D[:, j, :] = d
            Y = (Y - d) >> 7
        start = time.perf_counter()
        D = jax.device_put(D.reshape(self.n, _VECTOR_DIGITS * b), self._replicated)
        R = np.asarray(self._times(self.planes, D))
        self.device_seconds += time.perf_counter() - start
        R = R[:, self._valid].astype(np.float64).reshape(self.digits, self.n, _VECTOR_DIGITS, b)
        weight = 2.0 ** (7 * (np.arange(self.digits)[:, None] + np.arange(_VECTOR_DIGITS)[None, :]))
        self.products += 1
        return np.einsum("prjb,pj->rb", R, weight) * (scale / 2.0**54)

    def matmat(self, V: np.ndarray) -> np.ndarray:
        V = np.asarray(V, dtype=np.float64).reshape(self.n, -1)
        ones_v = V.sum(axis=0)
        return self.gv(V) - np.outer(self.r, ones_v) - (self.r @ V)[None, :] + self.m * ones_v[None, :]

    def matvec(self, v: np.ndarray) -> np.ndarray:
        return self.matmat(v).reshape(np.shape(v))


def lanczos_eigenpairs(op: CentredGramian, count: int) -> tuple:
    """The ``count`` largest eigenpairs of the operator in float64,
    descending: implicitly restarted Lanczos (ARPACK) from a fixed start.
    Its vector operations are small and many, so BLAS runs them on one
    thread rather than waking a pool that shares the host's cores with the
    accelerator runtime at every step."""
    from scipy.sparse.linalg import LinearOperator, eigsh
    from threadpoolctl import threadpool_limits

    n = op.n
    A = LinearOperator((n, n), matvec=op.matvec, matmat=op.matmat, dtype=np.float64)
    with threadpool_limits(limits=1, user_api="blas"):
        vals, vecs = eigsh(
            A, k=count, which="LA", tol=LANCZOS_TOL, ncv=min(n - 1, 4 * count + 8),
            v0=np.random.default_rng(0).standard_normal(n),
        )
    order = np.argsort(vals)[::-1]
    return vals[order], vecs[:, order]


def reference_eigen(cohort: dict, tiles: Sequence[Tile]) -> tuple:
    """The reference's leading eigenpairs of the centred Gramian: a few
    more than the components asked for, so a cluster at the last one is
    whole. Dense float64 ``eigh`` up to ``DENSE_EIGH_MAX_N`` samples,
    Lanczos above; either way each pair's residual is printed and held to
    ``RESIDUAL_BOUND``."""
    from benchmark.core import BenchFailure, say

    count = int(cohort["num_pc"]) + 6
    n = tiles[0].data.shape[1]
    if n <= DENSE_EIGH_MAX_N:
        B = center(host_gramian(tiles))
        vals, vecs = eigenpairs(B, count)
        B = (B + B.T) * 0.5
        apply, method = (lambda V: B @ V), "dense eigh"
    else:
        op = CentredGramian(tiles)
        vals, vecs = lanczos_eigenpairs(op, count)
        apply = op.matmat
        method = f"Lanczos, {op.products} products, {op.device_seconds:.3f} s on the devices and links"
    residuals = np.linalg.norm(apply(vecs) - vecs * vals, axis=0) / abs(vals[0])
    say(f"reference eigenpairs ({method}): residuals ‖Bu − λu‖/λ1 {residuals.tolist()}")
    if not np.all(residuals <= RESIDUAL_BOUND):
        raise BenchFailure(f"reference eigenpairs not converged: residuals {residuals.tolist()}")
    return vals, vecs
