"""Plain reference of a PCoA job, independent of the program.

It imports nothing of ``spark_examples_tpu`` and takes nothing the program
made. From the cohort's published definition (the configuration file: seed,
cohort size, populations, reference-block fraction) and a job's site grid it
regenerates the has-variation genotypes, forms the exact integer Gramian
``G = XᵀX``, double-centres it in float64 and takes the top principal
components with a float64 symmetric eigensolve.

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
2^24 and is added into an int64 host total, so the Gramian is exact.

The control of ``PERF.md`` is the same job one rung down the precision
ladder at every stage: the Gramian carried in bfloat16
(``gramian(..., precision="control")``), the centring in float32, and the
program's own algorithm, subspace iteration, with float8 matmul operands
where the program's float32 matmuls take bfloat16 ones on the TPU
(``control_pcs``).
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, List, Sequence, Tuple

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
    num_samples: int, n_pops: int, ref_block_fraction: float, block: int, carrier: str
):
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

    def run(G, site_key, vs_key, pops, start, n_valid, spacing, weight):
        i = jnp.arange(block, dtype=jnp.int64)
        pos_term = ((start + i) * spacing).astype(u64) * c(_P2)
        is_ref = (stream(site_key, pos_term, _S_REF_BLOCK) >> u64(11)) < c(ref_thresh)
        u_af = stream(site_key, pos_term, _S_AF) >> u64(48)
        af = c(_AF_BASE_Q32) + ((u_af * u_af * c(_AF_SPAN_Q16)) >> u64(16))
        per_pop = []
        for p in range(n_pops):
            u_p = stream(site_key, pos_term, _S_POP_BASE + p) >> u64(48)
            factor = c(_POP_BASE_Q16) + ((u_p * c(_POP_SPAN_Q17)) >> u64(16))
            per_pop.append(jnp.clip((af * factor) >> u64(16), c(_POP_LO_Q32), c(_POP_HI_Q32)))
        thresholds = jnp.stack(per_pop, axis=1)[:, pops].astype(u32)  # (B, N)
        h2 = mix(mix(vs_key ^ pos_term) ^ c(_S_GENOTYPE * _P3))
        sample_term = jnp.arange(num_samples, dtype=jnp.int64).astype(u64) * c(_P4)
        x64 = h2[:, None] ^ sample_term[None, :]
        d1 = fmix32(((x64 >> u64(32)) ^ x64).astype(u32))
        d2 = (d1 * u32(_GOLD32)) ^ u32(_FMIX_C1)
        keep = (i < n_valid) & ~is_ref
        has = keep[:, None] & ((d1 < thresholds) | (d2 < thresholds))
        x = has.astype(jnp.bfloat16)
        xw = (has.astype(jnp.int32) * weight).astype(jnp.bfloat16)
        part = jnp.dot(xw.T, x, preferred_element_type=jnp.float32)
        if carrier == "bfloat16":
            return (G.astype(jnp.float32) + part).astype(jnp.bfloat16)
        return G + part.astype(jnp.int32)

    return jax.jit(run)


def gramian(
    cohort: dict,
    ranges: Sequence[Tuple[int, int]],
    spacing: int,
    precision: str = "exact",
    device=None,
) -> np.ndarray:
    """The job's Gramian over grid-index ``ranges`` (one per contig), as
    float64 on the host: exact integers (int32 on the device, whose totals
    stay far below 2^31), or the control's bfloat16 carrier."""
    import jax
    import jax.numpy as jnp

    n = int(cohort["num_samples"])
    block = block_sites(n)
    carrier = "bfloat16" if precision == "control" else "int32"
    site_key, vs_key = cohort_keys(int(cohort["cohort_seed"]), cohort["variant_set_id"])
    program = _block_program(
        n, int(cohort["n_pops"]), float(cohort["ref_block_fraction"]), block, carrier
    )
    with jax.enable_x64(True), jax.default_device(device or jax.devices()[0]):
        keys = (jnp.asarray(np.uint64(site_key)), jnp.asarray(np.uint64(vs_key)))
        pops = jnp.asarray(populations(n, int(cohort["n_pops"])).astype(np.int32))
        G = jnp.zeros((n, n), dtype=jnp.bfloat16 if carrier == "bfloat16" else jnp.int32)
        for a, b, m in weighted_segments(ranges):
            for start in range(a, b, block):
                G = program(
                    G, *keys, pops, np.int64(start), np.int64(min(block, b - start)),
                    np.int64(spacing), np.int32(m),
                )
        return np.asarray(G.astype(jnp.float32) if carrier == "bfloat16" else G).astype(
            np.float64
        )


# ------------------------------------------------------------ finalize


def center(G: np.ndarray, precision: str = "exact") -> np.ndarray:
    """Gower double centring: v − rowMean − colMean + matrixMean."""
    dtype = np.float32 if precision == "control" else np.float64
    S = G.astype(dtype)
    row = S.mean(axis=1, keepdims=True, dtype=dtype)
    col = S.mean(axis=0, keepdims=True, dtype=dtype)
    return (S - row - col + S.mean(dtype=dtype)).astype(dtype)


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

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 256.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def control_components(B: np.ndarray, num_pc: int, iterations: int = 80, oversample: int = 8):
    """The control's eigensolve: the program's algorithm (subspace iteration
    and Rayleigh-Ritz) one rung below the precision its float32 matmuls run
    at on the TPU by default (bfloat16 operands, float32 sums): every
    matmul operand rounded to float8 e4m3, sums and the QR in float32."""
    import jax
    import jax.numpy as jnp

    n = B.shape[0]
    k = min(num_pc + oversample, n)
    V0 = np.linalg.qr(np.random.default_rng(0).standard_normal((n, k)))[0]

    def mm(a, b):
        return jnp.dot(_round_fp8(a), _round_fp8(b), precision="highest")

    @jax.jit
    def solve(B, V):
        B = (B + B.T) * 0.5

        def body(_, V):
            return jnp.linalg.qr(mm(B, V))[0]

        V = jax.lax.fori_loop(0, iterations, body, V)
        T = mm(V.T, mm(B, V))
        evals, Wk = jnp.linalg.eigh((T + T.T) * 0.5)
        order = jnp.argsort(-jnp.abs(evals))[:num_pc]
        return mm(V, Wk[:, order])

    out = solve(jnp.asarray(B, dtype=jnp.float32), jnp.asarray(V0, dtype=jnp.float32))
    return np.asarray(out).astype(np.float64)


def reference_eigen(cohort: dict, G: np.ndarray) -> tuple:
    """The reference's leading eigenpairs of the centred Gramian: a few
    more than the components asked for, so a cluster at the last one is
    whole."""
    return eigenpairs(center(G), int(cohort["num_pc"]) + 6)


def control_pcs(cohort: dict, G_control: np.ndarray) -> np.ndarray:
    """The control's components from its own (bfloat16-carried) Gramian."""
    return control_components(center(G_control, "control"), int(cohort["num_pc"]))
