"""Per-site (M-sized) device kernels: windowed LD statistics + association
carrier counts.

The PCA/GRM reduction layer only ever emits per-SAMPLE outputs (the N×N
Gramian). The population-genetics analyses (``analyses/``) add the other
output shape — per-SITE statistics — and this module is their device half.
Both kernels are stateless per dispatch (window in, small statistics out):
there is no device accumulator to donate, no dtype ladder to climb, and
the M-sized result never materializes on device — only the O(W²)/O(B)
window statistics do, which the host consumes immediately (the greedy
prune and the chi-square are inherently host-sequential/scalar work).

**Windowed LD** (:func:`build_ld_window_stats`): for a contig-ordered
window ``X ∈ {0,1}^(W×N)`` of has-variation rows, the pairwise r² between
sites i, j over binary genotypes needs only the co-carrier counts
``C = X Xᵀ`` and the per-site carrier counts ``k`` (for binary x,
``Σx² = Σx``):

    r²_ij = (n·C_ij − k_i·k_j)² / ((n·k_i − k_i²) · (n·k_j − k_j²))

``C`` is one W×W MXU matmul; under a mesh with a ``samples`` axis the
kernel runs blockwise under ``shard_map`` — each device computes the
partial ``C`` over its own sample columns and one ``psum`` over the
``samples`` axis completes it (the per-site analog of the Gramian's
finalize reduce; no ring is needed because the OUTPUT is per-site W×W,
not per-sample N×N). Everything is exact int32 integer arithmetic
(``W·max_count² ≤ N < 2^31``); the r² quotient itself is host float64
(:func:`r2_from_counts`), shared with the NumPy oracle so parity is
exact, not approximate.

**Association counts** (:func:`build_case_counts`): per site, the carrier
count among cases ``a = X @ case`` and the total carrier count
``t = X @ 1`` — the two device-side numbers the allelic 2×2 chi-square
needs; the scalar chi-square arithmetic stays on host in float64
(``analyses/assoc.py:chi2_from_counts``, also oracle-shared).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from spark_examples_tpu.ops.contracts import HAS_VARIATION  # noqa: F401  (the input contract both kernels assume)
from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS


def _window_counts_body(X_local, samples_axis: Optional[str]):
    """Per-device body: partial co-carrier counts over the local sample
    columns, completed by one psum when a samples axis exists."""
    import jax.numpy as jnp
    from jax import lax

    # range: HAS_VARIATION {0,1} membership bits; int8 holds them exactly
    # and the int8×int8→int32 dot is exact for W·N < 2^31 (ops/contracts.py).
    Xc = X_local.astype(jnp.int8)
    C = jnp.matmul(Xc, Xc.T, preferred_element_type=jnp.int32)
    # range: HAS_VARIATION bits sum to at most N < 2^31 per site.
    k = jnp.sum(X_local.astype(jnp.int32), axis=1)
    if samples_axis is not None:
        C = lax.psum(C, samples_axis)
        k = lax.psum(k, samples_axis)
    return C, k


def build_ld_window_stats(mesh=None):
    """The jitted window-statistics kernel for ``mesh`` (or single-device
    when ``None``/no samples axis): ``(W, N) uint8 → (C (W,W) int32,
    k (W,) int32)``. ONE construction site shared by the runtime
    (``analyses/ld.py``) and the device-free plan validator
    (``check/plan.py`` traces it over an ``AbstractMesh``), so the kernel
    the run executes and the kernel the validator proves are the same
    object. Build once per run — the returned callable is jit-cached."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if mesh is None or mesh.shape.get(SAMPLES_AXIS, 1) < 2:

        @jax.jit
        def window_stats(X):
            return _window_counts_body(X, None)

        return window_stats

    # The data axis (when present) carries no per-site work here — one
    # window at a time — so the window replicates over it and only the
    # sample columns shard; the same mesh serves PCA and LD unchanged.
    x_spec = P(None, SAMPLES_AXIS)

    @jax.jit
    def window_stats(X):
        return shard_map(
            lambda x: _window_counts_body(x, SAMPLES_AXIS),
            mesh=mesh,
            in_specs=(x_spec,),
            out_specs=(P(None, None), P(None)),
        )(X)

    return window_stats


def ld_window_stats_reference(rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host NumPy oracle of the window-statistics kernel."""
    X = np.asarray(rows, dtype=np.int64)
    return (X @ X.T).astype(np.int64), X.sum(axis=1).astype(np.int64)


def r2_from_counts(
    C: np.ndarray, k: np.ndarray, num_samples: int
) -> np.ndarray:
    """Pairwise r² from integer window statistics, float64, with the
    zero-variance guard: pairs involving a monomorphic site (variance
    numerator ``k·(n−k) == 0``) get r² = 0 — no correlation evidence,
    never NaN. The numerator/denominator are exact int64 products of the
    device-counted integers, so the oracle and the device path compute
    the IDENTICAL float64 quotient."""
    from spark_examples_tpu.utils.af import variance_counts

    n = int(num_samples)
    C = np.asarray(C, dtype=np.int64)
    k = np.asarray(k, dtype=np.int64)
    cov = n * C - k[:, None] * k[None, :]
    var = variance_counts(k, n)  # k·(n−k), exactly 0 for monomorphic
    denom = (var[:, None] * var[None, :]).astype(np.float64)
    num = cov.astype(np.float64) ** 2
    out = np.zeros_like(num)
    np.divide(num, denom, out=out, where=denom > 0)
    return out


def greedy_prune(
    C: np.ndarray,
    k: np.ndarray,
    num_samples: int,
    r2_threshold: float,
    valid: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Greedy windowed LD prune: walk sites in window (position) order,
    keep site i iff its r² against EVERY previously-kept site in the
    window is <= ``r2_threshold`` (prune strictly above, mirroring the
    ``--min-allele-frequency`` strictly-greater convention). Deterministic
    by construction — the walk order is the contig order. ``valid`` masks
    out tail-padding rows (never kept, never pruned against). Returns the
    kept bool mask over the window."""
    r2 = r2_from_counts(C, k, num_samples)
    W = r2.shape[0]
    kept = np.zeros(W, dtype=bool)
    kept_idx: list = []  # bounded by W, the window size — not O(M)
    for i in range(W):
        if valid is not None and not valid[i]:
            continue
        if kept_idx and float(r2[i, kept_idx].max()) > r2_threshold:
            continue
        kept[i] = True
        kept_idx.append(i)
    return kept


def build_case_counts():
    """The jitted per-site association-counts kernel: ``((B, N) uint8,
    (N,) uint8 case mask) → (a (B,) int32 carriers among cases,
    t (B,) int32 carriers total)``. Single construction site shared by
    the runtime and the plan validator's eval_shape check."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def case_counts(X, case):
        # range: HAS_VARIATION {0,1} bits and a {0,1} case mask — every
        # product and per-site sum is bounded by N < 2^31 (ops/contracts.py).
        Xi = X.astype(jnp.int32)
        a = Xi @ case.astype(jnp.int32)
        t = jnp.sum(Xi, axis=1)
        return a, t

    return case_counts


def case_counts_reference(
    rows: np.ndarray, case: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Host NumPy oracle of the association-counts kernel."""
    X = np.asarray(rows, dtype=np.int64)
    c = np.asarray(case, dtype=np.int64)
    return X @ c, X.sum(axis=1)


__all__ = [
    "build_case_counts",
    "build_ld_window_stats",
    "case_counts_reference",
    "greedy_prune",
    "ld_window_stats_reference",
    "r2_from_counts",
]
