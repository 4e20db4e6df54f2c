"""Gower double-centering of the similarity matrix.

The reference centers row-by-row against broadcast row sums
(``VariantsPca.scala:246-263``): entry (i, j) becomes
``v − rowMean(i) − colMean(j) + matrixMean`` with means taken over the full
row count N. On device this is three reductions and one fused elementwise
pass; the driver-side ``collect`` of row sums and the broadcast disappear.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS


def _dtypes(in_dtype):
    """(compute, output) dtypes for centering.

    Every similarity matrix is integer-valued by construction (0/1 operand
    counts), and the reference centers in Double unconditionally
    (``VariantsPca.scala:246-263``) — so when x64 is live, centering
    arithmetic runs in float64 regardless of the carrier dtype (int32 exact
    Gramians and f32 Gramians holding the same exact integers center
    bit-identically; whole-genome counts exceed f32's 2^24 exact range).
    The upcast happens inside the fused reduction/elementwise kernels, so no
    f64 N×N is ever materialized; the OUTPUT stays in the eigensolve's
    dtype (f32, or f64 for callers that passed f64 in)."""
    wide = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    out = jnp.float64 if in_dtype == jnp.float64 else jnp.float32
    return wide, out


@jax.jit
def gower_center(S: jax.Array) -> jax.Array:
    """B = S − rowMean − colMean + matrixMean (``VariantsPca.scala:252-263``)."""
    wide, out = _dtypes(S.dtype)
    Sw = S.astype(wide)
    row_mean = jnp.mean(Sw, axis=1, keepdims=True)
    col_mean = jnp.mean(Sw, axis=0, keepdims=True)
    total_mean = jnp.mean(Sw)
    return (Sw - row_mean - col_mean + total_mean).astype(out)


def gower_center_sharded(
    S: jax.Array, mesh: Mesh, n_true: int | None = None
) -> jax.Array:
    """Centering for a row-sharded Gramian (``samples`` axis): row means are
    local, column/matrix means are one ``psum`` over the row tiles.

    ``n_true`` handles cohort padding (``ShardedGramianAccumulator`` pads N
    to a multiple of the samples axis with all-zero rows/columns): means are
    taken over the true cohort size and padded rows/columns are re-zeroed
    after centering, so the padded result is exactly the dense result
    embedded in a zero block — eigenvectors and eigenvalues are unchanged.

    Centering arithmetic runs in float64 when x64 is live (see
    :func:`_dtypes`); the row-tile output is f32 either way — the downstream
    sharded eigensolve's dtype. The program is built once per (mesh, true
    size); its XLA module is ``jit_gower_center_sharded``.
    """
    n = S.shape[0] if n_true is None else int(n_true)
    return _gower_center_sharded(mesh, n)(S)


@functools.lru_cache(maxsize=16)
def _gower_center_sharded(mesh: Mesh, n: int):
    def gower_center_sharded(S_local):
        wide, _ = _dtypes(S_local.dtype)
        S_local = S_local.astype(wide)
        n_local = S_local.shape[0]
        row_start = jax.lax.axis_index(SAMPLES_AXIS) * n_local
        # Padded entries of S are zero by construction, so sums over the
        # padded extent equal sums over the true extent; only the divisor
        # and the output mask need the true size.
        row_mean = jnp.sum(S_local, axis=1, keepdims=True) / n
        col_sum = jax.lax.psum(jnp.sum(S_local, axis=0, keepdims=True), SAMPLES_AXIS)
        col_mean = col_sum / n
        total_mean = jnp.sum(col_sum) / (n * n)
        out = S_local - row_mean - col_mean + total_mean
        row_mask = (row_start + jnp.arange(n_local)) < n
        col_mask = jnp.arange(S_local.shape[1]) < n
        # range: centered values are real-valued (means subtracted) — the
        # downstream subspace eigensolve runs in f32 by design; integer
        # exactness intentionally ends at the centering boundary (the
        # accumulator ladder, ops/contracts.py, stops at the raw Gramian).
        return jnp.where(
            row_mask[:, None] & col_mask[None, :], out, 0.0
        ).astype(jnp.float32)

    fn = shard_map(
        gower_center_sharded,
        mesh=mesh,
        in_specs=P(SAMPLES_AXIS, None),
        out_specs=P(SAMPLES_AXIS, None),
    )
    return jax.jit(fn, out_shardings=NamedSharding(mesh, P(SAMPLES_AXIS, None)))


__all__ = ["gower_center", "gower_center_sharded"]
