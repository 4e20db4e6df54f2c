"""Principal components of the centered similarity matrix.

The reference feeds centered rows into MLlib's
``RowMatrix.computePrincipalComponents`` (``VariantsPca.scala:264-266``),
which builds the column covariance and eigendecomposes it. For a Gower
double-centered matrix B (symmetric, zero row/column means) the covariance is
``BᵀB/(n−1) = B²/(n−1)``, whose eigenvectors are B's eigenvectors ordered by
eigenvalue *magnitude*. So the TPU-native equivalent is a single
``jnp.linalg.eigh`` on the HBM-resident B with |λ|-descending ordering —
no covariance materialization, no driver round-trip. A unit test pins this
equivalence against a literal NumPy replication of the MLlib semantics.

Eigenvector sign is arbitrary in both implementations; we fix a deterministic
convention (largest-magnitude component positive) so runs are reproducible.
"""

from __future__ import annotations

from typing import Tuple

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("num_pc",))
def principal_components(
    centered: jax.Array, num_pc: int = 2
) -> Tuple[jax.Array, jax.Array]:
    """Top-k principal components of a centered symmetric matrix.

    Returns ``(components, eigenvalues)`` where ``components`` is (N, k) —
    row i is sample i's coordinates, matching the reference's consumption of
    the MLlib result (``VariantsPca.scala:267-270``) — and ``eigenvalues``
    holds the corresponding eigenvalues of B (descending |λ|).
    """
    # range: centered input is real-valued; the eigensolve is defined in
    # f32 — integer exactness ends at the centering boundary by design.
    B = centered.astype(jnp.float32)
    # Symmetrize against accumulated roundoff; B is symmetric by construction.
    B = (B + B.T) * 0.5
    eigenvalues, eigenvectors = jnp.linalg.eigh(B)
    order = jnp.argsort(-jnp.abs(eigenvalues))[:num_pc]
    top = eigenvectors[:, order]
    # Deterministic sign: largest-|component| entry of each PC is positive.
    idx = jnp.argmax(jnp.abs(top), axis=0)
    signs = jnp.sign(top[idx, jnp.arange(num_pc)])
    signs = jnp.where(signs == 0, 1.0, signs)
    return top * signs, eigenvalues[order]


@functools.partial(
    jax.jit, static_argnames=("num_pc", "iterations", "oversample")
)
def principal_components_subspace(
    centered: jax.Array,
    num_pc: int = 2,
    iterations: int = 80,
    oversample: int = 8,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k principal components by subspace iteration + Rayleigh–Ritz.

    The TPU-first eigensolver for the driver path: ``num_pc`` is tiny (the
    reference defaults to 2, ``GenomicsConf.scala:76``), so the full O(N³)
    ``eigh`` is the wrong tool — XLA's TPU eigh at N=2,504 compiles for
    minutes and runs in tens of seconds (measured before PR 1), while subspace
    iteration is a few hundred skinny (N×N)@(N×k) MXU matmuls: ~20 ms warm.
    Subspace iteration converges to the largest-|λ| eigenpairs — exactly the
    MLlib covariance ordering (see :func:`principal_components`). It also
    extends to a row-sharded B unchanged, where sharded eigh would not.

    Deterministic: fixed PRNG key, fixed iteration count, and the same sign
    convention as :func:`principal_components`.
    """
    # range: centered input is real-valued; the subspace iteration runs in
    # f32 by design — integer exactness ends at the centering boundary.
    B = centered.astype(jnp.float32)
    B = (B + B.T) * 0.5
    n = B.shape[0]
    k = min(num_pc + oversample, n)
    V = jax.random.normal(jax.random.PRNGKey(0), (n, k), dtype=B.dtype)
    V, _ = jnp.linalg.qr(V)

    def body(_, V):
        Q, _ = jnp.linalg.qr(B @ V)
        return Q

    V = jax.lax.fori_loop(0, iterations, body, V)
    return _rayleigh_ritz(V, B @ V, num_pc)


def _rayleigh_ritz(V, W, num_pc: int):
    """Rayleigh–Ritz extraction shared by the dense and sharded solvers:
    project (T = VᵀW where W = BV), eigh the small k×k, order by |λ|, and fix
    the deterministic sign convention (largest-|component| entry positive)."""
    T = V.T @ W
    evals, Wk = jnp.linalg.eigh((T + T.T) * 0.5)
    order = jnp.argsort(-jnp.abs(evals))[:num_pc]
    top = V @ Wk[:, order]
    idx = jnp.argmax(jnp.abs(top), axis=0)
    signs = jnp.sign(top[idx, jnp.arange(num_pc)])
    signs = jnp.where(signs == 0, 1.0, signs)
    return top * signs, evals[order]


def principal_components_subspace_sharded(
    centered: jax.Array,
    mesh,
    num_pc: int = 2,
    iterations: int = 80,
    oversample: int = 8,
    n_true: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Subspace iteration on a ROW-SHARDED centered matrix — the large-N
    completion of the sharded pipeline (``VariantsPca.scala:216-217``'s
    ~50K-samples regime): no device ever materializes the full N×N matrix.

    Per iteration the only sharded compute is ``B_local @ V`` (one skinny
    MXU matmul per row tile) followed by an ``all_gather`` of the (N, k)
    iterate — k is ``num_pc + oversample``, so the collective traffic is a
    few hundred KB regardless of N. QR/Rayleigh–Ritz run replicated on the
    gathered skinny matrix (identical on every device). Padded rows/columns
    (all-zero after :func:`gower_center_sharded` with ``n_true``) contribute
    nothing and the returned components simply carry zero rows for padding.
    The program is built once per (mesh, parameters, true size); its XLA
    module is ``jit_principal_components_subspace_sharded``.
    """
    n = centered.shape[0] if n_true is None else int(n_true)
    return _subspace_sharded(mesh, num_pc, iterations, oversample, n)(centered)


@functools.lru_cache(maxsize=16)
def _subspace_sharded(mesh, num_pc: int, iterations: int, oversample: int, n: int):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS

    k = min(num_pc + oversample, n)

    def principal_components_subspace_sharded(B_local):
        n_padded = B_local.shape[1]
        V = jax.random.normal(jax.random.PRNGKey(0), (n_padded, k), jnp.float32)

        def gathered_bv(V):
            # range: centered row tile is real-valued; the sharded
            # eigensolve runs in f32 by design (see the dense variants).
            W_local = B_local.astype(jnp.float32) @ V  # (n_local, k)
            return jax.lax.all_gather(
                W_local, SAMPLES_AXIS, axis=0, tiled=True
            )  # (n_padded, k), replicated

        def body(_, V):
            Q, _ = jnp.linalg.qr(gathered_bv(V))
            return Q

        V, _ = jnp.linalg.qr(V)
        V = jax.lax.fori_loop(0, iterations, body, V)
        return _rayleigh_ritz(V, gathered_bv(V), num_pc)

    # check_vma=False: the iterate alternates device-varying (B_local @ V)
    # and replicated (all_gather → identical QR on every device) forms, which
    # the static replication checker can't follow; the replicated out_specs
    # are correct because every device computes the same gathered iterate.
    fn = shard_map(
        principal_components_subspace_sharded,
        mesh=mesh,
        in_specs=P(SAMPLES_AXIS, None),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def mllib_reference_pca(centered, num_pc: int = 2):
    """NumPy oracle replicating MLlib ``computePrincipalComponents``
    literally: column covariance of the rows, then eigh, descending
    eigenvalues (used by tests to pin the equivalence argument above)."""
    import numpy as np

    M = np.asarray(centered, dtype=np.float64)
    n = M.shape[0]
    mean = M.mean(axis=0, keepdims=True)
    cov = (M - mean).T @ (M - mean) / (n - 1)
    eigenvalues, eigenvectors = np.linalg.eigh(cov)
    order = np.argsort(-eigenvalues)[:num_pc]
    return eigenvectors[:, order], eigenvalues[order]


__all__ = [
    "principal_components",
    "principal_components_subspace",
    "principal_components_subspace_sharded",
    "mllib_reference_pca",
]
