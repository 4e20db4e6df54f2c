"""The plain reference at scale: the Lanczos eigen reference against a
dense float64 eigensolve, the centred operator against ``center``, and the
tile-by-tile Gramian comparison against a whole-matrix fetch."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import core, reference, traffic  # noqa: E402


def _cohort_gramian(n: int, sites: int, seed: int) -> np.ndarray:
    """XᵀX of {0,1} genotypes of four equal populations, whose structure
    eigenvalues lie close together (the shape of the cells' cohorts)."""
    rng = np.random.default_rng(seed)
    af = rng.uniform(0.01, 0.5, sites)
    shift = rng.uniform(0.25, 1.75, (4, sites))
    p = np.clip(af[None] * shift, 0.002, 0.95)
    X = (rng.random((sites, n)) < p[(np.arange(n) * 4) // n].T).astype(np.float64)
    return np.rint(X.T @ X).astype(np.int32)


def _tiles(G: np.ndarray, devices) -> list:
    import jax

    return [
        reference.Tile(r0, r1, d, jax.device_put(G[r0:r1], d))
        for r0, r1, d in reference.row_layout(G.shape[0], devices)
    ]


@pytest.mark.parametrize("n, seed", [(512, 1), (1024, 2), (2048, 3)])
def test_lanczos_agrees_with_dense_eigh(n, seed):
    import jax

    G = _cohort_gramian(n, 12_000, seed)
    op = reference.CentredGramian(_tiles(G, jax.devices()[:2]))
    vals, vecs = reference.lanczos_eigenpairs(op, 8)
    dense_vals, dense_vecs = reference.eigenpairs(reference.center(G), 8)
    assert np.abs(vals - dense_vals).max() <= 1e-9 * dense_vals[0]
    residuals = np.linalg.norm(op.matmat(vecs) - vecs * vals, axis=0) / vals[0]
    assert residuals.max() <= reference.RESIDUAL_BOUND
    # A near-tied pair among the eight (inside CLUSTER_REL_GAP), and
    # components that mix it: any rotation inside it is an equally right
    # answer, so both references read the same gap.
    mags = np.abs(dense_vals)
    tied = [i for i in range(7) if mags[i] - mags[i + 1] < reference.CLUSTER_REL_GAP * mags[i]]
    assert tied, dense_vals
    i = tied[0]
    c, s = np.cos(0.6), np.sin(0.6)
    V = dense_vecs[:, : i + 2].copy()
    V[:, i] = c * dense_vecs[:, i] + s * dense_vecs[:, i + 1]
    V[:, i + 1] = -s * dense_vecs[:, i] + c * dense_vecs[:, i + 1]
    V += np.random.default_rng(seed).standard_normal(V.shape) * 1e-4
    gap = reference.eigenspace_gap(V, vals, vecs)
    assert gap == pytest.approx(reference.eigenspace_gap(V, dense_vals, dense_vecs), abs=1e-9)
    assert 1e-5 < gap < 1e-2


@pytest.mark.parametrize("devices", [1, 3])
def test_centred_operator_agrees_with_center(devices):
    import jax

    G = _cohort_gramian(300, 5_000, 7)
    op = reference.CentredGramian(_tiles(G, jax.devices()[:devices]))
    B = reference.center(G)
    V = np.random.default_rng(8).standard_normal((300, 5)) * np.array([1, 1e-3, 1e3, 1e-9, 7])
    assert np.abs(op.matmat(V) - B @ V).max() <= 1e-12 * np.abs(B).max() * np.abs(V).max(axis=0).max()
    v = V[:, 0]
    assert op.matvec(v).shape == v.shape
    np.testing.assert_allclose(op.matvec(v), B @ v, rtol=0, atol=1e-12 * np.abs(B @ v).max())


def test_centred_operator_is_exact_on_integer_vectors():
    import jax

    G = _cohort_gramian(200, 3_000, 9)
    op = reference.CentredGramian(_tiles(G, jax.devices()[:2]))
    V = np.random.default_rng(10).integers(-1000, 1000, (200, 3)).astype(np.float64)
    # G·V of integers is an integer below 2^53: exact in float64.
    np.testing.assert_array_equal(op.gv(V), G.astype(np.float64) @ V)


def test_tiles_must_cover_every_row_once():
    import jax

    G = _cohort_gramian(40, 500, 11)
    tiles = _tiles(G, jax.devices()[:2])
    with pytest.raises(ValueError):
        reference.CentredGramian(tiles[:1])


def _cell(name):
    """A cell of the manifest at its dry size, or the four-device ring
    cell of ``test_bench_faults.py``: kg1000's traffic over 64 samples on a
    1x4 mesh."""
    if name != "ring64.wgs-batch":
        return core.dry_overrides(core.cell(name))
    doc = core.dry_overrides(core.cell("kg1000.wgs-batch"))
    with open(os.path.join(ROOT, "tests", "benchmark", "data", "ring64.json"), encoding="utf-8") as f:
        doc["config"] = json.load(f)
    doc["chips"] = 4
    return doc


@pytest.mark.parametrize("name", ["kg1000.wgs-batch", "platinum.wgs-batch", "ring64.wgs-batch"])
def test_tiled_comparison_equals_a_whole_fetch(name):
    """The check's numbers, compared tile by tile on the devices, equal
    those of the whole matrix fetched to the host and compared there, on a
    sound Gramian and on one with a planted error."""
    import jax

    cell = _cell(name)
    cfg, trf = cell["config"], cell["traffic"]
    n, spacing = int(cfg["num_samples"]), int(trf["spacing"])
    devices = jax.devices()[: cell["chips"]]
    job = core.load_job(cell["job"]).Job(cell, devices, False, None)
    record = job(traffic.closed_job(trf, 12345, 0))
    S = record["S"]
    assert len({shard.index for shard in S.addressable_shards}) == cell["chips"]
    whole = reference.host_gramian(
        reference.gramian_tiles(cfg, record["ranges"], spacing, reference.row_layout(n, devices[:1]))
    )
    tiles = reference.gramian_tiles(cfg, record["ranges"], spacing, reference.shard_layout(S, n))
    np.testing.assert_array_equal(reference.host_gramian(tiles), whole)
    for planted in (0, 7):
        S_planted = S.at[n - 1, 1].add(planted)
        fetched = np.asarray(jax.device_get(S_planted)).astype(np.int64)[:n, :n]
        assert reference.max_abs_diff(S_planted, tiles, n) == float(np.abs(fetched - whole).max())
        assert reference.max_abs_diff(S_planted, tiles, n) == planted
    vals, vecs = reference.reference_eigen(cfg, tiles)
    dense_vals, dense_vecs = reference.eigenpairs(reference.center(whole), int(cfg["num_pc"]) + 6)
    np.testing.assert_array_equal(vals, dense_vals)
    assert reference.eigenspace_gap(record["pcs"], vals, vecs) == reference.eigenspace_gap(
        record["pcs"], dense_vals, dense_vecs
    )


def test_control_tiles_are_rows_of_the_whole_control():
    import jax

    cell = _cell("ring64.wgs-batch")
    cfg = cell["config"]
    ranges = [reference.grid_range(0, 3_000_000, 73), reference.grid_range(0, 2_000_000, 73)]
    whole = reference.host_gramian(
        reference.gramian_tiles(cfg, ranges, 73, reference.row_layout(64, jax.devices()[:1]), "control")
    )
    tiles = reference.gramian_tiles(cfg, ranges, 73, reference.row_layout(64, jax.devices()[:3]), "control")
    np.testing.assert_array_equal(reference.host_gramian(tiles), whole)
    exact = reference.gramian_tiles(cfg, ranges, 73, reference.row_layout(64, jax.devices()[:3]))
    assert reference.tiles_max_abs_diff(tiles, exact) == np.abs(whole - reference.host_gramian(exact)).max()
