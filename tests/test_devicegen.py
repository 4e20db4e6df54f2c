"""Device-side ingest vs the host synthetic source.

The device data plane (``ops/devicegen.py``) must be bitwise-identical to the
host packed path (``sources/synthetic.py:genotype_blocks``) — same splitmix64
draws, same fixed-point site metadata, same keep semantics — or the benchmark
would be running a different cohort than the wire path serves.
"""

import numpy as np
import pytest

import jax

from spark_examples_tpu.ops.devicegen import (
    DeviceGenGramianAccumulator,
    generate_has_variation,
    mix64,
    site_thresholds_on_device,
)
from spark_examples_tpu.ops.gramian import gramian_reference
from spark_examples_tpu.sharding.contig import Contig
from spark_examples_tpu.sources.synthetic import (
    SyntheticGenomicsSource,
    _mix,
    af_filter_micro,
)


def test_mix64_matches_host():
    xs = np.array(
        [0, 1, 2, 0xDEADBEEF, (1 << 64) - 1, 0x9E3779B97F4A7C15],
        dtype=np.uint64,
    )
    with jax.enable_x64(True):
        got = np.asarray(jax.device_get(mix64(jax.numpy.asarray(xs))))
    np.testing.assert_array_equal(got, _mix(xs))


def test_fmix32_matches_host_and_murmur3_vectors():
    """Device fmix32 == host _fmix32 == the published murmur3 finalizer
    (golden vectors pin the stream definition: any accidental drift in
    either implementation breaks loudly, not as a silent cohort change)."""
    from spark_examples_tpu.ops.devicegen import fmix32
    from spark_examples_tpu.sources.synthetic import _fmix32

    xs = np.array(
        [0, 1, 2, 0xDEADBEEF, 0xFFFFFFFF, 0x9E3779B9], dtype=np.uint32
    )
    host = _fmix32(xs)
    got = np.asarray(jax.device_get(fmix32(jax.numpy.asarray(xs))))
    np.testing.assert_array_equal(got, host)
    # murmur3 fmix32 reference values (h ^= h>>16; h*=0x85ebca6b;
    # h ^= h>>13; h*=0xc2b2ae35; h ^= h>>16), independently computed.
    def reference(h):
        h ^= h >> 16
        h = (h * 0x85EBCA6B) & 0xFFFFFFFF
        h ^= h >> 13
        h = (h * 0xC2B2AE35) & 0xFFFFFFFF
        h ^= h >> 16
        return h

    np.testing.assert_array_equal(host, [reference(int(x)) for x in xs])


def test_genotype_draw_pair_golden_vectors():
    """The v2 genotype stream definition, pinned: fold-after-sample-xor of
    the splitmix64 site state, one fmix32, multiplicative second allele.
    These values changing means the synthetic cohort itself changed —
    every recorded benchmark and parity artifact would silently shift."""
    from spark_examples_tpu.sources.synthetic import _genotype_draw_pair

    d1, d2 = _genotype_draw_pair(
        np.uint64(0x123456789ABCDEF0),
        np.array([100, 7300], dtype=np.int64),
        3,
    )
    assert d1.shape == (2, 3) and d1.dtype == np.uint32
    # Independently recomputed with the documented construction.
    def expected(vs_key, pos, sample):
        M = (1 << 64) - 1
        P1, P2, P3, P4 = (
            0x9E3779B97F4A7C15,
            0xC2B2AE3D27D4EB4F,
            0x165667B19E3779F9,
            0xD6E8FEB86659FD93,
        )

        def mix(x):
            x = (x + P1) & M
            x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & M
            x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & M
            return x ^ (x >> 31)

        h2 = mix(mix(vs_key ^ (pos * P2 & M)) ^ (100 * P3 & M))
        x64 = h2 ^ (sample * P4 & M)
        x = ((x64 >> 32) ^ x64) & 0xFFFFFFFF
        x ^= x >> 16
        x = (x * 0x85EBCA6B) & 0xFFFFFFFF
        x ^= x >> 13
        x = (x * 0xC2B2AE35) & 0xFFFFFFFF
        first = x ^ (x >> 16)
        second = ((first * 0x9E3779B9) & 0xFFFFFFFF) ^ 0x85EBCA6B
        return first, second

    for i, pos in enumerate((100, 7300)):
        for s in range(3):
            e1, e2 = expected(0x123456789ABCDEF0, pos, s)
            assert (int(d1[i, s]), int(d2[i, s])) == (e1, e2)


def _host_blocks(source, vsid, contig, **kw):
    return list(source.genotype_blocks(vsid, contig, block_size=512, **kw))


@pytest.mark.parametrize("min_af", [None, 0.1])
def test_device_thresholds_bitwise_match_host_plan(min_af):
    """On-device site metadata == the host's compacted threshold plan."""
    source = SyntheticGenomicsSource(num_samples=40, seed=7)
    contig = Contig("17", 41_196_311, 41_277_499)  # BRCA1
    plan = list(source.site_threshold_plan(contig, min_allele_frequency=min_af))
    host_pos = np.concatenate([p for p, _ in plan])
    host_thr = np.concatenate([t for _, t in plan])

    k0, k1 = source.site_grid_range(contig)
    grid_pos = np.arange(k0, k1, dtype=np.int64) * source.variant_spacing
    with jax.enable_x64(True):
        T = np.asarray(
            jax.device_get(
                site_thresholds_on_device(
                    jax.numpy.asarray(np.uint64(source.site_key)),
                    jax.numpy.asarray(grid_pos),
                    jax.numpy.asarray(np.ones(len(grid_pos), dtype=bool)),
                    source.n_pops,
                    source.ref_block_fraction,
                    af_filter_micro(min_af),
                )
            )
        )
    keep = np.isin(grid_pos, host_pos)
    np.testing.assert_array_equal(T[~keep], 0)
    np.testing.assert_array_equal(T[keep], host_thr)


def test_device_rows_bitwise_match_host_packed_path():
    source = SyntheticGenomicsSource(num_samples=40, seed=7)
    contig = Contig("17", 41_196_311, 41_277_499)
    vsid = "10473108253681171589"
    host = _host_blocks(source, vsid, contig)
    host_rows = np.concatenate([b["has_variation"] for b in host])
    host_pos = np.concatenate([b["positions"] for b in host])

    k0, k1 = source.site_grid_range(contig)
    grid_pos = np.arange(k0, k1, dtype=np.int64) * source.variant_spacing
    with jax.enable_x64(True):
        T = site_thresholds_on_device(
            jax.numpy.asarray(np.uint64(source.site_key)),
            jax.numpy.asarray(grid_pos),
            jax.numpy.asarray(np.ones(len(grid_pos), dtype=bool)),
            source.n_pops,
            source.ref_block_fraction,
            None,
        )
        rows = np.asarray(
            jax.device_get(
                generate_has_variation(
                    jax.numpy.asarray(grid_pos),
                    T,
                    jax.numpy.asarray(
                        np.array(
                            [source.genotype_stream_key(vsid)], dtype=np.uint64
                        )
                    ),
                    jax.numpy.asarray(source.populations.astype(np.int32)),
                )
            )
        ).astype(np.uint8)
    # The host path additionally drops all-zero-variation rows; align on
    # positions and compare those rows bitwise, and check dropped rows are
    # exactly the all-zero ones.
    keep = np.isin(grid_pos, host_pos)
    np.testing.assert_array_equal(rows[~keep], 0)
    np.testing.assert_array_equal(rows[keep], host_rows)


@pytest.mark.parametrize(
    "sets", [("vs",), ("setA", "setB")], ids=["one-set", "two-sets"]
)
def test_segmented_generation_matches_gather_and_host(sets):
    """Cohorts of contiguous populations of at least 128 columns (512 = 4 ×
    128, and 640 = 4 × 160 for the second set) select each column's
    threshold inside the hash instead of gathering it: the rows equal the
    gather branch (a traced ``pops``) and the host packed path bitwise, and
    the fused accumulator's Gramian and counters equal the host's."""
    source = SyntheticGenomicsSource(num_samples=512, seed=5, cohort_sizes={"setB": 640})
    contig = Contig("1", 0, 40_000)
    sizes = tuple(source.num_samples_for(s) for s in sets)
    set_sizes = sizes if len(sets) > 1 else None
    pops = np.concatenate([source.populations_for(s) for s in sets]).astype(np.int32)
    keys = np.array([source.genotype_stream_key(s) for s in sets], dtype=np.uint64)
    k0, k1 = source.site_grid_range(contig)
    grid_pos = np.arange(k0, k1, dtype=np.int64) * source.variant_spacing

    # The host's rows on the site grid, per set side by side; rows the host
    # drops are all-zero.
    host = np.zeros((len(grid_pos), sum(sizes)), dtype=np.uint8)
    host_rows = []
    for s, (vsid, lo) in enumerate(zip(sets, np.cumsum((0,) + sizes))):
        blocks = _host_blocks(source, vsid, contig)
        rows = np.concatenate([b["has_variation"] for b in blocks])
        at = np.searchsorted(grid_pos, np.concatenate([b["positions"] for b in blocks]))
        host[at, lo : lo + sizes[s]] = rows
        host_rows.append(len(rows))

    with jax.enable_x64(True):
        T = site_thresholds_on_device(
            jax.numpy.asarray(np.uint64(source.site_key)),
            jax.numpy.asarray(grid_pos),
            jax.numpy.asarray(np.ones(len(grid_pos), dtype=bool)),
            source.n_pops,
            source.ref_block_fraction,
            None,
        )
        args = (jax.numpy.asarray(grid_pos), T, jax.numpy.asarray(keys))
        segmented = np.asarray(
            generate_has_variation(*args, jax.numpy.asarray(pops), set_sizes)
        )
        gathered = np.asarray(
            jax.jit(generate_has_variation, static_argnums=4)(
                *args, jax.numpy.asarray(pops), set_sizes
            )
        )
    np.testing.assert_array_equal(segmented, gathered)
    np.testing.assert_array_equal(segmented.astype(np.uint8), host)

    acc = DeviceGenGramianAccumulator(
        num_samples=512,
        vs_keys=[int(k) for k in keys],
        pops=pops,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=64,
        blocks_per_dispatch=4,
        n_pops=source.n_pops,
        set_sizes=set_sizes,
        pops_per_set=[source.populations_for(s) for s in sets] if set_sizes else None,
    )
    assert acc.pop_segments == 4 * len(sets)
    acc.add_grid(k0, k1)
    np.testing.assert_array_equal(acc.finalize(), gramian_reference(host))
    rows, kept = acc.ingest_counters()
    assert rows.tolist() == host_rows
    assert kept == sum(len(p) for p, _ in source.site_threshold_plan(contig))


@pytest.mark.parametrize("exact_int", [True, False])
def test_fused_accumulator_matches_reference_gramian(exact_int):
    source = SyntheticGenomicsSource(num_samples=24, seed=11)
    contig = Contig("1", 0, 60_000)
    vsid = "vs"
    host = _host_blocks(source, vsid, contig)
    host_rows = np.concatenate([b["has_variation"] for b in host])

    acc = DeviceGenGramianAccumulator(
        num_samples=24,
        vs_keys=[source.genotype_stream_key(vsid)],
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=64,
        blocks_per_dispatch=4,
        exact_int=exact_int,
    )
    k0, k1 = source.site_grid_range(contig)
    acc.add_grid(k0, k1)
    got = acc.finalize()
    np.testing.assert_array_equal(got, gramian_reference(host_rows))
    with jax.enable_x64(True):
        variant_rows = np.asarray(jax.device_get(acc.variant_rows))
        kept = int(jax.device_get(acc.kept_sites))
    assert variant_rows.tolist() == [host_rows.shape[0]]
    # kept_sites counts AF/ref-kept sites BEFORE the all-zero-variation drop
    # — the compacted host threshold plan's site count.
    plan_sites = sum(
        len(p) for p, _ in source.site_threshold_plan(contig)
    )
    assert kept == plan_sites


def test_fused_accumulator_min_af_matches_host():
    source = SyntheticGenomicsSource(num_samples=16, seed=3)
    contig = Contig("2", 10_000, 90_000)
    vsid = "vs"
    host = _host_blocks(source, vsid, contig, min_allele_frequency=0.15)
    host_rows = np.concatenate([b["has_variation"] for b in host])

    acc = DeviceGenGramianAccumulator(
        num_samples=16,
        vs_keys=[source.genotype_stream_key(vsid)],
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        min_af_micro=af_filter_micro(0.15),
        block_size=32,
        blocks_per_dispatch=2,
    )
    k0, k1 = source.site_grid_range(contig)
    acc.add_grid(k0, k1)
    np.testing.assert_array_equal(acc.finalize(), gramian_reference(host_rows))


def test_auto_blocks_per_dispatch_scales_with_cohort():
    """Constant device work per dispatch: the tuned large-N geometry stays
    put, small cohorts get longer scans (platinum whole-genome ~2× faster,
    1.03 → 0.53 s — DESIGN.md §7.3), clamped to the measured [32, 512]
    range and a multiple of 8 (the tail program is K/8 blocks)."""
    from spark_examples_tpu.ops.devicegen import auto_blocks_per_dispatch

    assert auto_blocks_per_dispatch(2504, 16384) == 32  # the tuned optimum
    assert auto_blocks_per_dispatch(2504, 1024) == 512  # same group sites
    assert auto_blocks_per_dispatch(17, 16384) == 512  # clamp high
    assert auto_blocks_per_dispatch(25_000, 16384) == 32  # clamp low
    k = auto_blocks_per_dispatch(500, 16384)
    assert 32 <= k <= 512 and k % 8 == 0


def test_device_multiset_concatenates_per_set_genotypes():
    source = SyntheticGenomicsSource(num_samples=12, seed=3)
    contig = Contig("20", 100_000, 140_000)
    set_a, set_b = "setA", "setB"
    acc = DeviceGenGramianAccumulator(
        num_samples=12,
        vs_keys=[
            source.genotype_stream_key(set_a),
            source.genotype_stream_key(set_b),
        ],
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=32,
        blocks_per_dispatch=2,
    )
    k0, k1 = source.site_grid_range(contig)
    acc.add_grid(k0, k1)
    got = acc.finalize()

    rows_a = np.concatenate(
        [b["has_variation"] for b in _host_blocks(source, set_a, contig)]
    )
    pos_a = np.concatenate(
        [b["positions"] for b in _host_blocks(source, set_a, contig)]
    )
    rows_b = np.concatenate(
        [b["has_variation"] for b in _host_blocks(source, set_b, contig)]
    )
    pos_b = np.concatenate(
        [b["positions"] for b in _host_blocks(source, set_b, contig)]
    )
    # Build the joint matrix on the shared kept-site grid (drops differ only
    # by all-zero rows, which don't affect the Gramian).
    all_pos = np.union1d(pos_a, pos_b)
    joint = np.zeros((len(all_pos), 24), dtype=np.int64)
    joint[np.searchsorted(all_pos, pos_a), :12] = rows_a
    joint[np.searchsorted(all_pos, pos_b), 12:] = rows_b
    np.testing.assert_array_equal(got, joint.T @ joint)


@pytest.mark.parametrize(
    "mesh_shape", [{"samples": 4}, {"data": 2, "samples": 2}]
)
def test_ring_multiset_matches_dense_and_host(mesh_shape):
    """Multi-set ring ingest: concatenated per-set column blocks through the
    ring exchange equal the dense multi-set accumulator AND the host joint
    oracle — asymmetric cohorts (13 + 6 columns, padded 20) included, with
    per-set variant-row accounting identical to the dense path."""
    from spark_examples_tpu.ops.devicegen import DeviceGenRingGramianAccumulator
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS, make_mesh

    mesh = make_mesh(
        {
            **({DATA_AXIS: mesh_shape["data"]} if "data" in mesh_shape else {}),
            SAMPLES_AXIS: mesh_shape["samples"],
        }
    )
    source = SyntheticGenomicsSource(
        num_samples=13, seed=3, cohort_sizes={"setB": 6}
    )
    contig = Contig("20", 100_000, 140_000)
    sets = ["setA", "setB"]
    sizes = [source.num_samples_for(s) for s in sets]
    assert sizes == [13, 6]
    pops_per_set = [source.populations_for(s) for s in sets]
    keys = [source.genotype_stream_key(s) for s in sets]
    common = dict(
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=16,
        blocks_per_dispatch=2,
        n_pops=source.n_pops,
    )
    dense = DeviceGenGramianAccumulator(
        num_samples=13,
        vs_keys=keys,
        pops=source.populations,
        set_sizes=sizes,
        pops_per_set=pops_per_set,
        **common,
    )
    ring = DeviceGenRingGramianAccumulator(
        num_samples=13,
        vs_key=keys,
        pops=source.populations,
        mesh=mesh,
        set_sizes=sizes,
        pops_per_set=pops_per_set,
        **common,
    )
    k0, k1 = source.site_grid_range(contig)
    dense.add_grid(k0, k1)
    ring.add_grid(k0, k1)
    dense_G = dense.finalize()
    ring_G = ring.finalize()
    np.testing.assert_array_equal(ring_G, dense_G)

    # Host joint oracle on the shared kept-site grid.
    rows = {}
    pos = {}
    for s in sets:
        blocks = _host_blocks(source, s, contig)
        rows[s] = np.concatenate([b["has_variation"] for b in blocks])
        pos[s] = np.concatenate([b["positions"] for b in blocks])
    all_pos = np.union1d(pos[sets[0]], pos[sets[1]])
    joint = np.zeros((len(all_pos), sum(sizes)), dtype=np.int64)
    joint[np.searchsorted(all_pos, pos[sets[0]]), : sizes[0]] = rows[sets[0]]
    joint[np.searchsorted(all_pos, pos[sets[1]]), sizes[0] :] = rows[sets[1]]
    np.testing.assert_array_equal(ring_G, joint.T @ joint)

    dense_rows, dense_kept = dense.ingest_counters()
    ring_rows, ring_kept = ring.ingest_counters()
    np.testing.assert_array_equal(ring_rows, dense_rows)
    assert ring_kept == dense_kept
    assert ring_rows.tolist() == [rows["setA"].shape[0], rows["setB"].shape[0]]


def test_add_range_validates():
    source = SyntheticGenomicsSource(num_samples=8, seed=1)
    acc = DeviceGenGramianAccumulator(
        num_samples=8,
        vs_keys=[source.genotype_stream_key("v")],
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=8,
        blocks_per_dispatch=2,
    )
    with pytest.raises(ValueError):
        acc.add_range(0, 0)
    with pytest.raises(ValueError):
        acc.add_range(0, 17)


def test_fused_accumulator_data_parallel_mesh():
    """Data-parallel device ingest: slices generate disjoint grid spans,
    finalize psums — equals the host reference Gramian."""
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS, make_mesh

    mesh = make_mesh({DATA_AXIS: 4, SAMPLES_AXIS: 2})
    source = SyntheticGenomicsSource(num_samples=20, seed=13)
    contig = Contig("3", 0, 120_000)
    vsid = "vs"
    host = _host_blocks(source, vsid, contig)
    host_rows = np.concatenate([b["has_variation"] for b in host])

    acc = DeviceGenGramianAccumulator(
        num_samples=20,
        vs_keys=[source.genotype_stream_key(vsid)],
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=32,
        blocks_per_dispatch=2,
        mesh=mesh,
    )
    k0, k1 = source.site_grid_range(contig)
    acc.add_grid(k0, k1)
    np.testing.assert_array_equal(acc.finalize(), gramian_reference(host_rows))
    with jax.enable_x64(True):
        rows = np.asarray(jax.device_get(acc.variant_rows))
    assert rows.shape == (4, 1)
    assert rows.sum() == host_rows.shape[0]


def test_device_ingest_bitwise_identical_across_device_counts():
    """Determinism across parallelism (the race-detection stand-in,
    SURVEY §5): int32 accumulation is associative, so 1-device and 4-slice
    data-parallel ingest produce BITWISE-identical Gramians and counters."""
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, make_mesh

    source = SyntheticGenomicsSource(num_samples=16, seed=21)
    contig = Contig("5", 0, 150_000)
    kw = dict(
        num_samples=16,
        vs_keys=[source.genotype_stream_key("vs")],
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=32,
        blocks_per_dispatch=2,
    )
    k0, k1 = source.site_grid_range(contig)

    acc1 = DeviceGenGramianAccumulator(**kw)
    acc1.add_grid(k0, k1)
    acc4 = DeviceGenGramianAccumulator(**kw, mesh=make_mesh({DATA_AXIS: 4}))
    acc4.add_grid(k0, k1)
    np.testing.assert_array_equal(acc1.finalize(), acc4.finalize())
    with jax.enable_x64(True):
        r1 = np.asarray(jax.device_get(acc1.variant_rows)).sum()
        r4 = np.asarray(jax.device_get(acc4.variant_rows)).sum()
        k1_ = int(np.asarray(jax.device_get(acc1.kept_sites)).sum())
        k4_ = int(np.asarray(jax.device_get(acc4.kept_sites)).sum())
    assert r1 == r4 and k1_ == k4_


def test_device_ingest_bitwise_matches_host_fuzz():
    """Fuzz the device ingest kernel against the host packed path: any
    cohort/seed/region must produce the identical Gramian."""
    pytest.importorskip("hypothesis")  # declared only under the `test` extra
    from hypothesis import given, settings, strategies as st

    @given(
        seed=st.integers(min_value=0, max_value=2**31),
        n=st.integers(min_value=2, max_value=12),
        start=st.integers(min_value=0, max_value=200_000),
        width=st.integers(min_value=200, max_value=4_000),
    )
    @settings(max_examples=10, deadline=None)
    def check(seed, n, start, width):
        source = SyntheticGenomicsSource(num_samples=n, seed=seed)
        contig = Contig("7", start, start + width)
        blocks = _host_blocks(source, "vs", contig)
        rows = (
            np.concatenate([b["has_variation"] for b in blocks])
            if blocks
            else np.zeros((0, n), np.uint8)
        )
        acc = DeviceGenGramianAccumulator(
            num_samples=n,
            vs_keys=[source.genotype_stream_key("vs")],
            pops=source.populations,
            site_key=source.site_key,
            spacing=source.variant_spacing,
            ref_block_fraction=source.ref_block_fraction,
            block_size=16,
            blocks_per_dispatch=2,
        )
        k0, k1 = source.site_grid_range(contig)
        if k1 > k0:
            acc.add_grid(k0, k1)
        np.testing.assert_array_equal(acc.finalize(), gramian_reference(rows))

    check()


@pytest.mark.parametrize(
    "mesh_shape",
    [{"samples": 4}, {"data": 2, "samples": 4}, {"data": 2, "samples": 2}],
)
def test_ring_device_ingest_matches_host(mesh_shape):
    """Sharded large-N device ingest: per-slice column generation + ring
    exchange equals the host reference Gramian, at padded non-divisible N."""
    from spark_examples_tpu.ops.devicegen import DeviceGenRingGramianAccumulator
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS, make_mesh

    mesh = make_mesh(
        {
            **({DATA_AXIS: mesh_shape["data"]} if "data" in mesh_shape else {}),
            SAMPLES_AXIS: mesh_shape["samples"],
        }
    )
    source = SyntheticGenomicsSource(num_samples=18, seed=9)  # 18 % 4 != 0
    contig = Contig("4", 5_000, 95_000)
    host = _host_blocks(source, "vs", contig)
    host_rows = np.concatenate([b["has_variation"] for b in host])

    acc = DeviceGenRingGramianAccumulator(
        num_samples=18,
        vs_key=source.genotype_stream_key("vs"),
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        mesh=mesh,
        block_size=16,
        blocks_per_dispatch=2,
    )
    k0, k1 = source.site_grid_range(contig)
    acc.add_grid(k0, k1)
    np.testing.assert_array_equal(acc.finalize(), gramian_reference(host_rows))
    with jax.enable_x64(True):
        rows = int(np.asarray(jax.device_get(acc.variant_rows)).sum())
        kept = int(np.asarray(jax.device_get(acc.kept_sites)).sum())
    assert rows == host_rows.shape[0]
    plan_sites = sum(len(p) for p, _ in source.site_threshold_plan(contig))
    assert kept == plan_sites


@pytest.mark.parametrize(
    "data, samples, pack, donate",
    [
        (1, 2, "on", False),
        (1, 3, "on", False),
        (1, 3, "off", True),
        (1, 4, "on", True),
        (1, 4, "off", False),
        (1, 8, "on", False),
        (2, 3, "on", True),
        (2, 4, "off", True),
    ],
    ids=["s2", "s3", "s3-unpacked-donated", "s4-donated", "s4-unpacked", "s8",
         "d2s3-donated", "d2s4-unpacked-donated"],
)
def test_half_ring_finalize_equals_reference_and_full_ring(data, samples, pack, donate):
    """The half ring's finalized Gramian, main and tail programs both run,
    equals the NumPy reference and the host-fed full ring's result bit for
    bit: ⌊D/2⌋+1 dots per block and D-1-⌊D/2⌋ blocks mirrored at finalize,
    with no duplicated step at odd D."""
    from spark_examples_tpu.ops.devicegen import DeviceGenRingGramianAccumulator
    from spark_examples_tpu.ops.gramian import ShardedGramianAccumulator
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS, make_mesh

    mesh = make_mesh({DATA_AXIS: data, SAMPLES_AXIS: samples})
    source = SyntheticGenomicsSource(num_samples=19, seed=23)
    contig = Contig("9", 3_000, 83_000)
    host_rows = np.concatenate(
        [b["has_variation"] for b in _host_blocks(source, "vs", contig)]
    )
    acc = DeviceGenRingGramianAccumulator(
        num_samples=19,
        vs_key=source.genotype_stream_key("vs"),
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        mesh=mesh,
        block_size=16,
        blocks_per_dispatch=8,
        pack_bits=pack,
    )
    assert acc.half_ring
    assert acc.ring_dots_per_block == samples // 2 + 1
    assert [t.shape for t in acc.G] == [(data, acc.padded, acc.n_local)] * len(acc.G)
    assert len(acc.G) == acc.ring_dots_per_block
    assert acc.ring_mirrored_tiles == samples - 1 - samples // 2
    k0, k1 = source.site_grid_range(contig)
    assert (k1 - k0) % acc.sites_per_dispatch
    acc.add_grid(k0, k1)
    assert acc._update_tail is not None
    with jax.enable_x64(True):
        result = acc.finalize_sharded(donate=donate)
        got = np.asarray(jax.device_get(result))[:19, :19]
    assert result.shape == (acc.padded, acc.padded)
    assert (acc.G is None) == donate

    full = ShardedGramianAccumulator(
        19, mesh, block_size=16, exact_int=True, pack_bits=pack
    )
    full.add_rows(host_rows)
    np.testing.assert_array_equal(got, gramian_reference(host_rows))
    np.testing.assert_array_equal(got, full.finalize())


def test_ring_device_ingest_end_to_end_sharded_pca():
    """Ring device ingest feeds the sharded centering + eigensolve without
    gathering N x N; result matches the dense single-device pipeline."""
    from spark_examples_tpu.ops.centering import gower_center, gower_center_sharded
    from spark_examples_tpu.ops.devicegen import (
        DeviceGenGramianAccumulator,
        DeviceGenRingGramianAccumulator,
    )
    from spark_examples_tpu.ops.pca import (
        principal_components_subspace,
        principal_components_subspace_sharded,
    )
    from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS, make_mesh

    mesh = make_mesh({SAMPLES_AXIS: 8})
    source = SyntheticGenomicsSource(num_samples=21, seed=17)
    contig = Contig("6", 0, 200_000)
    kw = dict(
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=32,
        blocks_per_dispatch=2,
    )
    k0, k1 = source.site_grid_range(contig)

    ring = DeviceGenRingGramianAccumulator(
        num_samples=21, vs_key=source.genotype_stream_key("vs"), mesh=mesh, **kw
    )
    ring.add_grid(k0, k1)
    B_sharded = gower_center_sharded(ring.finalize_sharded(), mesh, n_true=21)
    c_sharded, e_sharded = principal_components_subspace_sharded(
        B_sharded, mesh, 2, n_true=21
    )
    c_sharded = np.asarray(jax.device_get(c_sharded))[:21]

    dense = DeviceGenGramianAccumulator(
        num_samples=21, vs_keys=[source.genotype_stream_key("vs")], **kw
    )
    dense.add_grid(k0, k1)
    import jax.numpy as jnp

    B_dense = gower_center(jnp.asarray(dense.finalize_device(), jnp.float32))
    c_dense, e_dense = principal_components_subspace(B_dense, 2)
    c_dense = np.asarray(jax.device_get(c_dense))

    np.testing.assert_allclose(
        np.asarray(jax.device_get(e_sharded)),
        np.asarray(jax.device_get(e_dense)),
        rtol=1e-4,
    )
    signs = np.sign((c_dense * c_sharded).sum(axis=0))
    signs[signs == 0] = 1
    np.testing.assert_allclose(c_dense, c_sharded * signs, atol=1e-3)


# ----------------------------------------------------------------- the loop's memory


def _ring64(block_size=16, blocks_per_dispatch=1):
    """A 64-sample ring accumulator on a 1x4 mesh of the virtual CPU devices
    and the grid range of its contig (10 dispatches of 16 sites)."""
    from spark_examples_tpu.ops.devicegen import DeviceGenRingGramianAccumulator
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS, make_mesh

    source = SyntheticGenomicsSource(num_samples=64, seed=42)
    acc = DeviceGenRingGramianAccumulator(
        num_samples=64,
        vs_key=source.genotype_stream_key("vs"),
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        mesh=make_mesh({DATA_AXIS: 1, SAMPLES_AXIS: 4}),
        block_size=block_size,
        blocks_per_dispatch=blocks_per_dispatch,
    )
    k0 = source.site_grid_range(Contig("17", 0, 100_000))[0]
    return acc, k0, k0 + 10 * block_size * blocks_per_dispatch


def _budget(monkeypatch, copies, gramian_bytes):
    """Device memory in which ``copies`` G tiles fill the loop's share."""
    from spark_examples_tpu.ops import devicegen, gramian

    budget = int((copies + 0.5) * gramian_bytes / devicegen.LOOP_HBM_FRACTION)
    monkeypatch.setattr(gramian, "per_device_memory_bytes", lambda: budget)


def _waits(monkeypatch):
    """The arrays the loop blocks on, in order."""
    waited = []
    block = jax.block_until_ready

    def recorded(x):
        waited.append(x)
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", recorded)
    return waited


@pytest.mark.parametrize("copies", [3, 4])
def test_ring_loop_keeps_at_most_its_copies(monkeypatch, copies):
    """Ten ring dispatches on a 1x4 mesh: the loop waits for the dispatch
    ``depth`` back after each one, so at most ``depth`` stay queued and the
    live copies of its state, the half ring's three step tiles, stay
    within ``gramian_copies_max`` (``depth + 2``)."""
    acc, k0, k1 = _ring64()
    steps = len(acc.G)
    assert acc.state_bytes_per_device == steps * 16 * 16 * 4 == 3 * 1024
    _budget(monkeypatch, copies, acc.state_bytes_per_device)
    waited = _waits(monkeypatch)
    tile = acc.G[0].shape

    def tiles():
        return sum(a.shape == tile for a in jax.live_arrays())

    others = tiles() - steps
    live = []
    dispatch = type(acc)._dispatch_ranges

    def counted(self, *args):
        dispatch(self, *args)
        live.append((tiles() - others) / steps)

    monkeypatch.setattr(type(acc), "_dispatch_ranges", counted)
    acc.add_grid(k0, k1)
    assert acc.dispatches >= 9
    assert acc.depth == copies - 2
    assert acc.gramian_copies_max == copies
    assert len(waited) == acc.dispatches - acc.depth
    assert max(live) <= acc.gramian_copies_max


def test_ring_loop_bound_and_donated_finalize_keep_the_gramian(monkeypatch):
    """The bounded loop and the finalize that takes G's own buffer give the
    Gramian of the unbounded loop, byte for byte."""
    free, k0, k1 = _ring64()
    free.add_grid(k0, k1)
    assert free.depth > free.dispatches
    with jax.enable_x64(True):
        expected = np.asarray(jax.device_get(free.finalize_sharded()))

    bounded, _, _ = _ring64()
    _budget(monkeypatch, 3, bounded.state_bytes_per_device)
    bounded.add_grid(k0, k1)
    assert bounded.depth == 1
    with jax.enable_x64(True):
        result = bounded.finalize_sharded(donate=True)
        assert bounded.G is None
        got = np.asarray(jax.device_get(result))
    assert got.dtype == expected.dtype
    np.testing.assert_array_equal(got, expected)


def test_ring_programs_are_lowered_once_for_every_dispatch_after_them():
    """A warm-up job (one main dispatch and one tail) prepares every
    dispatch of the next job: the programs' outputs keep the shardings of
    the accumulator's zeros, so the second main dispatch, fed the first
    one's outputs, finds its program in the jit cache."""
    step = 16 * 8
    warm, k0, _ = _ring64(blocks_per_dispatch=8)
    warm.add_grid(k0, k0 + step + 16)
    main, tail = warm._update, warm._update_tail
    sizes = (main._cache_size(), tail._cache_size())

    acc, _, _ = _ring64(blocks_per_dispatch=8)
    acc.add_grid(k0, k0 + 2 * step + 16)
    assert acc.dispatches == 3
    assert (acc._update, acc._update_tail) == (main, tail)
    assert (main._cache_size(), tail._cache_size()) == sizes


@pytest.mark.parametrize(
    "num_samples, dispatches", [(2504, 158), (17, 49)], ids=["kg1000", "platinum"]
)
def test_one_chip_cells_never_wait(num_samples, dispatches):
    """The whole-genome cells' Gramians (25 MB and 1.2 KB) get a depth past
    their dispatches per job on a v5e chip's 15.75 GB."""
    from spark_examples_tpu.ops.devicegen import dispatch_depth, gramian_copies_max

    gramian_bytes = num_samples * num_samples * 4
    depth = dispatch_depth(gramian_bytes, 15_750_000_000)
    assert depth >= dispatches
    assert gramian_copies_max(dispatches, depth) == dispatches + 1


def test_dense_loop_adds_no_wait_where_the_depth_is_not_reached(monkeypatch):
    waited = _waits(monkeypatch)
    source = SyntheticGenomicsSource(num_samples=24, seed=3)
    acc = DeviceGenGramianAccumulator(
        num_samples=24,
        vs_keys=[source.genotype_stream_key("vs")],
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        block_size=16,
        blocks_per_dispatch=1,
    )
    k0, k1 = source.site_grid_range(Contig("2", 0, 100_000))
    acc.add_grid(k0, k1)
    assert acc.dispatches > 9
    assert waited == []
    assert acc.gramian_copies_max == acc.dispatches + 1


@pytest.mark.parametrize(
    "gramian_bytes, device_bytes, depth",
    [(2_501_600_256, 15_750_000_000, 1), (2_501_600_256, 4_000_000_000, 1),
     (625_250_000, 15_750_000_000, 8), (25_080_064, 15_750_000_000, 249)],
    ids=["50k-ring", "50k-small-chip", "25k-ring", "kg1000"],
)
def test_dispatch_depth(gramian_bytes, device_bytes, depth):
    from spark_examples_tpu.ops.devicegen import dispatch_depth

    assert dispatch_depth(gramian_bytes, device_bytes) == depth
