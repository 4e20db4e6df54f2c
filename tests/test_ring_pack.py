"""Parity matrix + telemetry for the bit-packed, overlapped ring Gramian.

The packed ring wire format (``--ring-pack-bits``) must be BIT-EXACT
against both the unpacked oracle (``off``) and the host NumPy reference —
across mesh shapes, at cohort widths that are not multiples of 8 (ragged →
pack-width padding), for multi-set (merged-cohort) device generation, and
when count-valued blocks force the per-flush fallback to the unpacked
kernel. The ``gramian_ring_bytes`` counter is asserted against the one
audited traffic formula (``parallel/mesh.py:ring_traffic_bytes``) so the
8× claim in the manifests is arithmetic, not vibes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_examples_tpu.ops.gramian import (
    ShardedGramianAccumulator,
    _pack_bits_device,
    _unpack_bits,
    gramian_reference,
    resolve_ring_pack,
)
from spark_examples_tpu.parallel.mesh import (
    DATA_AXIS,
    RING_PACK_MULTIPLE,
    SAMPLES_AXIS,
    make_mesh,
    padded_cohort,
    ring_traffic_bytes,
)


def _random_rows(rng, n_variants, n_samples, p=0.3):
    return (rng.random((n_variants, n_samples)) < p).astype(np.uint8)


# ------------------------------------------------------------- pack/unpack


def test_pack_unpack_round_trip_ragged_widths():
    """Device unpack inverts np.packbits for every ragged width, and the
    device pack matches np.packbits bit-for-bit at byte-aligned widths."""
    rng = np.random.default_rng(0)
    for width in [1, 3, 7, 8, 9, 15, 16, 21, 24, 40, 64, 100]:
        bits = (rng.random((13, width)) < 0.4).astype(np.uint8)
        packed = np.packbits(bits, axis=-1)
        out = np.asarray(_unpack_bits(jnp.asarray(packed), width))
        np.testing.assert_array_equal(out, bits, err_msg=f"width={width}")
        if width % 8 == 0:
            dev = np.asarray(_pack_bits_device(jnp.asarray(bits)))
            np.testing.assert_array_equal(dev, packed, err_msg=f"width={width}")


def test_resolve_ring_pack_contract():
    assert resolve_ring_pack("auto") and resolve_ring_pack("on")
    assert not resolve_ring_pack("off")
    with pytest.raises(ValueError):
        resolve_ring_pack("sometimes")


def test_padded_cohort_rule():
    # Unpacked: multiple of the samples axis; packed: of 8x the samples
    # axis (every device tile a whole number of bytes).
    assert padded_cohort(21, 4, pack=False) == 24
    assert padded_cohort(21, 4, pack=True) == 32
    assert padded_cohort(64, 4, pack=True) == 64
    assert padded_cohort(64, 4, pack=True) // 4 % RING_PACK_MULTIPLE == 0


# ------------------------------------------------------ host-fed parity


MESHES = [
    {SAMPLES_AXIS: 4},
    {DATA_AXIS: 2, SAMPLES_AXIS: 2},
    {DATA_AXIS: 1, SAMPLES_AXIS: 8},
]


@pytest.mark.parametrize(
    "shape", MESHES, ids=["s4", "d2s2", "d1s8"]
)
@pytest.mark.parametrize("n_samples", [24, 21], ids=["aligned", "ragged"])
def test_packed_ring_parity_matrix(shape, n_samples):
    """packed == --ring-pack-bits off oracle == gramian_reference, across
    mesh shapes, including an N_local not divisible by 8 (n=21 over 4
    slices leaves ragged local widths the pack padding must absorb)."""
    mesh = make_mesh(dict(shape))
    rng = np.random.default_rng(11)
    rows = _random_rows(rng, 150, n_samples)
    results = {}
    for mode in ("on", "off"):
        acc = ShardedGramianAccumulator(
            n_samples, mesh, block_size=32, pack_bits=mode
        )
        for chunk in np.array_split(rows, 4):
            acc.add_rows(chunk)
        results[mode] = acc.finalize()
    reference = gramian_reference(rows)
    np.testing.assert_array_equal(results["off"], reference)
    np.testing.assert_array_equal(results["on"], results["off"])


def test_packed_ring_count_rows_fall_back_per_flush():
    """Count-valued blocks (same-set joins) cannot bit-pack; with packing
    on they must transparently ride the unpacked kernel — mixed with
    packed binary flushes in one accumulator — and stay exact."""
    mesh = make_mesh({SAMPLES_AXIS: 2})
    binary = _random_rows(np.random.default_rng(3), 4, 5)
    counts = np.array([[2, 1, 0, 3, 1], [0, 3, 1, 0, 2]], dtype=np.uint8)
    acc = ShardedGramianAccumulator(5, mesh, block_size=4, pack_bits="on")
    acc.add_rows(binary)  # fills one block exactly -> packed flush
    acc.add_rows(counts)  # partial block with counts -> unpacked flush
    all_rows = np.concatenate([binary, counts]).astype(np.int64)
    np.testing.assert_array_equal(acc.finalize(), all_rows.T @ all_rows)


def test_packed_ring_exact_int_parity():
    mesh = make_mesh({SAMPLES_AXIS: 4})
    rows = _random_rows(np.random.default_rng(8), 90, 21)
    for mode in ("on", "off"):
        acc = ShardedGramianAccumulator(
            21, mesh, block_size=16, exact_int=True, pack_bits=mode
        )
        acc.add_rows(rows)
        np.testing.assert_array_equal(acc.finalize(), gramian_reference(rows))


# ------------------------------------------------- device-generated parity


def _ring_device_acc(source, mesh, mode, vs_keys=None, set_sizes=None):
    from spark_examples_tpu.ops.devicegen import DeviceGenRingGramianAccumulator

    kwargs = dict(
        num_samples=source.num_samples,
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        mesh=mesh,
        block_size=16,
        blocks_per_dispatch=2,
        n_pops=source.n_pops,
        pack_bits=mode,
    )
    if vs_keys is None:
        kwargs["vs_key"] = source.genotype_stream_key("vs")
    else:
        kwargs["vs_key"] = vs_keys
        if set_sizes is not None:
            kwargs["set_sizes"] = set_sizes
            kwargs["pops_per_set"] = [source.populations] * len(set_sizes)
    return DeviceGenRingGramianAccumulator(**kwargs)


def test_devicegen_ring_packed_parity_single_set():
    from spark_examples_tpu.sharding.contig import Contig
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    mesh = make_mesh({DATA_AXIS: 2, SAMPLES_AXIS: 4})
    source = SyntheticGenomicsSource(num_samples=21, seed=9)  # ragged width
    contig = Contig("4", 5_000, 95_000)
    k0, k1 = source.site_grid_range(contig)
    finals = {}
    for mode in ("on", "off"):
        acc = _ring_device_acc(source, mesh, mode)
        acc.add_grid(k0, k1)
        finals[mode] = acc.finalize()
        if mode == "on":
            assert acc.n_local % RING_PACK_MULTIPLE == 0
    np.testing.assert_array_equal(finals["on"], finals["off"])


def test_devicegen_ring_packed_parity_multiset_merged_cohort():
    """The merged-cohort (multi-set) ring: concatenated per-set column
    blocks through the packed wire equal the unpacked oracle bit for bit,
    and the padded column space honors the pack-width invariant."""
    from spark_examples_tpu.sharding.contig import Contig
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    mesh = make_mesh({SAMPLES_AXIS: 4})
    source = SyntheticGenomicsSource(num_samples=9, seed=5)
    contig = Contig("7", 0, 60_000)
    k0, k1 = source.site_grid_range(contig)
    vs_keys = [
        source.genotype_stream_key("set-a"),
        source.genotype_stream_key("set-b"),
    ]
    finals = {}
    for mode in ("on", "off"):
        acc = _ring_device_acc(
            source, mesh, mode, vs_keys=vs_keys, set_sizes=[9, 9]
        )
        acc.add_grid(k0, k1)
        finals[mode] = acc.finalize()
        assert finals[mode].shape == (18, 18)
        if mode == "on":
            assert acc.padded % (4 * RING_PACK_MULTIPLE) == 0
    np.testing.assert_array_equal(finals["on"], finals["off"])


# ------------------------------------------------------------ telemetry


def test_ring_bytes_counter_matches_formula_and_shows_8x():
    from spark_examples_tpu.obs.metrics import (
        GRAMIAN_RING_BYTES,
        GRAMIAN_RING_FLUSH_SECONDS,
        MetricsRegistry,
    )

    mesh = make_mesh({SAMPLES_AXIS: 4})
    n = 64  # local width 16 in both wire formats -> identical work, 8x exact
    rows = _random_rows(np.random.default_rng(5), 64, n)
    recorded = {}
    for mode in ("on", "off"):
        registry = MetricsRegistry()
        acc = ShardedGramianAccumulator(
            n, mesh, block_size=32, pack_bits=mode, registry=registry
        )
        acc.add_rows(rows)
        acc.finalize()
        recorded[mode] = registry.value(GRAMIAN_RING_BYTES)
        # Two full 32-row flushes, each one ring circulation.
        expected = 2 * ring_traffic_bytes(32, 4, 16, packed=(mode == "on"))
        assert recorded[mode] == expected == acc.ring_bytes_total
        seconds = registry.value(GRAMIAN_RING_FLUSH_SECONDS)
        assert seconds["count"] == 2
    assert recorded["off"] == 8 * recorded["on"] > 0


def test_devicegen_ring_bytes_accounts_ragged_final_byte():
    """Device-generation ring traffic: padded vs valid capacity tracked,
    two half-ring permutes per site on four devices, and the
    packed/unpacked byte ratio reflects the pack-width padding of a ragged
    cohort (21 -> widths 8 packed-padded vs 6 unpacked)."""
    from spark_examples_tpu.sharding.contig import Contig
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    mesh = make_mesh({SAMPLES_AXIS: 4})
    source = SyntheticGenomicsSource(num_samples=21, seed=9)
    contig = Contig("4", 5_000, 95_000)
    k0, k1 = source.site_grid_range(contig)
    byte_totals = {}
    for mode in ("on", "off"):
        acc = _ring_device_acc(source, mesh, mode)
        acc.add_grid(k0, k1)
        assert acc.sites_capacity >= acc.sites_valid == k1 - k0
        byte_totals[mode] = acc.ring_bytes_total
        expected = ring_traffic_bytes(
            acc.sites_capacity, 4, acc.n_local, packed=(mode == "on"),
            permutes=2,
        )
        assert acc.ring_bytes_total == expected
    # Ragged cohort: unpacked n_local=6 (padded 24), packed n_local=8
    # (padded 32, 1 byte wide) -> the reduction is 6x here, 8x only at
    # byte-aligned widths ("ragged final byte accounted").
    assert byte_totals["off"] == 6 * byte_totals["on"] > 0


def test_driver_publishes_ring_bytes_for_device_ingest(tmp_path):
    """End to end through the CLI driver: a sharded synthetic run lands
    gramian_ring_bytes + devicegen_sites_capacity in its manifest, and
    packed results equal the oracle's result rows exactly."""
    from spark_examples_tpu.obs.manifest import (
        manifest_metric_value,
        read_manifest,
    )
    from spark_examples_tpu.obs.metrics import (
        DEVICEGEN_SITES_CAPACITY,
        GRAMIAN_RING_BYTES,
    )
    from spark_examples_tpu.pipeline import pca_driver

    lines = {}
    values = {}
    for mode in ("on", "off"):
        path = tmp_path / f"{mode}.json"
        lines[mode] = pca_driver.run(
            [
                "--num-samples", "64",
                "--references", "1:0:300000",
                "--mesh-shape", "1,4",
                "--similarity-strategy", "sharded",
                "--block-size", "64",
                "--ring-pack-bits", mode,
                "--metrics-json", str(path),
            ]
        )
        doc = read_manifest(str(path))
        values[mode] = manifest_metric_value(doc, GRAMIAN_RING_BYTES)
        assert manifest_metric_value(doc, DEVICEGEN_SITES_CAPACITY) > 0
    assert lines["on"] == lines["off"]
    assert values["off"] == 8 * values["on"] > 0


@pytest.mark.parametrize(
    "samples, mode",
    [(2, "on"), (3, "on"), (3, "off"), (4, "on"), (4, "off"), (8, "on")],
    ids=["s2", "s3", "s3-unpacked", "s4", "s4-unpacked", "s8"],
)
def test_driver_half_ring_attrs_and_gramian(samples, mode):
    """A device-generation job through the driver on a 1xD ring: the
    ``ingest`` span counts ⌊D/2⌋+1 dots per block, the half ring's state
    and ⌊D/2⌋ permutes of ring bytes; its ``finalize`` child counts
    D-1-⌊D/2⌋ mirrored blocks; and the donated finalize's Gramian equals
    the host-fed full ring's and the reference, bit for bit."""
    from spark_examples_tpu.config import PcaConf
    from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    n = 22
    source = SyntheticGenomicsSource(num_samples=n, seed=31)
    conf = PcaConf.parse(
        ["--ingest", "device", "--num-samples", str(n), "--block-size", "64",
         "--references", "2:0:150000", "--mesh-shape", f"1,{samples}",
         "--similarity-strategy", "sharded", "--ring-pack-bits", mode]
    )
    driver = VariantsPcaDriver(conf, source, devices=jax.devices()[:samples])
    contigs = conf.get_contigs(source, conf.variant_set_id)
    with jax.enable_x64(True):
        got = np.asarray(jax.device_get(driver.get_similarity_device_gen(contigs)))

    ingest = driver.spans.find("ingest")
    finalize = driver.spans.find("ingest/finalize")
    steps = samples // 2 + 1
    padded = padded_cohort(n, samples, pack=(mode == "on"))
    n_local = padded // samples
    assert ingest.attrs["ring_dots_per_block"] == steps
    assert finalize.attrs["ring_mirrored_tiles"] == samples - steps
    assert ingest.attrs["state_bytes_per_device"] == steps * n_local * n_local * 4
    assert ingest.attrs["gramian_bytes_per_device"] == n_local * padded * 4
    assert ingest.attrs["ring_bytes"] == ring_traffic_bytes(
        ingest.attrs["sites_capacity"], samples, n_local, mode == "on",
        samples // 2,
    )

    rows = np.concatenate(
        [
            block["has_variation"]
            for contig in contigs
            for block in source.genotype_blocks(
                conf.variant_set_id[0], contig, block_size=512,
                min_allele_frequency=conf.min_allele_frequency,
            )
        ]
    )
    full = ShardedGramianAccumulator(
        n, make_mesh({SAMPLES_AXIS: samples}), block_size=64, exact_int=True,
        pack_bits=mode,
    )
    full.add_rows(rows)
    np.testing.assert_array_equal(got[:n, :n], full.finalize())
    np.testing.assert_array_equal(got[:n, :n], gramian_reference(rows))


# ------------------------------------------------------------ plan checks


def _plan(argv, devices=None):
    from spark_examples_tpu.check.plan import validate_plan
    from spark_examples_tpu.config import PcaConf, build_pca_parser

    conf = PcaConf._from_namespace(build_pca_parser().parse_args(argv))
    return validate_plan(conf, plan_devices=devices)


def test_plan_packed_geometry_honors_pack_width_invariant():
    report = _plan(
        [
            "--mesh-shape", "1,4",
            "--similarity-strategy", "sharded",
            "--num-samples", "100",
        ],
        devices=4,
    )
    assert report.ok
    assert report.geometry["ring_pack_bits"] == "packed"
    assert report.geometry["ring_local_columns"] % RING_PACK_MULTIPLE == 0
    # 100 over 4x8 -> 128; auto-rounded, warned, never rejected.
    assert any(i.code == "cohort-padding" for i in report.issues)
    packed_flush = report.geometry["ring_bytes_per_flush"]
    oracle = _plan(
        [
            "--mesh-shape", "1,4",
            "--similarity-strategy", "sharded",
            "--num-samples", "100",
            "--ring-pack-bits", "off",
        ],
        devices=4,
    )
    assert oracle.ok
    assert oracle.geometry["ring_pack_bits"] == "unpacked"
    # 100 -> 104 unpacked (multiple of 4), width 26 vs packed width 4.
    assert oracle.geometry["ring_bytes_per_flush"] > 6 * packed_flush


def test_plan_rejects_sharded_geometry_past_hbm():
    report = _plan(
        [
            "--mesh-shape", "1,2",
            "--similarity-strategy", "sharded",
            "--num-samples", "300000",
        ],
        devices=2,
    )
    assert not report.ok
    assert any(i.code == "sharded-exceeds-hbm" for i in report.issues)


_KG50K = [
    "--ingest", "device", "--block-size", "16384", "--num-samples", "50000",
    "--mesh-shape", "1,4",
]
_AUTOSOMES = ",".join(
    f"{name}:0:{end}"
    for name, end in (
        ("1", 249250621), ("2", 243199373), ("3", 198022430), ("4", 191154276),
        ("5", 180915260), ("6", 171115067), ("7", 159138663), ("8", 146364022),
        ("9", 141213431), ("10", 135534747), ("11", 135006516), ("12", 133851895),
        ("13", 115169878), ("14", 107349540), ("15", 102531392), ("16", 90354753),
        ("17", 81195210), ("18", 78077248), ("19", 59128983), ("20", 63025520),
        ("21", 48129895), ("22", 51304566),
    )
)


def test_plan_sizes_the_50k_ring_by_the_loops_copies_not_its_dispatches():
    """50,000 samples on 1x4 through the auto strategy (the dense Gramian
    cannot fit, so the ring is planned): chr17 and the whole genome ask the
    same per-device bytes, three live copies of the half ring's state,
    three 0.63 GB step tiles (the loop queues one dispatch behind the
    running one), and one 2.5 GB row tile, inside 80% of a 16 GiB chip."""
    from spark_examples_tpu.ops.gramian import _DEFAULT_DEVICE_BYTES, DENSE_HBM_FRACTION

    reports = [
        _plan(_KG50K + ["--references", refs], devices=4)
        for refs in ("17:0:81195210", _AUTOSOMES)
    ]
    assert all(r.ok for r in reports), [r.format() for r in reports]
    need = {r.geometry["ring_hbm_bytes_per_device"] for r in reports}
    assert len(need) == 1
    (bytes_per_device,) = need
    tile = reports[0].geometry["sharded_tile_bytes_per_device"]
    assert tile == 12504 * 50016 * 4
    state = reports[0].geometry["ring_state_bytes_per_device"]
    assert state == 3 * 12504 * 12504 * 4
    assert reports[0].geometry["gramian_copies_max"] == 3
    assert bytes_per_device == 3 * state + tile <= DENSE_HBM_FRACTION * _DEFAULT_DEVICE_BYTES


def test_plan_rejects_a_three_copy_50k_ring_on_a_small_chip(monkeypatch):
    """On a 4 GB device the loop still keeps three 1.9 GB copies of the
    half ring's state (it queues at least one dispatch): the plan refuses
    what the run could not hold."""
    from spark_examples_tpu.ops import gramian

    monkeypatch.setattr(gramian, "_DEFAULT_DEVICE_BYTES", 4_000_000_000)
    report = _plan(_KG50K + ["--references", "17:0:81195210"], devices=4)
    assert not report.ok
    assert report.geometry["gramian_copies_max"] == 3
    assert any(
        i.code == "sharded-exceeds-hbm" and "3 live copies" in i.message
        for i in report.issues
    )


def test_plan_rejects_bogus_ring_pack_value():
    from spark_examples_tpu.check.plan import validate_plan
    from spark_examples_tpu.config import PcaConf

    conf = PcaConf()
    conf.ring_pack_bits = "sometimes"
    report = validate_plan(conf, plan_devices=1)
    assert not report.ok
    assert any(i.code == "ring-pack-bits" for i in report.issues)
