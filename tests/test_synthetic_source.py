"""Synthetic source: determinism, partition invariance, wire/packed agreement."""

import numpy as np
import pytest

from spark_examples_tpu.models.variant import VariantsBuilder
from spark_examples_tpu.sharding.contig import Contig, SexChromosomeFilter
from spark_examples_tpu.sources.base import ShardBoundary
from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource


def test_callsets_are_stable_and_sized(small_source):
    callsets = small_source.search_callsets(["vs-a"])
    assert len(callsets) == 40
    assert callsets[0]["id"] == "vs-a-0"
    assert callsets == small_source.search_callsets(["vs-a"])


@pytest.mark.parametrize(
    "set_ids, cohort_sizes",
    [
        (["vs-a"], {}),
        (["vs-a", "vs-b"], {"vs-b": 7}),
        (["vs-a", "vs-b", "vs-a"], {"vs-a": 12}),
    ],
    ids=["one-set", "two-sets-sized", "repeated-set"],
)
def test_search_callsets_equals_per_sample_names(set_ids, cohort_sizes):
    """Discovery builds exactly the per-sample ids and names, in order,
    each set once, each at its own cohort size."""
    source = SyntheticGenomicsSource(
        num_samples=40, seed=7, cohort_sizes=cohort_sizes
    )
    expected = [
        {"id": source.callset_id(v, i), "name": source.callset_name(v, i)}
        for v in dict.fromkeys(set_ids)
        for i in range(source.num_samples_for(v))
    ]
    assert source.search_callsets(set_ids) == expected


def test_callset_names_are_pinned():
    """The name rule ``S<tag>N<index>``, the tag the set key mod 90."""
    source = SyntheticGenomicsSource(num_samples=40, seed=7, cohort_sizes={"vs-b": 7})
    callsets = source.search_callsets(["vs-a", "vs-b"])
    assert callsets[0] == {"id": "vs-a-0", "name": "S36N00000"}
    assert callsets[39] == {"id": "vs-a-39", "name": "S36N00039"}
    assert callsets[46] == {"id": "vs-b-6", "name": "S40N00006"}
    assert source.callset_name("1000genomes", 12345) == "S03N12345"
    kg = SyntheticGenomicsSource(num_samples=2504, seed=42)
    assert kg.callset_name("10473108253681171589", 2503) == "S15N02503"


def test_search_callsets_hashes_each_set_once(monkeypatch):
    from spark_examples_tpu.sources import synthetic

    calls = []
    string_key = synthetic._string_key

    def counting(s):
        calls.append(s)
        return string_key(s)

    monkeypatch.setattr(synthetic, "_string_key", counting)
    source = SyntheticGenomicsSource(num_samples=5000, seed=7)
    callsets = source.search_callsets(["vs-a", "vs-b"])
    assert len(callsets) == 10_000
    assert calls == ["vs-a", "vs-b"]


def test_contigs_exclude_xy(small_source):
    names = {c.reference_name for c in small_source.get_contigs("vs", SexChromosomeFilter.EXCLUDE_XY)}
    assert "X" not in names and "Y" not in names
    assert "1" in names and "22" in names


def _collect(source, vsid, start, end):
    client = source.client()
    request = {
        "variantSetIds": [vsid],
        "referenceName": "17",
        "start": start,
        "end": end,
    }
    return list(client.search_variants(request, ShardBoundary.STRICT))


def test_partition_invariance(small_source):
    """Splitting a range in two yields exactly the whole-range records —
    the synthetic analog of ShardBoundary.STRICT double-count protection."""
    whole = _collect(small_source, "vs-a", 10_000, 14_000)
    left = _collect(small_source, "vs-a", 10_000, 12_000)
    right = _collect(small_source, "vs-a", 12_000, 14_000)
    assert [v["id"] for v in left + right] == [v["id"] for v in whole]
    assert (left + right) == whole


def test_records_are_deterministic(small_source):
    again = SyntheticGenomicsSource(num_samples=40, seed=7, variant_spacing=100)
    assert _collect(small_source, "vs-a", 0, 3_000) == _collect(again, "vs-a", 0, 3_000)


def test_different_seeds_differ():
    a = SyntheticGenomicsSource(num_samples=40, seed=1)
    b = SyntheticGenomicsSource(num_samples=40, seed=2)
    assert _collect(a, "vs", 0, 3_000) != _collect(b, "vs", 0, 3_000)


def test_wire_records_build_cleanly(small_source):
    for wire in _collect(small_source, "vs-a", 0, 5_000):
        built = VariantsBuilder.build(wire)
        assert built is not None
        _, variant = built
        assert variant.contig == "17"
        assert len(variant.calls) == 40
        if variant.reference_bases == "N":
            assert variant.alternate_bases is None
            assert all(not c.has_variation() for c in variant.calls)
        else:
            assert variant.alternate_bases is not None
            assert "AF" in variant.info


def test_packed_path_matches_wire_path(small_source):
    """The packed fast path and the JSON wire path must agree exactly."""
    contig = Contig("17", 0, 10_000)
    blocks = list(small_source.genotype_blocks("vs-a", contig, block_size=37))
    packed_by_pos = {}
    for block in blocks:
        for i, pos in enumerate(block["positions"]):
            packed_by_pos[int(pos)] = block["has_variation"][i]

    wire_by_pos = {}
    for wire in _collect(small_source, "vs-a", 0, 10_000):
        built = VariantsBuilder.build(wire)
        _, variant = built
        row = np.array(
            [1 if c.has_variation() else 0 for c in variant.calls], dtype=np.uint8
        )
        if row.any():
            wire_by_pos[variant.start] = row

    assert set(packed_by_pos) == set(wire_by_pos)
    for pos, row in wire_by_pos.items():
        np.testing.assert_array_equal(packed_by_pos[pos], row)


def test_genotypes_differ_across_variant_sets_but_sites_match(small_source):
    a = _collect(small_source, "vs-a", 0, 4_000)
    b = _collect(small_source, "vs-b", 0, 4_000)
    assert [v["start"] for v in a] == [v["start"] for v in b]
    assert [v.get("referenceBases") for v in a] == [v.get("referenceBases") for v in b]
    keys_a = [VariantsBuilder.build(v)[1].variant_key() for v in a]
    keys_b = [VariantsBuilder.build(v)[1].variant_key() for v in b]
    assert keys_a == keys_b  # joinable across datasets
    genotypes = lambda recs: [c["genotype"] for v in recs for c in v["calls"]]
    assert genotypes(a) != genotypes(b)


def test_af_filter_threshold_semantics(small_source):
    contig = Contig("17", 0, 30_000)
    all_blocks = list(small_source.genotype_blocks("vs-a", contig))
    filtered = list(
        small_source.genotype_blocks("vs-a", contig, min_allele_frequency=0.2)
    )
    afs = np.concatenate([b["af"] for b in filtered]) if filtered else np.array([])
    assert (afs.astype(np.float32) > np.float32(0.2)).all()
    n_all = sum(len(b["positions"]) for b in all_blocks)
    n_filtered = sum(len(b["positions"]) for b in filtered)
    assert 0 < n_filtered < n_all


def test_page_accounting(small_source):
    client = small_source.client()
    request = {
        "variantSetIds": ["vs"],
        "referenceName": "17",
        "start": 0,
        "end": 5_000,
    }
    records = list(client.search_variants(request, page_size=10))
    expected_pages = -(-len(records) // 10)
    assert client.counters.initialized_requests == expected_pages


def test_population_structure_separates_afs():
    source = SyntheticGenomicsSource(num_samples=60, seed=3, n_pops=3)
    contig = Contig("1", 0, 200_000)
    rows = np.concatenate(
        [b["has_variation"] for b in source.genotype_blocks("vs", contig)], axis=0
    ).astype(np.float64)
    pops = source._pops
    # Mean within-population correlation should exceed cross-population.
    freq = rows.mean(axis=0)
    centered = rows - rows.mean(axis=0, keepdims=True)
    cov = centered.T @ centered
    same = [
        cov[i, j]
        for i in range(60)
        for j in range(i + 1, 60)
        if pops[i] == pops[j]
    ]
    diff = [
        cov[i, j]
        for i in range(60)
        for j in range(i + 1, 60)
        if pops[i] != pops[j]
    ]
    assert np.mean(same) > np.mean(diff)


def test_reads_depth_and_determinism(small_source):
    client = small_source.client()
    request = {
        "readGroupSetIds": ["rgs-1"],
        "referenceName": "11",
        "start": 1_000,
        "end": 2_000,
    }
    reads = list(client.search_reads(request))
    assert reads
    assert reads == list(small_source.client().search_reads(request))
    for r in reads:
        assert 1_000 <= r["alignment"]["position"]["position"] < 2_000
        assert len(r["alignedSequence"]) == small_source.read_length
        assert len(r["alignedQuality"]) == small_source.read_length


def test_tumor_normal_differ_only_at_somatic_sites():
    source = SyntheticGenomicsSource(num_samples=4, seed=9, somatic_rate=0.01)
    normal = source.read_json("Normal-set", "1", 100_000_000, 0)
    tumor = source.read_json("Tumor-set", "1", 100_000_000, 0)
    positions = np.arange(100_000_000, 100_000_000 + source.read_length)
    somatic = source._is_somatic_site("1", positions)
    for i, (a, b) in enumerate(
        zip(normal["alignedSequence"], tumor["alignedSequence"])
    ):
        if a != b:
            assert somatic[i]
