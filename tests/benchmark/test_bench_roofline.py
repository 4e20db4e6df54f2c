"""The benchmark's operation and byte counts, and its table of peaks."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import roofline  # noqa: E402

WHOLE_GENOME_SITES = 39_466_223  # 22 autosomes at spacing 73 (bench.py)


@pytest.mark.parametrize(
    "n, sites, ops",
    [
        # N(N+1) per site: one multiply and one add per distinct entry.
        (2504, WHOLE_GENOME_SITES, 2504 * 2505 * 39_466_223),
        (17, WHOLE_GENOME_SITES, 17 * 18 * 39_466_223),
        (1, 1, 2),
        (3, 811, 3 * 4 * 811),
    ],
)
def test_gramian_ops_by_hand(n, sites, ops):
    assert roofline.gramian_ops(n, sites) == ops


def test_gramian_ops_whole_genome_value():
    assert roofline.gramian_ops(2504, WHOLE_GENOME_SITES) == 247_552_673_091_960


@pytest.mark.parametrize("n, nbytes", [(2504, 25_080_064), (17, 1_156), (1, 4)])
def test_gramian_bytes_by_hand(n, nbytes):
    assert roofline.gramian_bytes(n) == nbytes


def test_whole_genome_is_ops_bound_on_v5e():
    least, bound = roofline.least_seconds(2504, WHOLE_GENOME_SITES, "TPU v5 lite")
    assert bound == "ops"
    assert least == pytest.approx(247_552_673_091_960 / 393e12)


def test_platinum_is_ops_bound_on_v5e():
    least, bound = roofline.least_seconds(17, WHOLE_GENOME_SITES, "TPU v5 lite")
    assert bound == "ops"
    assert least == pytest.approx(17 * 18 * WHOLE_GENOME_SITES / 393e12)


def test_peaks_of_v5e():
    p = roofline.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5e", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks(kind)
    with pytest.raises(roofline.UnknownDevice):
        roofline.least_seconds(2504, 1, kind)


CHR17_SITES = 1_112_264  # chr17 at spacing 73


@pytest.mark.parametrize(
    "n, sites, chips, seconds",
    [
        # N(N+1)·sites over chips × 393e12: 2504·2505·39,466,223 / 393e12.
        (2504, WHOLE_GENOME_SITES, 1, 247_552_673_091_960 / 393e12),
        # 50,000·50,001·1,112,264 = 2,780,715,613,200,000 over 4 × 393e12.
        (50_000, CHR17_SITES, 4, 2_780_715_613_200_000 / 1_572e12),
        # 25,000·25,001·1,112,264 = 695,192,806,600,000 over 4 × 393e12.
        (25_000, CHR17_SITES, 4, 695_192_806_600_000 / 1_572e12),
    ],
)
def test_least_seconds_by_chips(n, sites, chips, seconds):
    least, bound = roofline.least_seconds(n, sites, "TPU v5 lite", chips)
    assert bound == "ops"
    assert least == pytest.approx(seconds, rel=1e-12)


def test_bytes_bound_scales_with_chips():
    # One site of 50,000 samples: 5,000,100,000 ops against 1e10 bytes.
    one, bound = roofline.least_seconds(50_000, 1, "TPU v5 lite", 1)
    assert bound == "bytes" and one == pytest.approx(1e10 / 819e9)
    four, bound = roofline.least_seconds(50_000, 1, "TPU v5 lite", 4)
    assert bound == "bytes" and four == pytest.approx(1e10 / (4 * 819e9))


@pytest.mark.parametrize(
    "n, sites, chips, packed, nbytes",
    [
        (64, 10, 4, False, 480),  # 10 sites × 48 other samples
        (64, 10, 4, True, 60),  # 480 bits
        (17, 1, 4, False, 12),  # 12.75 genotypes, whole ones
        (17, 1, 4, True, 2),  # 12 bits round up to 2 bytes
        (2504, 100, 1, True, 0),  # one chip exchanges nothing
        # chr17 at 50,000 samples on four chips: 41,709,900,000 genotypes.
        (50_000, CHR17_SITES, 4, False, 41_709_900_000),
        (50_000, CHR17_SITES, 4, True, 5_213_737_500),
    ],
)
def test_ring_bytes_by_hand(n, sites, chips, packed, nbytes):
    assert roofline.ring_bytes(n, sites, chips, packed) == nbytes


def test_ici_peak_of_v5e():
    # 1,600 Gbps of chip-to-chip interconnect per chip.
    assert roofline.peaks("TPU v5 lite")["ici_bytes_per_s"] == 1_600e9 / 8
