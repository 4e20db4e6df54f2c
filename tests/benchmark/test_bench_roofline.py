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
