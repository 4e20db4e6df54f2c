"""The operations and bytes a Gramian update needs, from its shapes, the
chip-to-chip bytes a samples-sharded one must exchange, and the table of
peaks they are held against (``peaks.json``)."""

from __future__ import annotations

import os

from benchmark.core import BENCH, load_json


class UnknownDevice(KeyError):
    """A device kind with no row in ``peaks.json``: never a default."""


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def gramian_ops(num_samples: int, sites: int) -> int:
    """int8 operations of ``G += XᵀX`` over ``sites`` rows of N columns,
    counting only the N(N+1)/2 distinct entries of the symmetric product
    (one multiply and one add each), so a symmetric-tile implementation
    cannot read above its peak."""
    return num_samples * (num_samples + 1) * sites


def gramian_bytes(num_samples: int, accum_bytes: int = 4) -> int:
    """Bytes every implementation must move: the genotypes are generated
    on the device from the site grid, so nothing is read from HBM that was
    not written there; the one unavoidable transfer is the finished N×N
    accumulator, written once."""
    return num_samples * num_samples * accum_bytes


def least_seconds(num_samples: int, sites: int, device_kind: str, chips: int = 1) -> tuple:
    """(least seconds, which bound sets it) for one Gramian computed on
    ``chips`` chips together: the work and the bytes against ``chips`` times
    one chip's peaks."""
    p = peaks(device_kind)
    ops_s = gramian_ops(num_samples, sites) / (chips * p["int8_ops_per_s"])
    bytes_s = gramian_bytes(num_samples) / (chips * p["hbm_bytes_per_s"])
    return (ops_s, "ops") if ops_s >= bytes_s else (bytes_s, "bytes")


def ring_bytes(num_samples: int, sites: int, chips: int, packed: bool) -> int:
    """The least bytes each chip must receive over the chip-to-chip links
    for a Gramian whose samples are split over ``chips``: the genotypes of
    every site for the samples the other chips hold, ``(chips − 1)/chips``
    of the cohort, one byte per genotype, or one bit when ``packed``."""
    genotypes = sites * num_samples * (chips - 1) // chips
    return -(-genotypes // 8) if packed else genotypes
