"""The operations and bytes a Gramian update needs, from its shapes, and
the table of peaks they are held against (``peaks.json``)."""

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


def least_seconds(num_samples: int, sites: int, device_kind: str) -> tuple:
    """(least seconds, which bound sets it) for one Gramian."""
    p = peaks(device_kind)
    ops_s = gramian_ops(num_samples, sites) / p["int8_ops_per_s"]
    bytes_s = gramian_bytes(num_samples) / p["hbm_bytes_per_s"]
    return (ops_s, "ops") if ops_s >= bytes_s else (bytes_s, "bytes")
