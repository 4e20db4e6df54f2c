"""The comparison that decides ``correct`` fails what it must.

Each test drives a whole dry run of a cell (``benchmark/run.py --dry``
skips the look for a chip) with the timed path broken underneath, and sees
``correct`` come out false: a step that returns its state unchanged, half
of a job's sites left out, an answer altered where it is produced, an
answer that never comes, and, on a samples-sharded ring over four
devices, a ring step whose tile exchange is left out and a shard that
keeps its state. The control, the plain reference one rung down the
precision ladder, fails the cells' limits at test size too.

The served cell is not in ``BENCHMARK.json`` (``PERF.md``, Open
questions), nor is the four-device ring cell (a 64-sample cohort on a 1x4
mesh, ``data/ring64.json``, held to kg1000's limits); their harness is
driven here through a manifest that holds them.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import core, readings  # noqa: E402
from benchmark import run as bench_run  # noqa: E402


SERVED = "kg1000.brca1-served"
RING = "ring64.wgs-batch"


@pytest.fixture()
def served_cell(monkeypatch):
    """The manifest with the served cell and its metrics added."""
    original = core.manifest

    def with_served():
        doc = original()
        doc["workloads"].append(
            {"name": SERVED, "config": "kg1000-2504", "traffic": "brca1-served", "chips": 1,
             "why": "BRCA1-length windows through the HTTP service, open loop"}
        )
        for name in ("served_p50_s", "served_p95_s"):
            doc["end_to_end"].append(
                {"name": name, "unit": "s", "better": "lower", "bound": 0.25,
                 "source": "host_clock", "workloads": [SERVED]}
            )
        for name, layer in (("idle_share.served", "device"),
                            ("finalize_ms.served", "centering and eigensolve"),
                            ("queue_wait_ms.served", "entry and serve")):
            doc["per_layer"].append(
                {"name": name, "unit": "ms", "better": "lower", "source": "device_trace",
                 "layer": layer, "moves": "served_p95_s", "workloads": [SERVED]}
            )
        return doc

    monkeypatch.setattr(core, "manifest", with_served)


@pytest.fixture()
def ring_cell(monkeypatch):
    """The manifest with a four-device ring cell: the configuration's
    flags put 64 samples on a 1x4 mesh through the sharded strategy."""
    manifest, load_limits = core.manifest, core.load_limits

    def with_ring():
        doc = manifest()
        doc["configs"].append(
            {"name": "ring64", "source": "test cohort", "file": "tests/benchmark/data/ring64.json",
             "reduced": [], "why": "the samples-sharded ring at test size"}
        )
        doc["workloads"].append(
            {"name": RING, "config": "ring64", "traffic": "wgs-batch", "chips": 4,
             "why": "a Gramian sharded over four devices, tiles exchanged in a ring"}
        )
        for metric in doc["end_to_end"] + doc["per_layer"]:
            if "kg1000.wgs-batch" in metric.get("workloads", []):
                metric["workloads"].append(RING)
        return doc

    def limits(cell):
        return load_limits("kg1000.wgs-batch" if cell == RING else cell)

    monkeypatch.setattr(core, "manifest", with_ring)
    monkeypatch.setattr(core, "load_limits", limits)


def _run(capfd, cell, trace=0):
    code = bench_run.main(
        ["--workload", cell, "--seed", "2718281828", "--seconds", "1", "--trace", str(trace), "--dry"]
    )
    assert code == 0
    out = capfd.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.fixture()
def state_unchanged(monkeypatch):
    """Every odd-numbered Gramian dispatch (the first, the third, ...)
    returns its state unchanged."""
    from spark_examples_tpu.ops import devicegen

    def unchanged(original):
        def broken(self, *args, **kwargs):
            before = (self.G, self.variant_rows, self.kept_sites)
            original(self, *args, **kwargs)
            if self.dispatches % 2 == 1:
                self.G, self.variant_rows, self.kept_sites = before

        return broken

    # One slice dispatches one range at a time; a data-parallel mesh (the
    # served worker over every CPU device here) round-robins ranges.
    for cls, name in (
        (devicegen.DeviceGenGramianAccumulator, "_dispatch_single"),
        (devicegen._GridDispatchAccumulator, "_dispatch_ranges"),
    ):
        monkeypatch.setattr(cls, name, unchanged(getattr(cls, name)))


@pytest.fixture()
def half_grid(monkeypatch):
    """Half of each contig's sites left out of the device ingest."""
    from spark_examples_tpu.ops import devicegen

    acc = devicegen.DeviceGenGramianAccumulator
    original = acc.add_grid

    def broken(self, first, last):
        original(self, first, first + (last - first) // 2)

    monkeypatch.setattr(acc, "add_grid", broken)


@pytest.fixture()
def half_sites(monkeypatch, half_grid):
    """Half of each job's sites left out, on the device ingest of a lone
    served job and on the packed lanes of a fused group alike."""
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    original = SyntheticGenomicsSource.genotype_blocks

    def broken(self, *args, **kwargs):
        for block in original(self, *args, **kwargs):
            half = len(block["positions"]) // 2
            yield {key: value[:half] for key, value in block.items()}

    monkeypatch.setattr(SyntheticGenomicsSource, "genotype_blocks", broken)


@pytest.fixture()
def ring_programs_rebuilt():
    """Ring update programs built afresh inside the test, and dropped after
    it, so that a planted fault neither misses a cached program nor
    outlives its test."""
    from spark_examples_tpu.ops import devicegen

    devicegen._ring_update.cache_clear()
    yield
    devicegen._ring_update.cache_clear()


@pytest.fixture()
def ring_step_dropped(monkeypatch, ring_programs_rebuilt):
    """The ring update leaves out its second step's tile exchange: each
    device dots the tile it already holds again, in its next neighbour's
    place."""
    import jax.numpy as jnp
    from jax import lax

    from spark_examples_tpu.ops import gramian

    def broken(G_local, X_cols, samples_axis, operand_dtype, packed=False):
        D = gramian.axis_size(samples_axis)
        i = lax.axis_index(samples_axis)
        n_local = X_cols.shape[1] * 8 if packed else X_cols.shape[1]

        def unpack(tile):
            return gramian._unpack_bits(tile, n_local) if packed else tile

        mine = unpack(X_cols).astype(operand_dtype).T
        tile, zero = X_cols, jnp.int32(0)
        for k in range(D):
            col = (((i + k) % D) * n_local).astype(jnp.int32)
            t = jnp.matmul(mine, unpack(tile).astype(operand_dtype), preferred_element_type=G_local.dtype)
            G_local = lax.dynamic_update_slice(
                G_local, lax.dynamic_slice(G_local, (zero, col), (n_local, n_local)) + t, (zero, col)
            )
            if k != 1:
                tile = lax.ppermute(tile, samples_axis, [((p + 1) % D, p) for p in range(D)])
        return G_local

    monkeypatch.setattr(gramian, "_ring_tiles", broken)


@pytest.fixture()
def shard_unchanged(monkeypatch, ring_programs_rebuilt):
    """The first device of the samples axis returns its row tile unchanged
    from every ring update."""
    import jax.numpy as jnp
    from jax import lax

    from spark_examples_tpu.ops import gramian

    original = gramian._ring_tiles

    def broken(G_local, X_cols, samples_axis, operand_dtype, packed=False):
        updated = original(G_local, X_cols, samples_axis, operand_dtype, packed=packed)
        return jnp.where(lax.axis_index(samples_axis) == 0, G_local, updated)

    monkeypatch.setattr(gramian, "_ring_tiles", broken)


@pytest.fixture()
def answer_altered(monkeypatch):
    """One coordinate of every job's components altered where made."""
    from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver

    original = VariantsPcaDriver.compute_pca

    def broken(self, similarity):
        result = original(self, similarity)
        name, pcs = result[0]
        result[0] = (name, [pcs[0] + 0.25] + list(pcs[1:]))
        return result

    monkeypatch.setattr(VariantsPcaDriver, "compute_pca", broken)


@pytest.fixture()
def answer_never_comes(monkeypatch):
    """Every third served job fails in the executor."""
    from benchmark import served
    from spark_examples_tpu.pipeline import pca_driver

    original = pca_driver.VariantsPcaDriver.compute_pca
    warm_up = served.Service.warm_up
    calls = {"n": 0, "window": False}

    def warmed(self, seed):
        warm_up(self, seed)
        calls["window"] = True

    def broken(self, similarity):
        if calls["window"]:
            calls["n"] += 1
            if calls["n"] % 3 == 1:
                raise RuntimeError("planted fault")
        return original(self, similarity)

    monkeypatch.setattr(served.Service, "warm_up", warmed)
    monkeypatch.setattr(pca_driver.VariantsPcaDriver, "compute_pca", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_grid", "answer_altered"])
@pytest.mark.parametrize("cell", ["kg1000.wgs-batch", "platinum.wgs-batch"])
def test_batch_fault_is_not_correct(fault, cell, request, capfd):
    request.getfixturevalue(fault)
    line = _run(capfd, cell)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())


@pytest.mark.parametrize(
    "fault", ["state_unchanged", "ring_step_dropped", "shard_unchanged", "answer_altered"]
)
def test_ring_fault_is_not_correct(fault, request, capfd, ring_cell):
    request.getfixturevalue(fault)
    line = _run(capfd, RING)
    assert line["correct"] is False
    broken = "pc_eigenspace_gap" if fault == "answer_altered" else "gramian_max_abs_diff"
    assert line["compared"][broken]["value"] > line["compared"][broken]["limit"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_sites", "answer_altered", "answer_never_comes"])
def test_served_fault_is_not_correct(fault, request, capfd, served_cell):
    request.getfixturevalue(fault)
    line = _run(capfd, SERVED)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", ["kg1000.wgs-batch", "platinum.wgs-batch", SERVED, RING])
def test_sound_run_is_correct(cell, capfd, served_cell, ring_cell):
    line = _run(capfd, cell)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["count"] == core.cell(cell)["chips"]


def test_served_traced_run_reads_its_metrics(capfd, served_cell):
    line = _run(capfd, SERVED, trace=1)
    assert line["correct"] is True
    # The CPU trace has no device plane; the daemon's queue waits are read.
    assert set(line["metrics"]) == {"queue_wait_ms.served"}


@pytest.mark.parametrize(
    "cell, seed", [("kg1000.wgs-batch", 3), ("platinum.wgs-batch", 4), (SERVED, 5), (RING, 6)]
)
def test_control_is_not_correct(cell, seed, served_cell, ring_cell):
    doc = core.dry_overrides(core.cell(cell))
    numbers = readings.control_numbers(doc, seed)
    limits = doc["limits"]
    assert not core.judge(numbers, limits)
    assert numbers["pc_eigenspace_gap"] > limits["pc_eigenspace_gap"]
    if "gramian_max_abs_diff" in numbers:
        assert numbers["gramian_max_abs_diff"] > limits["gramian_max_abs_diff"]
    assert np.isfinite(list(numbers.values())).all()
