"""The comparison that decides ``correct`` fails what it must.

Each test drives a whole dry run of a cell (``benchmark/run.py --dry``
skips the look for a chip) with the timed path broken underneath, and sees
``correct`` come out false: a step that returns its state unchanged, half
of a job's sites left out, an answer altered where it is produced, an
answer that never comes. The control, the plain reference one rung down
the precision ladder, fails the cells' limits at test size too. One cell
runs on one chip, so there is no exchange between chips to leave out.

The served cell is not in ``BENCHMARK.json`` (``PERF.md``, Open
questions); its harness is driven here through a manifest that holds it.
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


@pytest.mark.parametrize("fault", ["state_unchanged", "half_sites", "answer_altered", "answer_never_comes"])
def test_served_fault_is_not_correct(fault, request, capfd, served_cell):
    request.getfixturevalue(fault)
    line = _run(capfd, SERVED)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", ["kg1000.wgs-batch", "platinum.wgs-batch", SERVED])
def test_sound_run_is_correct(cell, capfd, served_cell):
    line = _run(capfd, cell)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1


def test_served_traced_run_reads_its_metrics(capfd, served_cell):
    line = _run(capfd, SERVED, trace=1)
    assert line["correct"] is True
    # The CPU trace has no device plane; the daemon's queue waits are read.
    assert set(line["metrics"]) == {"queue_wait_ms.served"}


@pytest.mark.parametrize(
    "cell, seed", [("kg1000.wgs-batch", 3), ("platinum.wgs-batch", 4), (SERVED, 5)]
)
def test_control_is_not_correct(cell, seed, served_cell):
    doc = core.dry_overrides(core.cell(cell))
    numbers = readings.control_numbers(doc, seed)
    limits = doc["limits"]
    assert not core.judge(numbers, limits)
    assert numbers["pc_eigenspace_gap"] > limits["pc_eigenspace_gap"]
    if "gramian_max_abs_diff" in numbers:
        assert numbers["gramian_max_abs_diff"] > limits["gramian_max_abs_diff"]
    assert np.isfinite(list(numbers.values())).all()
