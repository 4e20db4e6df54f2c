"""A new file under ``benchmark/configs``, ``benchmark/traffic`` or
``benchmark/metrics`` becomes a configuration, a traffic mix or a metric by
its name alone: no file that is already there is edited."""

import hashlib
import importlib.util
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _digest(directory):
    out = {}
    for base, _dirs, files in os.walk(directory):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, directory)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture()
def checkout(tmp_path):
    """A copy of the benchmark as a later PR finds it."""
    shutil.copytree(
        os.path.join(ROOT, "benchmark"),
        tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    return tmp_path


def _core(checkout):
    spec = importlib.util.spec_from_file_location(
        "bench_core_copy", checkout / "benchmark" / "core.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_new_files_become_a_config_a_mix_and_a_metric(checkout):
    before = _digest(checkout / "benchmark")
    bench = checkout / "benchmark"
    (bench / "configs" / "tiny-cohort.json").write_text(
        json.dumps({"name": "tiny-cohort", "num_samples": 8, "cohort_seed": 1})
    )
    (bench / "traffic" / "burst-served.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 3.5})
    )
    (bench / "metrics" / "jobs_seen.burst.py").write_text(
        "def read(run):\n    return float(len(run.jobs))\n"
    )
    (bench / "limits" / "tiny.burst-served.json").write_text(
        json.dumps({"pc_eigenspace_gap": 0.5})
    )
    doc = json.loads((checkout / "BENCHMARK.json").read_text())
    doc["configs"].append(
        {"name": "tiny-cohort", "source": "x", "file": "benchmark/configs/tiny-cohort.json",
         "reduced": [], "why": "x"}
    )
    doc["workloads"].append(
        {"name": "tiny.burst-served", "config": "tiny-cohort", "traffic": "burst-served",
         "chips": 1, "why": "x"}
    )
    doc["per_layer"].append(
        {"name": "jobs_seen.burst", "unit": "jobs", "better": "higher",
         "source": "program_counter", "layer": "entry and serve", "moves": "served_p95_s",
         "workloads": ["tiny.burst-served"]}
    )
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))

    core = _core(checkout)
    cell = core.cell("tiny.burst-served")
    assert cell["config"] == {"name": "tiny-cohort", "num_samples": 8, "cohort_seed": 1}
    assert cell["traffic"] == {"loop": "open", "rate_per_s": 3.5}
    assert cell["limits"] == {"pc_eigenspace_gap": 0.5}
    assert [m["name"] for m in cell["per_layer"]] == ["jobs_seen.burst"]
    assert "setup_s" in [m["name"] for m in cell["end_to_end"]]

    class FakeRun:
        jobs = [{}, {}, {}]

    assert core.load_reader("jobs_seen.burst")(FakeRun()) == 3.0
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_cell_of_the_manifest_resolves():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import core

    doc = core.manifest()
    for work in doc["workloads"]:
        cell = core.cell(work["name"], doc)
        assert cell["traffic"]["loop"] in ("closed", "open")
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for metric in cell["per_layer"]:
            assert callable(core.load_reader(metric["name"]))


def test_unknown_cell_is_a_failure():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import core

    with pytest.raises(core.BenchFailure):
        core.cell("no.such-cell")
