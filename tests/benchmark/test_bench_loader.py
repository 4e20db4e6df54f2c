"""A new file under ``benchmark/configs``, ``benchmark/traffic``,
``benchmark/metrics`` or ``benchmark/jobs`` becomes a configuration, a
traffic mix, a metric or a job kind by its name alone: no file that is
already there is edited. A new job kind runs a cell end to end from new
files on a copy of the checkout: the synthetic cohort fed from the host
through the packed path, checked by the default check, which a planted
fault fails; a kind's data preparation is left out of ``setup_s`` and its
directory removed, also after a failure; an unknown kind is a failure."""

import hashlib
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: A job kind of the test's own: PCoA of the synthetic cohort fed from the
#: host as packed has-variation rows (``--source synthetic --ingest
#: packed``, ``VariantsPcaDriver.get_similarity_rows``); its ``prepare``
#: writes the cohort's definition, which the job reads back.
PACKED_KIND = """
import json
import os

from benchmark import batch, traffic

COHORT = ("num_samples", "cohort_seed", "n_pops", "ref_block_fraction", "variant_set_id", "num_pc")


def prepare(cell, directory):
    path = os.path.join(directory, "cohort.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump({key: cell["config"][key] for key in COHORT}, f)
    return path


class Job:
    def __init__(self, cell, devices, traced, data):
        from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

        with open(data, encoding="utf-8") as f:
            cohort = json.load(f)
        self.trf, self.devices, self.traced = cell["traffic"], devices, traced
        self.source = SyntheticGenomicsSource(
            num_samples=int(cohort["num_samples"]),
            seed=int(cohort["cohort_seed"]),
            variant_spacing=int(self.trf["spacing"]),
            ref_block_fraction=float(cohort["ref_block_fraction"]),
            n_pops=int(cohort["n_pops"]),
        )
        self.flags = [
            "--variant-set-id", cohort["variant_set_id"],
            "--source", "synthetic",
            "--ingest", "packed",
            "--block-size", str(self.trf["block_size"]),
            "--num-pc", str(cohort["num_pc"]),
            "--num-samples", str(cohort["num_samples"]),
            "--seed", str(cohort["cohort_seed"]),
        ]

    def __call__(self, references):
        block_size = int(self.trf["block_size"])

        def rows(driver, contigs):
            set_id = driver.conf.variant_set_id[0]
            return driver.get_similarity_rows(
                block["has_variation"]
                for contig in contigs
                for block in self.source.genotype_blocks(set_id, contig, block_size=block_size)
            )

        record, _ = batch.pcoa(self, references, rows)
        return record


def warm_up(job):
    job(traffic.closed_job(job.trf, 0, 0))
"""

#: A job kind whose ``prepare`` sleeps ``prepare_sleep_s`` and whose jobs
#: do no device work; it brings its own check. With ``"fail"`` in its
#: configuration its warm-up fails after the data was prepared.
SLEEPY_KIND = """
import time

from benchmark import core


def prepare(cell, directory):
    time.sleep(float(cell["config"]["prepare_sleep_s"]))
    return directory


class Job:
    def __init__(self, cell, devices, traced, data):
        self.cfg, self.trf, self.traced = cell["config"], cell["traffic"], traced

    def __call__(self, references):
        time.sleep(0.05)
        return {"seconds": 0.05, "S": None, "pcs": None, "ranges": []}


def warm_up(job):
    if job.cfg.get("fail"):
        raise core.BenchFailure("planted failure after the data was prepared")
    core.say(f"warmed up at process age {core.process_age_s()!r}")


def check(cell, kept):
    return {"pc_eigenspace_gap": 0.0}
"""

#: ``benchmark/run.py`` with half of each job's rows dropped on their way
#: into ``get_similarity_rows``: ``python -c HALF_ROWS <checkout> <args>``.
HALF_ROWS = """
import os
import runpy
import sys

sys.path.insert(0, sys.argv[1])
from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver

rows = VariantsPcaDriver.get_similarity_rows


def half_rows(self, blocks, *args, **kwargs):
    return rows(self, (block[: len(block) // 2] for block in blocks), *args, **kwargs)


VariantsPcaDriver.get_similarity_rows = half_rows
sys.argv = [os.path.join(sys.argv[1], "benchmark", "run.py")] + sys.argv[2:]
runpy.run_path(sys.argv[0], run_name="__main__")
"""

TINY_BATCH = {
    "loop": "closed", "references": "21:0:3000000,22:0:3000000", "spacing": 73,
    "block_size": 1024, "sampled_jobs": 1, "sample_among": 2, "trace_seconds": 1,
}
TINY_COHORT = {
    "num_samples": 24, "variant_set_id": "4252737135923902652", "cohort_seed": 42,
    "n_pops": 4, "ref_block_fraction": 0.1, "num_pc": 2,
}


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
    os.symlink(os.path.join(ROOT, "spark_examples_tpu"), tmp_path / "spark_examples_tpu")
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
        assert cell["job"] == cell["config"].get("job", core.DEFAULT_JOB)
        kind = core.load_job(cell["job"])
        assert callable(kind.Job) and callable(kind.warm_up)


def test_unknown_cell_is_a_failure():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import core

    with pytest.raises(core.BenchFailure):
        core.cell("no.such-cell")


# ------------------------------------------------------------ job kinds


def _add_cell(checkout, name, kind, config, limits, kind_source=None):
    """New files only: a kind module (unless ``kind_source`` is None), a
    configuration naming the kind, the ``tiny-batch`` traffic, the cell's
    limits, and the manifest's entries."""
    bench = checkout / "benchmark"
    if kind_source is not None:
        (bench / "jobs" / f"{kind}.py").write_text(kind_source)
    (bench / "configs" / f"{name}.json").write_text(json.dumps({"name": name, "job": kind, **config}))
    (bench / "traffic" / "tiny-batch.json").write_text(json.dumps(TINY_BATCH))
    (bench / "limits" / f"{name}.tiny-batch.json").write_text(json.dumps(limits))
    doc = json.loads((checkout / "BENCHMARK.json").read_text())
    doc["configs"].append(
        {"name": name, "source": "test cohort", "file": f"benchmark/configs/{name}.json",
         "reduced": [], "why": "a job kind from new files"}
    )
    doc["workloads"].append(
        {"name": f"{name}.tiny-batch", "config": name, "traffic": "tiny-batch", "chips": 1,
         "why": "a job kind from new files"}
    )
    for metric in doc["end_to_end"]:
        if "workloads" in metric:
            metric["workloads"].append(f"{name}.tiny-batch")
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))
    return f"{name}.tiny-batch"


def _run_cell(checkout, cell, preamble=None):
    """``benchmark/run.py`` of the copy on one CPU device, ``--dry``, with
    a ``TMPDIR`` of its own; ``preamble`` wraps it (``HALF_ROWS``)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_EXAMPLES_TPU_NO_CACHE"] = "1"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=1").strip()
    env["TMPDIR"] = str(checkout / "tmp")
    os.makedirs(env["TMPDIR"], exist_ok=True)
    args = ["--workload", cell, "--seed", "3100000019", "--seconds", "1", "--trace", "0", "--dry"]
    command = (
        [sys.executable, "-c", preamble, str(checkout)] if preamble
        else [sys.executable, "benchmark/run.py"]
    )
    return subprocess.run(
        command + args, cwd=checkout, env=env, capture_output=True, text=True, timeout=300
    )


def _prepared_directory(stderr):
    return re.search(r"prepare: [0-9.]+ s, data in (\S+)", stderr).group(1)


@pytest.mark.parametrize("fault", [None, "half_rows"])
def test_a_new_job_kind_runs_a_cell_from_new_files_alone(checkout, fault):
    before = _digest(checkout / "benchmark")
    cell = _add_cell(
        checkout, "tiny-packed", "packed", TINY_COHORT,
        {"gramian_max_abs_diff": 0, "pc_eigenspace_gap": 1e-3}, PACKED_KIND,
    )
    proc = _run_cell(checkout, cell, HALF_ROWS if fault else None)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    gap = line["compared"]["gramian_max_abs_diff"]["value"]
    if fault:
        assert line["correct"] is False and gap > 0
    else:
        assert line["correct"] is True, proc.stderr[-3000:]
        assert gap == 0 and set(line["metrics"]) == {"job_s", "setup_s"}
    # The kind reports no dispatch counts, so the window's line names none.
    assert re.search(r"^window: \d+ jobs in [0-9.]+ s, job seconds \[[^]]*\]$", proc.stderr, re.M)
    assert not os.path.exists(_prepared_directory(proc.stderr))
    after = _digest(checkout / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("fail", [False, True], ids=["sound", "failure"])
def test_setup_leaves_out_preparation_and_its_directory_goes(checkout, fail):
    sleep_s = 2.0
    config = {"prepare_sleep_s": sleep_s, **({"fail": True} if fail else {})}
    cell = _add_cell(checkout, "sleepy", "sleepy", config, {"pc_eigenspace_gap": 1e-3}, SLEEPY_KIND)
    proc = _run_cell(checkout, cell)
    directory = _prepared_directory(proc.stderr)
    assert directory.startswith(str(checkout / "tmp"))
    assert not os.path.exists(directory)
    prepare_s = float(re.search(r"prepare: ([0-9.]+) s", proc.stderr).group(1))
    assert prepare_s >= sleep_s
    if fail:
        assert proc.returncode == 1 and proc.stdout.strip() == ""
        assert "planted failure" in proc.stderr
        return
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    warmed = float(re.search(r"warmed up at process age ([0-9.]+)", proc.stderr).group(1))
    setup_s = line["metrics"]["setup_s"]["value"]
    # Set-up is the process's age when warm-up ends less the preparation
    # (printed to the millisecond).
    assert -0.001 <= setup_s - (warmed - prepare_s) < 0.5
    assert setup_s < warmed - sleep_s + 0.5


def test_an_unknown_job_kind_is_a_failure(checkout):
    cell = _add_cell(checkout, "nokind", "nokind", TINY_COHORT, {"pc_eigenspace_gap": 1e-3})
    proc = _run_cell(checkout, cell)
    assert proc.returncode == 1 and proc.stdout.strip() == ""
    assert "no job kind 'nokind'" in proc.stderr
