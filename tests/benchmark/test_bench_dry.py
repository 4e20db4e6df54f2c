"""Dry rehearsal: each cell end to end at its ``dry`` size on the CPU
through ``benchmark/run.py --dry``, and the refusal to run without a TPU."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["kg1000.wgs-batch", "platinum.wgs-batch"]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_EXAMPLES_TPU_NO_CACHE"] = "1"
    # One CPU device, as one chip: replace the test suite's device count.
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=1").strip()
    return env


def _run(*args, timeout=240):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=timeout,
    )


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("cell", CELLS)
def test_dry_cell_prints_the_contract_line(cell):
    proc = _run("--workload", cell, "--seed", "3000000017", "--seconds", "1.5", "--trace", "0", "--dry")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert KEYS <= set(line)
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    e2e = {m["name"] for m in _manifest()["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == e2e
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    assert line["device"]["count"] == 1
    assert {"platform", "kind", "memory_peak_bytes"} <= set(line["device"])
    # The compared numbers close standard error, each beside its limit.
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compare ") and " limit " in t for t in tail)
    assert list(line)[-1] == "compared"


def test_dry_traced_run_reads_per_layer_metrics():
    proc = _run("--workload", "platinum.wgs-batch", "--seed", "5", "--seconds", "1", "--trace", "1", "--dry")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    # The CPU trace has no device plane: the device readers find nothing
    # and stay silent; the program's own counter is read.
    assert set(line["metrics"]) == {"dispatches_per_job.job"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    proc = _run("--workload", "platinum.wgs-batch", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "platinum.wgs-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
