"""The one-chip dense cell at 25,000 samples, ``kg25k-chr17.batch``: its
files resolve, the driver's own rules pick the dense strategy, 32 blocks
per dispatch and a depth of one dispatch for it, its dry size runs correct
on one CPU device, and the ``ingest`` span carries the depth the memory
rule chose on the dense and on the ring path."""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import core, readings  # noqa: E402

CELL = "kg25k-chr17.batch"
#: One v5e chip's device memory budget: the 15.75 GB the sizing notes use,
#: and the ``bytes_limit`` a v5e chip reports (16.909 GB).
V5E_BYTES = (15_750_000_000, 16_909_000_000)
PER_LAYER = [
    "idle_share.job", "gramian_update_ms.job", "gramian_roofline.job",
    "dispatches_per_job.job", "finalize_ms.job", "genotype_gen_ms.job",
    "gramian_dot_ms.job", "host_enqueue_ms.job", "driver_init_ms.job",
    "dispatch_padding_share.job", "ingest_peak_gb.job",
]


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_EXAMPLES_TPU_NO_CACHE"] = "1"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=1").strip()
    return env


# ------------------------------------------------------------ the files


def test_the_cell_resolves_with_its_files_and_metrics():
    cell = core.cell(CELL)
    assert cell["chips"] == 1
    assert cell["config"]["num_samples"] == 25000
    assert "flags" not in cell["config"]
    assert cell["traffic"] == core.load_traffic("chr17-batch")
    assert cell["traffic"]["references"] == "17:0:81195210"
    assert cell["traffic"]["spacing"] == 73 and cell["traffic"]["block_size"] == 16384
    assert cell["limits"]["gramian_max_abs_diff"] == 0
    assert [m["name"] for m in cell["end_to_end"]] == ["job_s", "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == PER_LAYER
    for metric in cell["per_layer"]:
        assert callable(core.load_reader(metric["name"]))


def test_the_configuration_keeps_kg1000s_semantics():
    doc = core.manifest()
    entry = {c["name"]: c for c in doc["configs"]}["kg25k"]
    assert entry["reduced"] == ["references"]
    kg25k = core.load_json(os.path.join(ROOT, entry["file"]))
    kg1000 = core.load_json(os.path.join(ROOT, "benchmark", "configs", "kg1000-2504.json"))
    for key in ("variant_set_id", "cohort_seed", "n_pops", "ref_block_fraction", "num_pc",
                "precision", "control"):
        assert kg25k[key] == kg1000[key], key


def test_four_chip_cells_stay_within_half_the_benchmark():
    doc = core.manifest()
    four = [w["name"] for w in doc["workloads"] if w["chips"] == 4]
    assert CELL not in four
    assert len(four) <= max(1, len(doc["workloads"]) // 2)


# ------------------------------------------------------------ the driver's rules


def test_the_auto_dispatch_rule_gives_the_cell_32_blocks():
    from spark_examples_tpu.ops.devicegen import auto_blocks_per_dispatch

    assert auto_blocks_per_dispatch(25000, 16384) == 32


@pytest.mark.parametrize("device_bytes", V5E_BYTES)
def test_the_dense_rule_keeps_25k_on_one_chip_and_not_50k(monkeypatch, device_bytes):
    from spark_examples_tpu.ops import gramian

    monkeypatch.setattr(gramian, "per_device_memory_bytes", lambda: device_bytes)
    assert gramian.dense_strategy_fits(25_000)
    assert not gramian.dense_strategy_fits(50_000)


@pytest.mark.parametrize("device_bytes", V5E_BYTES)
def test_the_loop_keeps_three_copies_at_25k(device_bytes):
    from spark_examples_tpu.ops.devicegen import dispatch_depth, gramian_copies_max

    assert dispatch_depth(25_000**2 * 4, device_bytes) == 1
    assert gramian_copies_max(3, 1) == 3


# ------------------------------------------------------------ dry runs


def test_dry_cell_is_correct_on_one_device():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", "3000000641", "--seconds", "1", "--trace", "0", "--dry"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["device"]["count"] == 1
    assert set(line["metrics"]) == {"job_s", "setup_s"}
    assert line["compared"]["gramian_max_abs_diff"]["value"] == 0


def test_control_is_not_correct_at_dry_size():
    import jax

    doc = core.dry_overrides(core.cell(CELL))
    numbers = readings.control_numbers(doc, 7, devices=jax.devices()[:1])
    assert numbers["pc_eigenspace_gap"] > doc["limits"]["pc_eigenspace_gap"]
    assert numbers["gramian_max_abs_diff"] > doc["limits"]["gramian_max_abs_diff"]


# ------------------------------------------------------------ the ingest span


def _ingest_attrs(cell_name, devices):
    """The ``ingest`` span attributes and the record of one dry job."""
    from benchmark import batch, traffic
    from spark_examples_tpu.obs.spans import recent_spans

    cell = core.dry_overrides(core.cell(cell_name))
    job = batch.Job(cell, devices, traced=False)
    record = job(traffic.closed_job(cell["traffic"], 5, 0))
    ingest = [s for s in recent_spans() if s["path"] == "ingest"][-1]
    return ingest["attrs"], record


@pytest.mark.parametrize("cell_name, chips", [(CELL, 1), ("kg50k-chr17.sharded", 4)],
                         ids=["dense", "ring"])
def test_ingest_span_carries_the_dispatch_depth(cell_name, chips):
    import jax

    from spark_examples_tpu.ops.devicegen import dispatch_depth, gramian_copies_max
    from spark_examples_tpu.ops.gramian import per_device_memory_bytes

    if jax.device_count() < chips:
        pytest.skip(f"needs {chips} virtual CPU devices")
    attrs, record = _ingest_attrs(cell_name, jax.devices()[:chips])
    state = attrs.get("state_bytes_per_device", attrs["gramian_bytes_per_device"])
    assert attrs["dispatch_depth"] == dispatch_depth(state, per_device_memory_bytes())
    assert attrs["gramian_copies_max"] == gramian_copies_max(
        record["dispatches"], attrs["dispatch_depth"]
    )
