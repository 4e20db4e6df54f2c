"""The four-chip ring cell, ``kg50k-chr17.sharded``: its files resolve, its
dry size runs correct on four virtual CPU devices, the program's ``ingest``
span carries the loop's memory attributes on the ring, and the cell's two
readers (``ring_exchange_ms.job``, ``ingest_peak_gb.job``) read what is
there and nothing where it is not."""

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
from benchmark.trace import Trace  # noqa: E402

CELL = "kg50k-chr17.sharded"
RECORDED = os.path.join(HERE, "data", "platinum-chr21.xplane.pb")
MS = 1_000_000  # ns
PER_LAYER = [
    "idle_share.job", "gramian_update_ms.job", "gramian_roofline.job",
    "dispatches_per_job.job", "finalize_ms.job", "genotype_gen_ms.job",
    "gramian_dot_ms.job", "host_enqueue_ms.job", "driver_init_ms.job",
    "dispatch_padding_share.job", "ring_exchange_ms.job", "ingest_peak_gb.job",
]


def _env(devices):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_EXAMPLES_TPU_NO_CACHE"] = "1"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={devices}").strip()
    return env


def _dry(*args, devices=4):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, *args, "--dry"],
        cwd=ROOT, env=_env(devices), capture_output=True, text=True, timeout=300,
    )


# ------------------------------------------------------------ the files


def test_the_cell_resolves_with_its_files_and_metrics():
    cell = core.cell(CELL)
    assert cell["chips"] == 4
    assert cell["config"]["num_samples"] == 50000
    assert cell["config"]["flags"] == ["--mesh-shape", "1,4"]
    assert cell["traffic"]["references"] == "17:0:81195210"
    assert cell["traffic"]["spacing"] == 73 and cell["traffic"]["block_size"] == 16384
    assert cell["limits"] == {"gramian_max_abs_diff": 0, "pc_eigenspace_gap": 0.001}
    assert [m["name"] for m in cell["end_to_end"]] == ["job_s", "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == PER_LAYER
    for metric in cell["per_layer"]:
        assert callable(core.load_reader(metric["name"]))


def test_the_auto_dispatch_rule_gives_the_cell_32_blocks():
    from spark_examples_tpu.ops.devicegen import auto_blocks_per_dispatch

    assert auto_blocks_per_dispatch(50000, 16384) == 32


def test_four_chip_cells_stay_within_half_the_benchmark():
    doc = core.manifest()
    four = [w["name"] for w in doc["workloads"] if w["chips"] == 4]
    assert four == [CELL]
    assert len(four) <= max(1, len(doc["workloads"]) // 2)


# ------------------------------------------------------------ dry runs


def test_dry_cell_is_correct_on_four_devices():
    proc = _dry("--seed", "3000000641", "--seconds", "1.5", "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"job_s", "setup_s"}
    assert line["compared"]["gramian_max_abs_diff"]["value"] == 0


def test_dry_cell_wants_four_devices():
    proc = _dry("--seed", "1", "--seconds", "1", "--trace", "0", devices=1)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 4 chips" in proc.stderr


def test_control_is_not_correct_at_dry_size():
    doc = core.dry_overrides(core.cell(CELL))
    numbers = readings.control_numbers(doc, 7, devices=_four_devices())
    assert numbers["pc_eigenspace_gap"] > doc["limits"]["pc_eigenspace_gap"]
    assert numbers["gramian_max_abs_diff"] > doc["limits"]["gramian_max_abs_diff"]


def _four_devices():
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs four virtual CPU devices")
    return jax.devices()[:4]


# ------------------------------------------------------------ the ingest span


def _ingest_attrs(cell_name):
    """The ``ingest`` span attributes of one dry job of ``cell_name``."""
    from benchmark import traffic
    from spark_examples_tpu.obs.spans import recent_spans

    cell = core.dry_overrides(core.cell(cell_name))
    job = core.load_job(cell["job"]).Job(cell, _four_devices(), False, None)
    record = job(traffic.closed_job(cell["traffic"], 5, 0))
    ingest = [s for s in recent_spans() if s["path"] == "ingest"][-1]
    return ingest["attrs"], record


def test_ring_ingest_span_carries_the_loops_memory():
    attrs, record = _ingest_attrs(CELL)
    n_local = 64 // 4  # 64 samples, already a multiple of the pack width
    assert attrs["gramian_bytes_per_device"] == n_local * 64 * 4
    assert attrs["gramian_copies_max"] == record["dispatches"] + 1
    assert attrs["ring_bytes"] > 0
    assert "device_peak_bytes" not in attrs  # CPU devices report no memory stats


def test_dense_ingest_span_has_no_ring_attributes():
    attrs, _ = _ingest_attrs("platinum.wgs-batch")
    assert "ring_bytes" not in attrs and "device_peak_bytes" not in attrs
    assert attrs["gramian_bytes_per_device"] == 17 * 17 * 4


# ------------------------------------------------------------ the readers


class Run:
    def __init__(self, trace, jobs):
        self.trace = trace
        self.jobs = jobs


RING_SCOPES = {
    "jit_devicegen_ring_update": {"fusion.3": "generate", "fusion.7": "int8_dot",
                                  "collective-permute-done.1": "ring_exchange"},
    "jit_devicegen_ring_update_tail": {"collective-permute-done.2": "ring_exchange"},
}


def _ring_trace():
    """Two ring programs on each of two chips in a 100 ms window."""
    ops, modules = {}, {}
    for chip in ("/device:TPU:0", "/device:TPU:1"):
        ops[chip] = [
            ("%fusion.3 = s8[16384,12504] fusion(...)", 1 * MS, 2 * MS),
            ("%fusion.7 = s32[12504,50016] fusion(...)", 3 * MS, 10 * MS),
            ("%collective-permute-done.1 = u8[16384,1563] collective-permute-done(...)", 13 * MS, 1 * MS),
            ("%collective-permute-done.2 = u8[16384,1563] collective-permute-done(...)", 31 * MS, 3 * MS),
            ("%collective-permute-done.1 = f32[10] collective-permute-done(...)", 50 * MS, 4 * MS),
        ]
        modules[chip] = [
            ("jit_devicegen_ring_update(3)", 0, 20 * MS),
            ("jit_devicegen_ring_update_tail(4)", 30 * MS, 5 * MS),
            ("jit_principal_components_subspace_sharded(5)", 49 * MS, 10 * MS),
        ]
    return Trace(ops, modules, [("bench:window", 0, 100 * MS)])


def _job_spans(run_id, peak):
    attrs = {"sites_valid": 1, "sites_capacity": 2}
    if peak is not None:
        attrs["device_peak_bytes"] = peak
    return [{"path": "ingest", "parent": None, "run_id": run_id, "seconds": 1.0,
             "self_seconds": 1.0, "attrs": attrs}]


@pytest.fixture
def program(monkeypatch):
    from spark_examples_tpu.obs import spans
    from spark_examples_tpu.ops import devicegen

    served = []
    monkeypatch.setattr(spans, "recent_spans", lambda: list(served))
    monkeypatch.setattr(devicegen, "update_op_scopes", lambda: RING_SCOPES)
    return served


def test_ring_exchange_reads_the_scoped_permutes(program):
    # 1 ms + 3 ms per chip over 2 jobs; the eigensolve's permute is not the ring's.
    assert core.load_reader("ring_exchange_ms.job")(Run(_ring_trace(), [{}, {}])) == pytest.approx(2.0)


def test_ring_exchange_reads_nothing_off_a_trace_without_a_ring(monkeypatch):
    from spark_examples_tpu.ops import devicegen

    monkeypatch.setattr(devicegen, "update_op_scopes", lambda: {"jit_update": {"fusion.253": "generate"}})
    run = Run(Trace.load(RECORDED), [{}, {}])
    assert core.load_reader("ring_exchange_ms.job")(run) is None
    assert core.load_reader("ring_exchange_ms.job")(Run(None, [{}])) is None


def test_ingest_peak_is_the_largest_over_the_jobs(program):
    program += _job_spans("warm-up", 9_000_000_000)
    program += _job_spans("a", 10_250_000_000) + _job_spans("b", 10_750_000_000)
    assert core.load_reader("ingest_peak_gb.job")(Run(_ring_trace(), [{}, {}])) == pytest.approx(10.75)


@pytest.mark.parametrize("peaks", [(None, None), (10_000_000_000, None)], ids=["parent", "one-missing"])
def test_ingest_peak_reads_nothing_without_the_attribute(program, peaks):
    for run_id, peak in zip("ab", peaks):
        program += _job_spans(run_id, peak)
    assert core.load_reader("ingest_peak_gb.job")(Run(_ring_trace(), [{}, {}])) is None


def test_ingest_peak_reads_nothing_without_a_device_plane(program):
    program += _job_spans("a", 10_000_000_000)
    trace = Trace({}, {}, [("bench:window", 0, 100 * MS)])
    assert core.load_reader("ingest_peak_gb.job")(Run(trace, [{}])) is None


# ------------------------------------------------------------ finalize names


def test_finalize_prefixes_match_the_sharded_programs():
    """The sharded centring and eigensolve compile as modules the existing
    ``finalize_ms.job`` prefixes read."""
    import importlib.util

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_examples_tpu.ops import centering, pca
    from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS

    spec = importlib.util.spec_from_file_location(
        "finalize_reader", os.path.join(ROOT, "benchmark", "metrics", "finalize_ms.job.py")
    )
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    mesh = Mesh(_four_devices(), (SAMPLES_AXIS,))
    S = jax.ShapeDtypeStruct((64, 64), jnp.float32, sharding=NamedSharding(mesh, P(SAMPLES_AXIS, None)))
    names = {
        "jit_gower_center_sharded": centering._gower_center_sharded(mesh, 60),
        "jit_principal_components_subspace_sharded": pca._subspace_sharded(mesh, 2, 80, 8, 60),
    }
    for name, program in names.items():
        assert program.lower(S).as_text().splitlines()[0].startswith(f"module @{name} ")
        assert name.startswith(reader.MODULES)
    trace = _ring_trace()
    assert trace.module_seconds(reader.MODULES) == pytest.approx(0.010)
