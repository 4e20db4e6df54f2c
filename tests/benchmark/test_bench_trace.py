"""The trace reduction of the benchmark (``benchmark/trace.py``): on hand-made
intervals, and on a small trace recorded on the chip and checked in beside
this file (``data/platinum-chr21.xplane.pb``: two fenced chr21 jobs of the
17-sample cohort, read through ``jax.profiler.ProfileData``)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import core  # noqa: E402
from benchmark.trace import Trace, _union  # noqa: E402

RECORDED = os.path.join(HERE, "data", "platinum-chr21.xplane.pb")
MS = 1_000_000  # ns


def _hand_made():
    chip = "/device:TPU:0"
    ops = {chip: [("fusion.1", 0 * MS, 10 * MS), ("fusion.1", 5 * MS, 10 * MS),
                  ("dot.2", 30 * MS, 20 * MS), ("copy.3", 90 * MS, 10 * MS)]}
    modules = {chip: [("jit_update(7)", 0, 15 * MS), ("jit_gower_center(9)", 30 * MS, 20 * MS),
                      ("jit_principal_components_subspace(4)", 90 * MS, 10 * MS)]}
    spans = [("bench:window", 0, 100 * MS), ("bench:job", 0, 100 * MS),
             ("bench:ingest", 0, 20 * MS), ("bench:finalize", 20 * MS, 100 * MS)]
    return Trace(ops, modules, spans)


def test_union_merges_overlaps():
    assert _union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert _union([]) == []


def test_busy_is_the_union_of_operations():
    t = _hand_made()
    assert t.window_s() == pytest.approx(0.1)
    assert t.busy_s() == pytest.approx(0.015 + 0.020 + 0.010)


def test_busy_within_host_spans():
    t = _hand_made()
    assert t.busy_within_s(t.spans_named("ingest")) == pytest.approx(0.015)
    assert t.busy_within_s(t.spans_named("finalize")) == pytest.approx(0.030)


def test_module_seconds_by_name():
    t = _hand_made()
    read = core.load_reader("finalize_ms.served")
    assert t.module_seconds(("jit_gower_center", "jit_principal_components_subspace")) == pytest.approx(0.030)

    class FakeRun:
        trace = t
        jobs = [{}, {}]

    assert read(FakeRun()) == pytest.approx(15.0)


def test_top_ops_and_idle_gaps():
    t = _hand_made()
    assert t.top_ops(2) == [["fusion.1", pytest.approx(0.020)], ["dot.2", pytest.approx(0.020)]]
    gaps = t.idle_gaps(5)
    assert gaps[0] == ["finalize", pytest.approx(0.040)]
    assert gaps[1] == ["finalize", pytest.approx(0.015)]
    assert len(gaps) == 2


def test_idle_share_reader():
    class FakeRun:
        trace = _hand_made()
        jobs = []

    assert core.load_reader("idle_share.job")(FakeRun()) == pytest.approx(55.0)


def test_no_device_plane_reads_nothing():
    class FakeRun:
        trace = Trace({}, {}, [("bench:window", 0, 10), ("bench:ingest", 0, 5)])
        jobs = [{"sites_scanned": 10, "dispatches": 3}]
        cell = {"config": {"num_samples": 4}, "chips": 1}
        device_kind = "cpu"

    for name in ("idle_share.job", "gramian_update_ms.job", "gramian_roofline.job",
                 "finalize_ms.served", "idle_share.served"):
        assert core.load_reader(name)(FakeRun()) is None
    assert core.load_reader("dispatches_per_job.job")(FakeRun()) == 3.0


@pytest.fixture(scope="module")
def recorded():
    return Trace.load(RECORDED)


def test_recorded_trace_planes(recorded):
    assert recorded.chips == 1
    assert sum(len(v) for v in recorded.ops.values()) == 4848
    assert len(recorded.spans_named("job")) == 2
    assert len(recorded.spans_named("ingest")) == 2
    assert len(recorded.spans_named("finalize")) == 2
    names = {n.split("(")[0] for events in recorded.modules.values() for n, _, _ in events}
    assert {"jit_update", "jit_gower_center", "jit_principal_components_subspace"} <= names


def test_recorded_trace_reduction(recorded):
    assert recorded.window_s() == pytest.approx(0.029291046)
    assert recorded.busy_s() == pytest.approx(0.006574379)
    assert recorded.busy_within_s(recorded.spans_named("ingest")) == pytest.approx(0.005899237)
    assert recorded.busy_within_s(recorded.spans_named("finalize")) == pytest.approx(0.000675142)
    assert recorded.module_seconds(
        ("jit_gower_center", "jit_principal_components_subspace")
    ) == pytest.approx(0.000672711)
    assert recorded.top_ops(2) == [
        ["fusion.253", pytest.approx(0.002213545)], ["fusion.252", pytest.approx(0.000644242)]
    ]
    gaps = recorded.idle_gaps(3)
    assert [g[0] for g in gaps] == ["driver-setup", "driver-setup", "finalize"]
    assert gaps[0][1] == pytest.approx(0.005355871)


def test_recorded_trace_metrics(recorded):
    class RecordedRun:
        trace = recorded
        jobs = [{"sites_scanned": 659314, "dispatches": 1}] * 2
        cell = {"config": {"num_samples": 17}, "chips": 1}
        device_kind = "TPU v5 lite"

    run = RecordedRun()
    assert core.load_reader("gramian_update_ms.job")(run) == pytest.approx(0.005899237 / 2 * 1e3)
    assert core.load_reader("idle_share.job")(run) == pytest.approx(100 * (1 - 0.006574379 / 0.029291046))
    share = core.load_reader("gramian_roofline.job")(run)
    assert share == pytest.approx(100 * 17 * 18 * 659314 / 393e12 / (0.005899237 / 2))
    assert 0 < share < 100


@pytest.mark.parametrize("chips", [1, 4])
def test_roofline_reader_holds_the_cells_chips(recorded, chips):
    class RecordedRun:
        trace = recorded
        jobs = [{"sites_scanned": 659314, "dispatches": 1}] * 2
        cell = {"config": {"num_samples": 17}, "chips": chips}
        device_kind = "TPU v5 lite"

    # Device time is averaged over the chips, so the least time is that of
    # all of the cell's chips together.
    share = core.load_reader("gramian_roofline.job")(RecordedRun())
    assert share == pytest.approx(100 * 17 * 18 * 659314 / (chips * 393e12) / (0.005899237 / 2))
