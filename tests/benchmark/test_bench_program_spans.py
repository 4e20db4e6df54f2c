"""The per-layer readers that take their numbers from the program itself
(``benchmark/program.py``): the stage spans of ``obs/spans.py`` and the
scope map of ``ops/devicegen.py``, on hand-built runs with both stubbed."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import core  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

MS = 1_000_000  # ns
CHIP = "/device:TPU:0"
SCOPES = {
    "jit_devicegen_update": {"fusion.3": "generate", "convolution_add_fusion.2": "int8_dot",
                             "reduce.8": "count"},
    "jit_devicegen_update_tail": {"fusion.3": "generate", "fusion.9": "int8_dot"},
}


class Run:
    def __init__(self, trace, jobs):
        self.trace = trace
        self.jobs = jobs


def _trace():
    """Two update programs and a centring program on one chip, in a 100 ms
    window; ``fusion.3`` also runs inside the centring program, where it is
    not generation."""
    ops = [
        ("%fusion.3 = pred[16384,2504] fusion(...)", 1 * MS, 4 * MS),
        ("%convolution_add_fusion.2 = s32[2504,2504] fusion(...)", 5 * MS, 10 * MS),
        ("%reduce.8 = s64[] reduce(...)", 15 * MS, 1 * MS),
        ("%fusion.3 = pred[2048,2504] fusion(...)", 21 * MS, 2 * MS),
        ("%fusion.9 = s32[2504,2504] fusion(...)", 23 * MS, 3 * MS),
        ("%fusion.3 = f32[2504,2504] fusion(...)", 40 * MS, 5 * MS),
    ]
    modules = [
        ("jit_devicegen_update(11)", 0, 20 * MS),
        ("jit_devicegen_update_tail(12)", 20 * MS, 10 * MS),
        ("jit_gower_center(13)", 39 * MS, 10 * MS),
    ]
    spans = [("bench:window", 0, 100 * MS)]
    return Trace({CHIP: ops}, {CHIP: modules}, spans)


def _no_device_plane():
    return Trace({}, {}, [("bench:window", 0, 100 * MS)])


def _job_spans(run_id, init_s, enqueue_s, dispatch_s, stats_s, poke_s, valid, capacity):
    return [
        {"path": "driver-init", "parent": None, "run_id": run_id, "seconds": init_s,
         "self_seconds": init_s, "attrs": {}},
        {"path": "ingest/enqueue/poke", "parent": "ingest/enqueue", "run_id": run_id,
         "seconds": poke_s, "self_seconds": poke_s, "attrs": {}},
        {"path": "ingest/enqueue/dispatch", "parent": "ingest/enqueue", "run_id": run_id,
         "seconds": dispatch_s, "self_seconds": dispatch_s, "attrs": {}},
        {"path": "ingest/enqueue/stats", "parent": "ingest/enqueue", "run_id": run_id,
         "seconds": stats_s, "self_seconds": stats_s, "attrs": {}},
        {"path": "ingest/enqueue", "parent": "ingest", "run_id": run_id, "seconds": enqueue_s,
         "self_seconds": enqueue_s - dispatch_s - stats_s - poke_s, "attrs": {}},
        {"path": "ingest", "parent": None, "run_id": run_id, "seconds": enqueue_s + 0.01,
         "self_seconds": 0.01, "attrs": {"sites_valid": valid, "sites_capacity": capacity}},
    ]


@pytest.fixture
def program(monkeypatch):
    """Stub the program's span buffer and scope map; returns the list the
    stubbed ``recent_spans`` serves."""
    from spark_examples_tpu.obs import spans
    from spark_examples_tpu.ops import devicegen

    served = []
    monkeypatch.setattr(spans, "recent_spans", lambda: list(served))
    monkeypatch.setattr(devicegen, "update_op_scopes", lambda: SCOPES)
    return served


def test_scoped_device_time_per_job(program):
    run = Run(_trace(), jobs=[{}, {}])
    # generate: 4 ms + 2 ms (not the centring program's fusion.3), over 2 jobs.
    assert core.load_reader("genotype_gen_ms.job")(run) == pytest.approx(3.0)
    # int8_dot: 10 ms + 3 ms (the tail's own instruction names).
    assert core.load_reader("gramian_dot_ms.job")(run) == pytest.approx(6.5)


def test_scoped_device_time_is_clipped_to_the_window(program):
    trace = _trace()
    trace.window = (0, 10 * MS)
    run = Run(trace, jobs=[{}])
    assert core.load_reader("gramian_dot_ms.job")(run) == pytest.approx(5.0)


def test_program_span_readers(program):
    program += _job_spans("warm-up", 0.5, 0.5, 0.2, 0.1, 0.1, 10, 20)
    program += _job_spans("a", 0.050, 2.030, 2.000, 0.004, 0.006, 39_466_223, 40_173_568)
    program += _job_spans("b", 0.060, 2.040, 2.000, 0.004, 0.006, 39_466_223, 40_173_568)
    run = Run(_trace(), jobs=[{}, {}])
    assert core.load_reader("driver_init_ms.job")(run) == pytest.approx(55.0)
    # The dispatch calls' 2 s per job (a full device queue) is not host work.
    assert core.load_reader("host_enqueue_ms.job")(run) == pytest.approx(25.0)
    assert core.load_reader("dispatch_padding_share.job")(run) == pytest.approx(1.7607, abs=5e-5)


@pytest.mark.parametrize(
    "valid, capacity, share",
    [(39_466_223, 49 * 1_048_576, 23.1879), (39_466_223, 40_173_568, 1.7607)],
    ids=["platinum", "kg1000"],
)
def test_padding_share_of_the_cells(program, valid, capacity, share):
    program += _job_spans("only", 0.01, 0.01, 0.0, 0.0, 0.0, valid, capacity)
    value = core.load_reader("dispatch_padding_share.job")(Run(_trace(), jobs=[{}]))
    assert round(value, 4) == share


METRICS = [
    "genotype_gen_ms.job",
    "gramian_dot_ms.job",
    "host_enqueue_ms.job",
    "driver_init_ms.job",
    "dispatch_padding_share.job",
]


@pytest.mark.parametrize("metric", METRICS)
def test_no_device_plane_reads_nothing(program, metric):
    program += _job_spans("a", 0.05, 0.03, 0.0, 0.0, 0.0, 1, 2)
    assert core.load_reader(metric)(Run(_no_device_plane(), jobs=[{}])) is None
    assert core.load_reader(metric)(Run(None, jobs=[{}])) is None


@pytest.mark.parametrize("metric", METRICS[2:])
def test_too_few_runs_read_nothing(program, metric):
    program += _job_spans("a", 0.05, 0.03, 0.0, 0.0, 0.0, 1, 2)
    assert core.load_reader(metric)(Run(_trace(), jobs=[{}, {}])) is None


@pytest.mark.parametrize("metric", METRICS[2:])
def test_an_incomplete_run_reads_nothing(program, metric):
    program += _job_spans("a", 0.05, 0.03, 0.0, 0.0, 0.0, 1, 2)
    program += [s for s in _job_spans("b", 0.05, 0.03, 0.0, 0.0, 0.0, 1, 2) if s["path"] == "driver-init"]
    if metric == "driver_init_ms.job":
        assert core.load_reader(metric)(Run(_trace(), jobs=[{}, {}])) is not None
    else:
        assert core.load_reader(metric)(Run(_trace(), jobs=[{}, {}])) is None


def test_enqueue_without_a_dispatch_aggregate_reads_nothing(program):
    """Without the dispatch aggregate the enqueue span's self time would
    hold the device queue's backpressure, not host work."""
    program += [s for s in _job_spans("a", 0.05, 0.03, 0.0, 0.0, 0.0, 1, 2)
                if s["path"] != "ingest/enqueue/dispatch"]
    assert core.load_reader("host_enqueue_ms.job")(Run(_trace(), jobs=[{}])) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_spans_or_scopes_reads_nothing(monkeypatch, metric):
    """A checkout from before the program recorded stage spans and scope
    maps: the readers find nothing to import and stay silent."""
    from spark_examples_tpu.obs import spans
    from spark_examples_tpu.ops import devicegen

    monkeypatch.delattr(spans, "recent_spans")
    monkeypatch.delattr(devicegen, "update_op_scopes")
    assert core.load_reader(metric)(Run(_trace(), jobs=[{}])) is None


def test_unknown_instructions_read_nothing(monkeypatch, program):
    from spark_examples_tpu.ops import devicegen

    monkeypatch.setattr(devicegen, "update_op_scopes", lambda: {"jit_devicegen_update": {}})
    assert core.load_reader("genotype_gen_ms.job")(Run(_trace(), jobs=[{}])) is None
