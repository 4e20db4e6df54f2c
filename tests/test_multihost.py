"""A REAL 2-process ``jax.distributed`` run (the reference's cluster-spanning
capability, ``/root/reference/README.md:64-104``; ``GenomicsConf.scala:50-57``).

These tests spawn actual coordinator-connected subprocesses — no mocking, no
single-process simulation — and assert the multi-controller code paths
(``parallel/mesh.py:host_value``, the replicated finalize in
``ops/devicegen.py``) execute and agree with the host oracle in EVERY
process.
"""

import json
import subprocess
import sys

from spark_examples_tpu.parallel.multihost import verify_multihost


def test_two_process_distributed_run():
    """Phase 1: (a) data-parallel device ingest over the global 2×4-device
    mesh with the cross-slice finalize reduce, (b) ring ingest over the
    samples-only mesh whose ppermute hops cross the process boundary, and
    (c) the hierarchical two-level schedule on that same ring (host factor
    2) — all Gramians == host oracle in both processes. Phase 2: the fleet
    rehearsal — host-sharded ingest over four contigs (each process reads
    ~1/2 of the solo bases), PC rows byte-identical to the solo oracle,
    per-host conformance bounds hold, and the per-process flight-recorder
    segments merge into one valid Chrome trace."""
    report = verify_multihost(num_processes=2, local_devices=4)
    assert report["gramian_ok"], json.dumps(report, indent=2)
    assert report["ring_gramian_ok"], json.dumps(report, indent=2)
    assert report["hier_gramian_ok"], json.dumps(report, indent=2)
    # The global results must actually span both processes — otherwise this
    # test would silently degrade into a single-controller run.
    assert report["result_spans_processes"], json.dumps(report, indent=2)
    for child in report["children"]:
        assert child["global_devices"] == 8, child
        assert child["local_devices"] == 4, child
        assert child["hier_schedule_kind"] == "hier", child
    assert report["cli_ok"], json.dumps(report, indent=2)
    assert report["cli_outputs_identical"], json.dumps(report, indent=2)
    assert report["cli_pc_lines"] == 24, json.dumps(report, indent=2)
    assert report["fleet_host_sharded"], json.dumps(report, indent=2)
    assert report["fleet_io_ok"], json.dumps(report, indent=2)
    # Two processes over four equal windows: the split is exactly half —
    # per-process ingest strictly below the solo total.
    bases = report["fleet_io_reference_bases"]
    assert sum(bases["per_process"]) == bases["solo"]
    assert all(0 < b < bases["solo"] for b in bases["per_process"])
    assert report["fleet_conformance_ok"], json.dumps(report, indent=2)
    assert report["fleet_trace_ok"], json.dumps(report, indent=2)


def test_three_process_distributed_run_non_power_of_two():
    """Three coordinator-connected processes, 2 devices each — a 6-device
    global fleet. Non-power-of-two process counts exercise the shapes the
    2×4 run cannot: the data-axis round-robin hands UNEVEN dispatch counts
    to the slices (7 grid groups over 6 slices), the ring exchange runs
    6 ppermute hops with 4 of every 6 crossing a process boundary, and the
    hier schedule factors the samples axis 3×2. The fleet rehearsal's
    4-contig split over 3 hosts is uneven by construction ([2,1,1]) — the
    1/H+overshoot bound and the exact partition-sum still hold."""
    report = verify_multihost(num_processes=3, local_devices=2)
    assert report["gramian_ok"], json.dumps(report, indent=2)
    assert report["ring_gramian_ok"], json.dumps(report, indent=2)
    assert report["hier_gramian_ok"], json.dumps(report, indent=2)
    assert report["result_spans_processes"], json.dumps(report, indent=2)
    for child in report["children"]:
        assert child["global_devices"] == 6, child
        assert child["local_devices"] == 2, child
    assert report["cli_ok"], json.dumps(report, indent=2)
    assert report["cli_outputs_identical"], json.dumps(report, indent=2)
    assert report["cli_pc_lines"] == 24, json.dumps(report, indent=2)
    assert report["fleet_host_sharded"], json.dumps(report, indent=2)
    assert report["fleet_io_ok"], json.dumps(report, indent=2)
    assert report["fleet_conformance_ok"], json.dumps(report, indent=2)
    assert report["fleet_trace_ok"], json.dumps(report, indent=2)


def test_child_cli_exits_nonzero_on_bad_coordinator():
    """A child whose coordinator is unreachable must fail loudly within its
    initialization timeout — not hang, not fall back to single-process."""
    from spark_examples_tpu.parallel.multihost import _child_env

    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "from spark_examples_tpu.parallel.mesh import distributed_init\n"
            # Port 1 is never listening; a non-coordinator process (id 1)
            # must give up after the timeout rather than retry forever.
            "distributed_init('127.0.0.1:1', 2, 1, initialization_timeout=5)",
        ],
        env=_child_env(1),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0


def test_partial_cluster_flags_rejected():
    """Partially-specified cluster flags must raise, not silently fall back
    to a single-process run over 1/N of the fleet."""
    import pytest

    from spark_examples_tpu.parallel.mesh import distributed_init

    with pytest.raises(ValueError, match="num-processes"):
        distributed_init("127.0.0.1:1", None, 0)
