"""Benchmark: 1000 Genomes whole-genome PCoA on one TPU chip, end to end.

Baseline (BASELINE.md): the reference runs the whole-genome 1KG phase 1 PCoA
(2,504 samples, ~39.4M variant sites) in ~2 hours on 40 CPU cores
(``/root/reference/README.md:126-138``). North star: < 5 minutes on a v5e-8.

This is a TRUE ingest-inclusive run of the flagship pipeline
(``VariantsPcaDriver``), not a projection:

- the synthetic cohort is sized to the real workload: 2,504 samples and a
  site grid of ≥39.4M candidate sites across the 22 autosomes
  (``--all-references`` semantics, spacing 73 ≈ 2.88 Gb / 39.4M);
- ingest is INSIDE the timed region: the host streams per-site thresholds
  (the variant-metadata plane) while the device generates the genotype data
  plane and accumulates the Gramian, fused per dispatch
  (``ops/devicegen.py``);
- finalize (Gower centering + subspace-iteration PCA of the 2504×2504
  matrix) and the result fetch are inside the timed region;
- only compilation is excluded (warmed on a small contig first; the
  persistent cache makes it a no-op on reruns). The run is timed to the
  fetched (N, num_pc) result, so nothing is left in flight.

Prints exactly one JSON line (driver stage prints are redirected to stderr).
"""

import argparse
import contextlib
import json
import shutil
import os
import sys
import time

import numpy as np

N_SAMPLES = 2504
VARIANT_SPACING = 73  # 2.881 Gb autosomes / 73 = 39.5M sites >= 1KG's 39.4M
BASELINE_SECONDS = 7200.0
# Measured optimum on v5e (DESIGN.md "single-chip ingest roofline"): large
# dispatch groups amortize per-dispatch overhead; contig remainders run
# through the accumulator's ~K/8 tail program, so group padding stays <2%.
# BLOCKS_PER_DISPATCH defaults to the driver's constant-work auto rule
# (small cohorts get longer scans — ops/devicegen.py:auto_blocks_per_dispatch,
# platinum ~2× faster: 1.03 → 0.53 s); BENCH_BLOCKS_PER_DISPATCH pins it.
BLOCK = int(os.environ.get("BENCH_BLOCK", 16384))
BLOCKS_PER_DISPATCH = (
    int(os.environ["BENCH_BLOCKS_PER_DISPATCH"])
    if "BENCH_BLOCKS_PER_DISPATCH" in os.environ
    else None
)

# The BASELINE.json benchmark configs (plus a beyond-reference large-cohort
# demo). Only whole-genome has a published
# reference number (7200 s); the others report wall-clock with
# vs_baseline=null.
CONFIGS = {
    "whole-genome": {
        "metric": "1000G whole-genome PCoA wall-clock",
        "args": ["--all-references"],
        "sets": ["bench-1kg"],
        "baseline_seconds": BASELINE_SECONDS,
    },
    "brca1": {
        "metric": "BRCA1-region PCoA wall-clock (reference default config)",
        "args": ["--references", "17:41196311:41277499"],
        "sets": ["bench-1kg"],
        "baseline_seconds": None,
    },
    "chr17": {
        "metric": "single-chromosome (chr17) PCoA wall-clock",
        "args": ["--references", "17:0:81195210"],
        "sets": ["bench-1kg"],
        "baseline_seconds": None,
    },
    "platinum": {
        # Platinum Genomes is a SMALL deep-call cohort (~17 genomes), not a
        # second 2,504-sample set — the honest model of the reference's
        # second public variant set (``SearchVariantsExample.scala:28``).
        "metric": "Platinum-style deep-call cohort (17 samples) whole-genome PCoA wall-clock",
        "args": ["--all-references"],
        "sets": ["bench-platinum"],
        "num_samples": 17,
        "baseline_seconds": None,
    },
    "large-cohort": {
        # Beyond-reference scale demo: a 25,000-sample cohort (10x 1KG) —
        # the regime the reference's in-memory strategy guidance warns about
        # (~50K samples ~ 20 GB, VariantsPca.scala:216-217). No strategy
        # override: the HBM-derived auto rule
        # (ops/gramian.py:dense_strategy_fits) picks dense here (the int32
        # Gramian is 2.5 GB; ~4 working copies still fit v5e's 16 GB).
        "metric": "large-cohort (25,000 samples) chr17 PCoA wall-clock",
        "args": ["--references", "17:0:81195210"],
        "sets": ["bench-1kg"],
        "num_samples": 25_000,
        "baseline_seconds": None,
    },
    "large-cohort-sharded": {
        # The SHARDED large-cohort regime (getSimilarityMatrixStream's
        # memory-bounded analog): same 25,000-sample chr17 workload forced
        # through the samples-sharded ring so the bit-packed, overlapped
        # ring exchange (ops/gramian.py:_ring_tiles) is measured — and its
        # gramian_ring_bytes manifest counter surfaces packed-vs-unpacked
        # ICI traffic directly. Needs >= 2 devices for a samples axis; the
        # mesh is resolved at runtime (all devices on samples).
        "metric": "large-cohort (25,000 samples) chr17 sharded-ring PCoA wall-clock",
        "args": ["--references", "17:0:81195210"],
        "sets": ["bench-1kg"],
        "num_samples": 25_000,
        "sharded": True,
        "baseline_seconds": None,
    },
    "merged": {
        # The reference's ACTUAL joint-cohort scenario: 1000 Genomes (2,504
        # samples) joined with Platinum (~17 deep genomes) at shared sites
        # (``VariantsPca.scala:155-168``) — an ASYMMETRIC 2,521-column join,
        # not two identical cohorts. ONE references list for both sets (the
        # Scala zip-truncation semantics, GenomicsConf.scala:91-95): each
        # autosome is scanned once.
        "metric": "merged 1000G+Platinum joint-cohort PCoA wall-clock (2521 columns)",
        "args": ["--references", "AUTOSOMES"],
        "sets": ["bench-1kg", "bench-platinum"],
        "cohort_sizes": {"bench-platinum": 17},
        "baseline_seconds": None,
    },
}


# -------------------------------------------------------------- analyses bench
# The population-genetics analyses (analyses/: GRM/kinship, windowed LD
# pruning, association scan) ride the host-fed packed block stream — the
# per-site workload layer on the same substrate. Each config is a
# chromosome-17-scale synthetic cohort (1KG sample count) reported from the
# run MANIFEST like every device config; wall-clock includes the analysis's
# (small, stateless) kernel compiles — there is no PCA-style warmup split
# because per-block kernels compile once in milliseconds, not tens of
# seconds.

ANALYSIS_REFERENCES = "17:0:81195210"

ANALYSIS_CONFIGS = {
    "grm": {
        "metric": (
            "GRM/kinship (VanRaden, 2,504 samples, chr17) wall-clock"
        ),
    },
    "ld-prune": {
        "metric": (
            "windowed LD r² prune (2,504 samples, chr17, window 256) "
            "wall-clock"
        ),
    },
    "assoc-scan": {
        "metric": (
            "per-site case/control chi-square scan (2,504 samples, chr17) "
            "wall-clock"
        ),
    },
}


# ------------------------------------------------------------ serve bench
# The serving layer under mixed traffic: an in-process resident service
# (serve/) with executor slices, driven through the REAL HTTP API by
# concurrent submitters. Reports P50/P99 per admission class in two
# phases — small jobs alone (unloaded), then small jobs while a large job
# holds the large slice (loaded) — so the number that matters to users
# ("does a cheap query stall behind a whole-genome run?") is measured,
# not argued. ci.sh asserts loaded small P99 <= ~2x unloaded.

SERVE_LOAD_SMALL_FLAGS = ["--num-samples", "8", "--references", "1:0:50000"]
SERVE_LOAD_LARGE_FLAGS = [
    "--num-samples",
    "16",
    "--references",
    "1:0:2500000",
]
#: Classify the large-phase job as LARGE without waiting minutes on CPU:
#: the limit sits between the small (~500 sites) and large (~25k sites)
#: shapes above.
SERVE_LOAD_SITE_LIMIT = 5_000
SERVE_LOAD_SMALL_JOBS = 12


def _percentile(values, fraction: float) -> float:
    ordered = sorted(values)
    index = min(len(ordered) - 1, int(round(fraction * (len(ordered) - 1))))
    return ordered[index]


def _small_wall_snapshot(service) -> dict:
    """One merged ``serve_job_wall_seconds`` snapshot for the small
    admission class (all kinds and compile dispositions) — the same
    histograms ``/v1/fleet/stats`` and the calibration ledger ride on,
    so the bench reports the numbers operators will actually see."""
    from spark_examples_tpu.obs.metrics import SERVE_JOB_WALL_SECONDS

    merged = {"buckets": {}, "sum": 0.0, "count": 0}
    family = service.registry.get(SERVE_JOB_WALL_SECONDS)
    if family is None:
        return merged
    for child in family.children():
        if child.labels_dict.get("job_class") != "small":
            continue
        snap = child.snapshot()
        for bound, cumulative in snap["buckets"].items():
            merged["buckets"][bound] = merged["buckets"].get(bound, 0) + int(
                cumulative
            )
        merged["sum"] += float(snap["sum"])
        merged["count"] += int(snap["count"])
    return merged


def _snapshot_delta(after: dict, before: dict) -> dict:
    """The histogram increments one bench phase contributed: cumulative
    bucket counts subtract bound-by-bound (children of one family share
    bounds, and counts only grow)."""
    bounds = set(after["buckets"]) | set(before["buckets"])
    return {
        "buckets": {
            bound: after["buckets"].get(bound, 0)
            - before["buckets"].get(bound, 0)
            for bound in bounds
        },
        "sum": after["sum"] - before["sum"],
        "count": after["count"] - before["count"],
    }


def _phase_quantiles(delta: dict, phase: str) -> dict:
    from spark_examples_tpu.obs.metrics import histogram_quantile

    if delta["count"] <= 0:
        raise RuntimeError(
            f"serve-load {phase} phase recorded no small-job wall samples"
        )
    return {
        "count": delta["count"],
        "mean": round(delta["sum"] / delta["count"], 4),
        "p50": round(histogram_quantile(delta, 0.50), 4),
        "p99": round(histogram_quantile(delta, 0.99), 4),
    }


#: Identical small jobs per fused-batch phase group: enough lanes that a
#: one-dispatch group visibly amortizes per-job device dispatch, small
#: enough that the serial reference stays quick on CPU.
SERVE_FUSED_GROUP_JOBS = 6
#: Cheap jobs queued behind the expensive job in the ordering phase.
SERVE_ORDERING_CHEAP_JOBS = 6
#: The ordering phase's expensive shape: compute-bound (the N² Gramian
#: update, not the site count) so its WARM run holds the single worker
#: long enough that the cheap jobs demonstrably queue behind (FIFO) or
#: jump past (cost) the second expensive submission.
SERVE_ORDERING_EXPENSIVE_FLAGS = [
    "--num-samples",
    "128",
    "--references",
    "1:0:10000000",
]
#: One class lane for the whole ordering phase: the site limit sits
#: ABOVE the expensive shape, so cheap and expensive share a lane and
#: the ordering under test is within-lane.
SERVE_ORDERING_SITE_LIMIT = 500_000


def _submit_small_jobs(service, flags, count) -> list:
    from spark_examples_tpu.serve.protocol import request_doc

    ids = []
    for _ in range(count):
        status, doc = service.submit(request_doc(flags))
        if status != 202:
            raise RuntimeError(f"serve bench submit rejected {status}: {doc}")
        ids.append(doc["job"]["id"])
    return ids


def _wait_jobs(service, ids, timeout: float = 600.0) -> list:
    jobs = []
    deadline = time.time() + timeout
    for jid in ids:
        while True:
            _, doc = service.job_status(jid)
            job = doc["job"]
            if job["status"] in ("done", "failed", "cancelled"):
                break
            if time.time() > deadline:
                raise RuntimeError(f"serve bench timed out waiting on {jid}")
            time.sleep(0.02)
        if job["status"] != "done":
            raise RuntimeError(f"serve bench job failed: {job}")
        jobs.append(job)
    return jobs


def _run_fused_group(batch_fuse: bool) -> dict:
    """One group of identical small jobs through an in-process service:
    fusion on (one stacked device program per group) or off (the same
    batch group back to back). Returns the group's summed executor
    seconds, its result rows (for the byte-parity check), and the
    dispatch counters proving which path ran."""
    import tempfile

    from spark_examples_tpu.serve.daemon import PcaService

    run_dir = tempfile.mkdtemp(prefix="serve_fused_")
    service = PcaService(
        run_dir=run_dir,
        small_slices=0,
        batch_fuse=batch_fuse,
        batch_max_jobs=SERVE_FUSED_GROUP_JOBS,
        batch_linger_seconds=2.0,
    ).start()
    try:
        # Warmup one FULL group, not one job: the serial path's per-job
        # program and the fused path's K-lane stacked program both
        # compile here, so the measured group compares steady-state
        # dispatch (the resident daemon's compile-once regime), not one
        # path's cold compile against the other's warm cache.
        _wait_jobs(
            service,
            _submit_small_jobs(
                service, SERVE_LOAD_SMALL_FLAGS, SERVE_FUSED_GROUP_JOBS
            ),
        )
        t0 = time.perf_counter()
        ids = _submit_small_jobs(
            service, SERVE_LOAD_SMALL_FLAGS, SERVE_FUSED_GROUP_JOBS
        )
        jobs = _wait_jobs(service, ids)
        wall = time.perf_counter() - t0
        dispatch = service.fleet_stats()["dispatch"]
    finally:
        service.stop(timeout=60)
        shutil.rmtree(run_dir, ignore_errors=True)
    return {
        "executor_seconds": sum(job["seconds"] for job in jobs),
        "client_wall_seconds": wall,
        "pc_lines": [job["result"]["pc_lines"] for job in jobs],
        "fused_sizes": [job["fused_size"] for job in jobs],
        "dispatch": dispatch,
    }


def _run_fused_batch_phase() -> dict:
    """The fused-batch phase: one K-job group fused (one device program)
    vs the identical group with ``--no-batch-fuse`` (back to back),
    byte-parity asserted, group throughput compared."""
    fused = _run_fused_group(batch_fuse=True)
    serial = _run_fused_group(batch_fuse=False)
    if fused["dispatch"]["fused_groups"] < 1:
        raise RuntimeError(
            f"fused-batch phase never fused a group: {fused['dispatch']}"
        )
    if serial["dispatch"]["fused_groups"] != 0:
        raise RuntimeError(
            f"--no-batch-fuse config fused anyway: {serial['dispatch']}"
        )
    reference = serial["pc_lines"][0]
    for source, lines_per_job in (("fused", fused["pc_lines"]),
                                  ("serial", serial["pc_lines"])):
        for lines in lines_per_job:
            if lines != reference:
                raise RuntimeError(
                    f"fused-batch phase {source} results diverged from the "
                    "serial reference — byte parity broken"
                )
    throughput_ratio = (
        serial["executor_seconds"] / fused["executor_seconds"]
        if fused["executor_seconds"] > 0
        else None
    )
    return {
        "group_jobs": SERVE_FUSED_GROUP_JOBS,
        "byte_identical": True,
        "fused": {
            "executor_seconds": round(fused["executor_seconds"], 4),
            "client_wall_seconds": round(fused["client_wall_seconds"], 4),
            "fused_sizes": fused["fused_sizes"],
            "dispatch": fused["dispatch"],
        },
        "serial": {
            "executor_seconds": round(serial["executor_seconds"], 4),
            "client_wall_seconds": round(serial["client_wall_seconds"], 4),
            "dispatch": serial["dispatch"],
        },
        # >1 means the one-program group outran the same jobs back to
        # back on the identical warm service.
        "group_throughput_ratio": (
            round(throughput_ratio, 3) if throughput_ratio is not None else None
        ),
    }


def _run_ordering_config(ordering: str) -> dict:
    """Mixed load through one worker lane under the given queue
    ordering: an expensive job queued FIRST, cheap jobs behind it, all
    while a blocker holds the worker — cost ordering should pop the
    cheap jobs past the expensive one, FIFO must not."""
    import tempfile

    from spark_examples_tpu.serve.daemon import PcaService
    from spark_examples_tpu.serve.protocol import request_doc

    run_dir = tempfile.mkdtemp(prefix="serve_order_")
    service = PcaService(
        run_dir=run_dir,
        small_slices=0,
        ordering=ordering,
        small_site_limit=SERVE_ORDERING_SITE_LIMIT,
        batch_max_jobs=SERVE_ORDERING_CHEAP_JOBS,
    ).start()
    try:
        # Warm both geometries so the measured phase compares scheduling,
        # not compilation.
        _wait_jobs(service, _submit_small_jobs(service, SERVE_LOAD_SMALL_FLAGS, 1))
        _wait_jobs(
            service,
            _submit_small_jobs(service, SERVE_ORDERING_EXPENSIVE_FLAGS, 1),
        )
        # The blocker occupies the worker while the contested queue forms.
        blocker = _submit_small_jobs(
            service, SERVE_ORDERING_EXPENSIVE_FLAGS, 1
        )
        expensive = _submit_small_jobs(
            service, SERVE_ORDERING_EXPENSIVE_FLAGS, 1
        )
        cheap = _submit_small_jobs(
            service, SERVE_LOAD_SMALL_FLAGS, SERVE_ORDERING_CHEAP_JOBS
        )
        jobs = _wait_jobs(service, blocker + expensive + cheap)
    finally:
        service.stop(timeout=60)
        shutil.rmtree(run_dir, ignore_errors=True)
    cheap_latency = [
        job["finished_unix"] - job["submitted_unix"] for job in jobs[2:]
    ]
    return {
        "ordering": ordering,
        "cheap_jobs": SERVE_ORDERING_CHEAP_JOBS,
        "cheap_p50_seconds": round(_percentile(cheap_latency, 0.5), 4),
        "cheap_p99_seconds": round(_percentile(cheap_latency, 0.99), 4),
        "expensive_latency_seconds": round(
            jobs[1]["finished_unix"] - jobs[1]["submitted_unix"], 4
        ),
    }


def _run_cost_ordering_phase() -> dict:
    """Cost-ordered scheduling vs FIFO on the identical mixed load: the
    number that justifies SJF-within-class — how much queue-wait the
    cheap jobs stop paying for one expensive job ahead of them."""
    cost = _run_ordering_config("cost")
    fifo = _run_ordering_config("fifo")
    ratio = (
        fifo["cheap_p99_seconds"] / cost["cheap_p99_seconds"]
        if cost["cheap_p99_seconds"] > 0
        else None
    )
    return {
        "cost": cost,
        "fifo": fifo,
        # >1 means cost ordering cut the cheap jobs' P99 vs FIFO.
        "fifo_over_cost_p99": round(ratio, 3) if ratio is not None else None,
    }


def _serve_load_phase(client, jobs: int) -> list:
    """Submit ``jobs`` small jobs one after another (a poller's view:
    submit -> terminal), returning per-job wall seconds."""
    latencies = []
    for _ in range(jobs):
        t0 = time.perf_counter()
        doc = client.submit(SERVE_LOAD_SMALL_FLAGS)
        job = client.wait(doc["job"]["id"], timeout=300, poll_cap_seconds=0.1)
        if job["job"]["status"] != "done":
            raise RuntimeError(f"serve-load small job failed: {job}")
        latencies.append(time.perf_counter() - t0)
    return latencies


def _run_serve_load_config(device) -> dict:
    import tempfile

    import jax

    from spark_examples_tpu.serve.client import ServeClient
    from spark_examples_tpu.serve.daemon import PcaService
    from spark_examples_tpu.serve.http import start_server

    device_count = len(jax.devices())
    run_dir = tempfile.mkdtemp(prefix="serve_load_")
    service = PcaService(
        run_dir=run_dir,
        small_slices=None,  # auto: 1 small slice when a device is spare
        small_site_limit=SERVE_LOAD_SITE_LIMIT,
    ).start()
    server = start_server(service)
    client = ServeClient(server.url)
    sliced = len(service._workers) > 1
    try:
        # Warmup: compile the small geometry once (cold compile is the
        # daemon's startup cost, not a steady-state latency).
        warm = client.submit(SERVE_LOAD_SMALL_FLAGS)
        client.wait(warm["job"]["id"], timeout=300, poll_cap_seconds=0.1)

        baseline_snap = _small_wall_snapshot(service)
        unloaded = _serve_load_phase(client, SERVE_LOAD_SMALL_JOBS)
        unloaded_snap = _small_wall_snapshot(service)

        large_doc = client.submit(SERVE_LOAD_LARGE_FLAGS)
        large_id = large_doc["job"]["id"]
        if large_doc["job"]["class"] != "large":
            raise RuntimeError(
                f"serve-load large job classified {large_doc['job']['class']}"
            )
        t_large = time.perf_counter()
        loaded = _serve_load_phase(client, SERVE_LOAD_SMALL_JOBS)
        loaded_snap = _small_wall_snapshot(service)
        large = client.wait(large_id, timeout=600, poll_cap_seconds=0.2)
        large_seconds = time.perf_counter() - t_large
        if large["job"]["status"] != "done":
            raise RuntimeError(f"serve-load large job failed: {large}")
        health = client.healthz()
        # The observability surface under test: the HTTP fleet-stats
        # document must exist and carry the same class quantiles.
        import urllib.request

        with urllib.request.urlopen(
            server.url + "/v1/fleet/stats", timeout=30
        ) as resp:
            fleet = json.loads(resp.read().decode("utf-8"))
    finally:
        server.shutdown()
        service.stop(timeout=60)
        shutil.rmtree(run_dir, ignore_errors=True)

    # The fused-batch and queue-ordering phases ride their own
    # single-lane services (the contested topology each needs), after
    # the mixed-load service released the devices.
    fused_batch = _run_fused_batch_phase()
    cost_ordering = _run_cost_ordering_phase()

    unloaded_stats = _phase_quantiles(
        _snapshot_delta(unloaded_snap, baseline_snap), "unloaded"
    )
    loaded_stats = _phase_quantiles(
        _snapshot_delta(loaded_snap, unloaded_snap), "loaded"
    )
    unloaded_p99 = unloaded_stats["p99"]
    loaded_p99 = loaded_stats["p99"]
    ratio = loaded_p99 / unloaded_p99 if unloaded_p99 > 0 else None
    return {
        "metric": (
            "small-job P99 under concurrent large-job load vs unloaded "
            "(resident service, executor slices)"
        ),
        "value": round(ratio, 3) if ratio is not None else None,
        "unit": "x",
        "vs_baseline": None,
        "details": {
            "devices": device_count,
            "slices": [
                {"name": s["name"], "devices": s["devices"]}
                for s in health["slices"]
            ],
            "sliced": sliced,
            "small_jobs_per_phase": SERVE_LOAD_SMALL_JOBS,
            # Server-side wall quantiles from `serve_job_wall_seconds`
            # snapshot deltas — the metric `/v1/fleet/stats` serves.
            "small_unloaded_seconds": unloaded_stats,
            "small_loaded_seconds": loaded_stats,
            # Client-observed submit->terminal latency, for comparison
            # with the server-side histograms (includes HTTP + polling).
            "client_observed_seconds": {
                "unloaded_p50": round(_percentile(unloaded, 0.5), 4),
                "unloaded_p99": round(_percentile(unloaded, 0.99), 4),
                "loaded_p50": round(_percentile(loaded, 0.5), 4),
                "loaded_p99": round(_percentile(loaded, 0.99), 4),
            },
            "fleet_stats": {
                "classes": fleet.get("classes"),
                "calibration": fleet.get("calibration"),
                "dispatch": fleet.get("dispatch"),
                "counters": fleet.get("counters"),
            },
            # One K-job group fused (one stacked device program) vs the
            # identical group back to back, byte parity asserted.
            "fused_batch": fused_batch,
            # Cost-ordered (SJF) vs FIFO on the identical mixed load.
            "cost_ordering": cost_ordering,
            "large_job_seconds": round(
                large["job"]["seconds"] or large_seconds, 3
            ),
            "loaded_over_unloaded_p99": (
                round(ratio, 3) if ratio is not None else None
            ),
            "device": str(device),
        },
    }


# --------------------------------------------------------- multihost bench
# Pod ingest scaling: a REAL 2-process gloo fleet (parallel/multihost.py)
# running the unmodified variants-pca CLI with HOST-SHARDED ingest — each
# process reads only its contig partition. The headline number is the
# largest per-host share of the solo run's ingested reference bases: ~1/H
# means ingest bandwidth scales linearly with hosts (the PR's claim), 1.0
# would mean every host still reads everything. Correctness rides along:
# the report is only accepted when the fleet's PC rows are byte-identical
# to the solo oracle and every per-host conformance bound holds.

MULTIHOST_PROCESSES = 2
MULTIHOST_LOCAL_DEVICES = 2


def _run_multihost_config(device) -> dict:
    from spark_examples_tpu.parallel.multihost import verify_multihost

    report = verify_multihost(
        num_processes=MULTIHOST_PROCESSES,
        local_devices=MULTIHOST_LOCAL_DEVICES,
    )
    if not report.get("ok"):
        raise RuntimeError(
            "multihost fleet rehearsal failed: "
            + json.dumps({k: v for k, v in report.items() if k != "children"})
        )
    bases = report["fleet_io_reference_bases"]
    solo_bases = int(bases["solo"])
    per_process = [int(b) for b in bases["per_process"]]
    fractions = [round(b / solo_bases, 4) for b in per_process]
    max_fraction = max(fractions)
    return {
        "metric": (
            f"host-sharded pod ingest: largest per-host share of solo "
            f"ingest bytes ({MULTIHOST_PROCESSES}-process gloo fleet, "
            "PC rows byte-identical to the solo oracle)"
        ),
        "value": max_fraction,
        "unit": "fraction of solo ingest per host",
        # Baseline: the pre-host-sharding data path, where every host read
        # the whole input (fraction 1.0 per host).
        "vs_baseline": round(1.0 / max_fraction, 2) if max_fraction else None,
        "details": {
            "num_processes": MULTIHOST_PROCESSES,
            "local_devices_per_process": MULTIHOST_LOCAL_DEVICES,
            "solo_reference_bases": solo_bases,
            "per_process_reference_bases": per_process,
            "per_process_fraction_of_solo": fractions,
            "partition_sum_exact": sum(per_process) == solo_bases,
            "wall_seconds": report.get("fleet_wall_seconds"),
            "cli_outputs_identical": report["cli_outputs_identical"],
            "cli_pc_lines": report["cli_pc_lines"],
            "hier_gramian_ok": report["hier_gramian_ok"],
            "fleet_conformance_ok": report["fleet_conformance_ok"],
            "fleet_trace_ok": report["fleet_trace_ok"],
            "device": str(device),
            "baseline": (
                "every host reading the whole input (per-host fraction 1.0; "
                "the pre-pod-ingest data path)"
            ),
        },
    }


def _write_bench_phenotypes(path: str, conf) -> None:
    """A balanced case/control TSV over the synthetic cohort's real
    callset names (the assoc verb's strict both-ways coverage check)."""
    from spark_examples_tpu.pipeline.pca_driver import make_source

    names = [
        cs["name"]
        for cs in make_source(conf).search_callsets(conf.variant_set_id)
    ]
    with open(path, "w") as f:
        for i, name in enumerate(names):
            f.write(f"{name}\t{i % 2}\n")


def _run_analysis_config(name: str, device) -> dict:
    import tempfile

    from spark_examples_tpu.obs.manifest import validate_manifest

    tmpdir = tempfile.mkdtemp(prefix="analyses_bench_")
    try:
        manifest_path = os.path.join(tmpdir, "manifest.json")
        base = [
            "--num-samples", str(N_SAMPLES),
            "--references", ANALYSIS_REFERENCES,
            "--block-size", "4096",
            "--metrics-json", manifest_path,
        ]
        if name == "grm":
            from spark_examples_tpu.analyses.grm import run_grm_pipeline
            from spark_examples_tpu.config import GrmConf

            conf = GrmConf.parse(base)
            start = time.perf_counter()
            result = run_grm_pipeline(conf)
            wall = time.perf_counter() - start
            manifest = result.manifest
            extra = {"kinship_summary": result.summary}
        elif name == "ld-prune":
            from spark_examples_tpu.analyses.ld import run_ld_pipeline
            from spark_examples_tpu.config import LdConf

            conf = LdConf.parse(
                base + ["--ld-r2-threshold", "0.2", "--ld-window-sites", "256"]
            )
            start = time.perf_counter()
            result = run_ld_pipeline(conf)
            wall = time.perf_counter() - start
            manifest = result.manifest
            extra = {
                "sites_kept": result.sites_kept,
                "kept_fraction": (
                    round(result.sites_kept / result.sites_tested, 4)
                    if result.sites_tested
                    else None
                ),
            }
        else:  # assoc-scan
            from spark_examples_tpu.analyses.assoc import run_assoc_pipeline
            from spark_examples_tpu.config import AssocConf

            phenotypes = os.path.join(tmpdir, "phenotypes.tsv")
            conf = AssocConf.parse(base + ["--phenotypes", phenotypes])
            _write_bench_phenotypes(phenotypes, conf)
            start = time.perf_counter()
            result = run_assoc_pipeline(conf)
            wall = time.perf_counter() - start
            manifest = result.manifest
            extra = {
                "cases": result.n_cases,
                "controls": result.n_controls,
                "top_chi2": result.top[0][0] if result.top else None,
            }
        schema_errors = validate_manifest(manifest)
        assert not schema_errors, schema_errors
        analysis = manifest["analysis"]
        sites = int(analysis["sites_tested"])
        return {
            "metric": ANALYSIS_CONFIGS[name]["metric"],
            "value": round(wall, 3),
            "unit": "s",
            "vs_baseline": None,
            "details": {
                "analysis": analysis,
                "sites_per_sec": round(sites / wall) if wall > 0 else None,
                "compile_seconds_excluded": 0.0,
                **extra,
                "device": str(device),
                "baseline": (
                    "no published reference number for this analysis"
                ),
            },
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


# ---------------------------------------------------------------- ingest bench
# The file-ingest data plane (chunk-parallel native parse + prefetch +
# double-buffered device feed) is benchmarked apart from the device configs:
# it is host-side, deterministic, and the one stage the 2h/40-core baseline
# was actually bound by (SURVEY.md §7 — ingest, not math).

INGEST_FIXTURE_SAMPLES = 64
INGEST_FIXTURE_ROWS = 40_000  # × ~3-400 B/row ≈ 12 MB decompressed


def _write_ingest_fixture(path: str) -> None:
    rng = np.random.default_rng(20_24)
    gt_choices = np.array(["0|0", "0|1", "1|1", ".|."])
    with open(path, "w") as f:
        f.write("##fileformat=VCFv4.2\n")
        f.write(
            "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\t"
            + "\t".join(f"S{i:03d}" for i in range(INGEST_FIXTURE_SAMPLES))
            + "\n"
        )
        gts = gt_choices[
            rng.integers(0, len(gt_choices),
                         (INGEST_FIXTURE_ROWS, INGEST_FIXTURE_SAMPLES))
        ]
        for k in range(INGEST_FIXTURE_ROWS):
            info = f"AF={rng.random():.4f}" if k % 4 else "NS=2"
            f.write(
                f"17\t{100 + 37 * k}\t.\tAC\tG\t.\t.\t{info}\tGT\t"
                + "\t".join(gts[k])
                + "\n"
            )


def _run_ingest_config(device) -> dict:
    import tempfile

    tmpdir = tempfile.mkdtemp(prefix="ingest_bench_")
    try:
        return _run_ingest_measurements(tmpdir, device)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _run_ingest_measurements(tmpdir: str, device) -> dict:
    from spark_examples_tpu.ops.gramian import GramianAccumulator
    from spark_examples_tpu.pipeline.datasets import PrefetchIterator
    from spark_examples_tpu.sources.files import (
        _PackedVcf,
        _StreamedVcf,
        default_ingest_workers,
    )
    from spark_examples_tpu.utils.native import native_unavailable_reason

    path = os.path.join(tmpdir, "bench.vcf")
    _write_ingest_fixture(path)
    size_mb = os.path.getsize(path) / 1e6

    # Parse throughput vs worker count, best of 2 (first run also pays the
    # one-time native build; the repeat isolates steady-state parse).
    counts = sorted({0, 1, 2, 4, default_ingest_workers()})
    seconds = {}
    for workers in counts:
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            view = _PackedVcf(path, "bench", ingest_workers=workers)
            best = min(best, time.perf_counter() - t0)
        seconds[workers] = best
    native = view.native
    per_worker = {
        str(w): {
            "seconds": round(s, 3),
            "mb_per_s": round(size_mb / s, 1),
            "speedup_vs_serial": round(seconds[0] / s, 2),
        }
        for w, s in seconds.items()
    }

    # Ingest/compute overlap: the streamed fixture through the bounded
    # prefetch queue into the double-buffered Gramian feed — the exact
    # driver wiring (pipeline/pca_driver.py:_similarity_stage), measured at
    # the component seam so the numbers are profiler-free.
    view = _StreamedVcf(
        path, "bench", chunk_bytes=1 << 20,
        ingest_workers=default_ingest_workers(),
    )
    acc = GramianAccumulator(
        INGEST_FIXTURE_SAMPLES, block_size=2048, pipeline_depth=2
    )
    t0 = time.perf_counter()
    prefetch = PrefetchIterator(
        (hv for _, _, _, _, hv in view.iter_chunk_arrays()), depth=2
    )
    try:
        for hv in prefetch:
            acc.add_rows(hv)
        wall = time.perf_counter() - t0
        acc.finalize_device()
    finally:
        prefetch.close()
    # Structured overlap accounting straight from the iterator (the same
    # dict the run manifest embeds); the one-line report rides along for
    # humans reading the JSON.
    overlap = {
        "wall_seconds": round(wall, 3),
        **{
            key: round(value, 3) if isinstance(value, float) else value
            for key, value in prefetch.overlap_stats().items()
        },
        "report": prefetch.overlap_report(),
    }

    best_workers = min(seconds, key=seconds.get)
    return {
        "metric": (
            f"chunk-parallel native VCF parse ({size_mb:.1f} MB, "
            f"{INGEST_FIXTURE_ROWS} rows × {INGEST_FIXTURE_SAMPLES} samples)"
        ),
        "value": per_worker[str(best_workers)]["mb_per_s"],
        "unit": "MB/s",
        "vs_baseline": per_worker[str(best_workers)]["speedup_vs_serial"],
        "details": {
            "native_parser": native,
            "native_unavailable_reason": (
                None if native else native_unavailable_reason()
            ),
            "host_cpus": os.cpu_count(),
            "default_ingest_workers": default_ingest_workers(),
            "parse_by_workers": per_worker,
            "ingest_compute_overlap": overlap,
            "baseline": "serial oracle path (--ingest-workers 0), same host",
            "device": str(device),
        },
    }


def _autosome_references() -> str:
    from spark_examples_tpu.constants import Examples

    return ",".join(
        f"{name}:0:{length}"
        for name, length in Examples.HUMAN_CHROMOSOMES.items()
        if name not in ("X", "Y")
    )


def _make_driver(conf_args, source):
    from spark_examples_tpu.config import PcaConf
    from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver

    conf = PcaConf.parse(conf_args)
    return conf, VariantsPcaDriver(conf, source)


def _run_config(name: str, device) -> dict:
    import jax

    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    config = CONFIGS[name]
    if config.get("sharded") and len(jax.devices()) < 2:
        return {
            "metric": config["metric"],
            "value": None,
            "unit": "s",
            "vs_baseline": None,
            "details": {
                "skipped": "sharded ring needs >= 2 devices for a samples "
                f"axis; have {len(jax.devices())}",
                "device": str(device),
            },
        }
    n_sets = len(config["sets"])
    n_samples = config.get("num_samples", N_SAMPLES)
    cohort_sizes = config.get("cohort_sizes")
    per_set_sizes = [
        (cohort_sizes or {}).get(s, n_samples) for s in config["sets"]
    ]
    total_columns = sum(per_set_sizes)
    from spark_examples_tpu.ops.devicegen import auto_blocks_per_dispatch

    # Resolve the scan length the driver will use (explicit env pin, or the
    # constant-work auto rule) — the warmup region must cover one full
    # group of the SAME length or the measured run compiles cold.
    k_resolved = BLOCKS_PER_DISPATCH or auto_blocks_per_dispatch(
        total_columns, BLOCK
    )
    warmup_bases = VARIANT_SPACING * (
        BLOCK * k_resolved + BLOCK * max(1, k_resolved // 8)
    )
    base_args = [
        "--variant-set-id", ",".join(config["sets"]),
        "--ingest", "device",
        "--block-size", str(BLOCK),
        "--num-pc", "2",
        # Per-set cohort sizes; the dense/sharded strategy is left on auto —
        # the HBM-derived rule decides (ops/gramian.py:dense_strategy_fits).
        "--num-samples", ",".join(str(s) for s in per_set_sizes),
    ]
    if BLOCKS_PER_DISPATCH is not None:
        base_args += ["--blocks-per-dispatch", str(BLOCKS_PER_DISPATCH)]
    if config.get("sharded"):
        # All devices on the samples axis: the ring spans the whole chip
        # set and every device holds one row tile of the padded Gramian.
        base_args += [
            "--mesh-shape", f"1,{len(jax.devices())}",
            "--similarity-strategy", "sharded",
        ]
    source = SyntheticGenomicsSource(
        num_samples=n_samples,
        seed=42,
        variant_spacing=VARIANT_SPACING,
        cohort_sizes=cohort_sizes,
    )

    # Warmup: identical shapes (one dispatch group + full-cohort finalize),
    # so every jit in the measured run is compile-cache warm.
    warm_start = time.perf_counter()
    warm_refs = ";".join([f"1:0:{warmup_bases}"] * n_sets)
    conf_w, driver_w = _make_driver(
        base_args + ["--references", warm_refs], source
    )
    contigs_w = conf_w.get_contigs(source, conf_w.variant_set_id)
    S_w = driver_w.get_similarity_device_gen(contigs_w)
    driver_w.compute_pca(S_w)
    compile_seconds = time.perf_counter() - warm_start

    # The measured run, ingest-inclusive.
    run_args = [
        _autosome_references() if a == "AUTOSOMES" else a
        for a in config["args"]
    ]
    conf, driver = _make_driver(base_args + run_args, source)
    contigs = conf.get_contigs(source, conf.variant_set_id)
    start = time.perf_counter()
    S = driver.get_similarity_device_gen(contigs)
    result = driver.compute_pca(S)  # fetches the (N, num_pc) components
    wall = time.perf_counter() - start

    # Per-config numbers come from the run MANIFEST (obs/manifest.py) — the
    # same schema-validated document ``--metrics-json`` writes — not from
    # driver internals: what this benchmark reports is what any operator's
    # manifest would say.
    from spark_examples_tpu.obs.manifest import (
        build_run_manifest,
        manifest_metric_value,
        validate_manifest,
    )
    from spark_examples_tpu.obs.metrics import (
        DEVICEGEN_DISPATCHES,
        DEVICEGEN_SITES_CAPACITY,
        GRAMIAN_RING_BYTES,
        INGEST_SITES_SCANNED,
    )

    manifest = build_run_manifest(
        conf=conf,
        spans=driver.spans,
        registry=driver.registry,
        io_stats=driver.io_stats,
    )
    schema_errors = validate_manifest(manifest)
    assert not schema_errors, schema_errors
    acc = driver._device_gen_acc

    def metric(name):
        value = manifest_metric_value(manifest, name)
        assert value is not None, f"manifest missing metric {name!r}"
        return int(value)

    sites_scanned = metric(INGEST_SITES_SCANNED)
    variant_rows = int(manifest["io_stats"]["variants"])
    dispatches = metric(DEVICEGEN_DISPATCHES)
    assert len(result) == total_columns
    assert all(len(pcs) == 2 for _, pcs in result)

    # Dispatch padding waste: grid capacity dispatched (tail-group padding
    # included) vs the valid sites inside it — the fixed small-run overhead
    # that puts brca1 ~3 orders of magnitude below whole-genome throughput.
    sites_capacity = metric(DEVICEGEN_SITES_CAPACITY)
    padding_waste = (
        round(1.0 - sites_scanned / sites_capacity, 4) if sites_capacity else 0.0
    )
    # Ring-exchange ICI traffic (sharded configs only): straight from the
    # manifest counter, so packed-vs-unpacked is visible per artifact.
    ring_bytes = manifest_metric_value(manifest, GRAMIAN_RING_BYTES)

    # Predicted-vs-measured ring bytes from the manifest's schedule block
    # (sharded runs): the STATIC per-flush projection next to the
    # per-flush-accounted total — a nonzero delta means a counts-fallback
    # flush or formula drift, and BENCH rounds catch it per artifact.
    schedule = manifest.get("schedule") or {}
    sched_predicted = schedule.get("predicted_ring_bytes")
    sched_measured = schedule.get("measured_ring_bytes")
    sched_delta = (
        round(abs(sched_measured - sched_predicted) / sched_predicted, 6)
        if sched_predicted
        else None
    )

    # Host-memory headroom (manifest schema v2): measured peak RSS next to
    # the static bound parallel/mesh.py:host_peak_bytes proves for bounded
    # ingest paths — BENCH artifacts record how much of the proven budget
    # each config actually used.
    host_memory = manifest.get("host_memory") or {}
    host_peak = host_memory.get("peak_rss_bytes")
    host_bound = host_memory.get("static_bound_bytes")

    # Device ingest parallelizes over the mesh — report throughput per chip
    # actually used: data axis × samples axis (the ring accumulator puts
    # every chip to work on the samples axis even at data_parallel=1).
    chips_used = getattr(acc, "data_parallel", 1) * getattr(
        acc, "samples_parallel", 1
    )
    baseline = config["baseline_seconds"]
    return {
        "metric": (
            f"{config['metric']} (end-to-end incl. ingest; "
            f"{total_columns} columns, {sites_scanned} sites)"
        ),
        "value": round(wall, 3),
        "unit": "s",
        "vs_baseline": round(baseline / wall, 2) if baseline else None,
        "details": {
            "sites_scanned": sites_scanned,
            "variant_rows_accumulated": variant_rows,
            "sites_per_sec_per_chip": round(sites_scanned / wall / chips_used),
            "chips_used": chips_used,
            "device_dispatches": dispatches,
            "sites_capacity_dispatched": sites_capacity,
            "dispatch_padding_waste_fraction": padding_waste,
            **(
                {"gramian_ring_bytes": int(ring_bytes)}
                if ring_bytes is not None
                else {}
            ),
            **(
                {
                    "reduce_schedule": schedule.get("kind"),
                    "sched_predicted_bytes": int(sched_predicted),
                    "sched_ring_bytes_delta_fraction": sched_delta,
                }
                if sched_predicted is not None
                else {}
            ),
            **(
                {"host_peak_rss_bytes": int(host_peak)}
                if host_peak is not None
                else {}
            ),
            **(
                {
                    "host_static_bound_bytes": int(host_bound),
                    "host_mem_headroom_fraction": (
                        round(1.0 - host_peak / host_bound, 4)
                        if host_peak is not None and host_bound
                        else None
                    ),
                }
                if host_bound is not None
                else {}
            ),
            # Prover-conformance pairs straight from the manifest block
            # (measured vs proven per prover) — BENCH artifacts carry the
            # regression tripwire verdicts next to the numbers they bound.
            **(
                {"prover_conformance": manifest["conformance"]}
                if manifest.get("conformance")
                else {}
            ),
            "block_size": BLOCK,
            "blocks_per_dispatch": k_resolved,
            "compile_seconds_excluded": round(compile_seconds, 3),
            "gramian_dtype": str(np.dtype("int32")),
            "device": str(device),
            "baseline": (
                "~7200 s on 40 CPU cores (reference README.md:126-138)"
                if baseline
                else "no published reference number for this config"
            ),
        },
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--config",
        choices=sorted(CONFIGS)
        + ["ingest", "serve-load", "multihost"]
        + sorted(ANALYSIS_CONFIGS),
        default=None,
        help=(
            "Run ONE benchmark config (PCA device configs, 'ingest', "
            "'serve-load', 'multihost', or an analyses/ config: grm, "
            "ld-prune, assoc-scan). Default: run ALL "
            "configs and print the whole-genome headline with every "
            "config's result embedded in details.configs — each README "
            "number gets a driver-verified artifact."
        ),
    )
    args = parser.parse_args()

    import jax

    from spark_examples_tpu.utils.cache import (
        compile_cache_entries,
        enable_persistent_compile_cache,
    )

    # The one compile cache every entry point shares (utils/cache.py).
    enable_persistent_compile_cache()
    device = jax.devices()[0]

    if args.config is not None:
        with contextlib.redirect_stdout(sys.stderr):
            if args.config == "ingest":
                payload = _run_ingest_config(device)
            elif args.config == "serve-load":
                payload = _run_serve_load_config(device)
            elif args.config == "multihost":
                payload = _run_multihost_config(device)
            elif args.config in ANALYSIS_CONFIGS:
                payload = _run_analysis_config(args.config, device)
            else:
                payload = _run_config(args.config, device)
        print(json.dumps(payload))
        return

    # All configs, one process: later configs reuse live jit caches where
    # shapes repeat; per-config compile_seconds_excluded and the persistent
    # cache entry counts attribute warm vs cold compilation.
    entries_before = compile_cache_entries()
    results = {}
    with contextlib.redirect_stdout(sys.stderr):
        for name in CONFIGS:
            results[name] = _run_config(name, device)
    headline = results["whole-genome"]
    payload = dict(headline)
    payload["details"] = dict(headline["details"])
    payload["details"]["compile_cache"] = {
        "entries_before": entries_before,
        "entries_after": compile_cache_entries(),
        "cold_run": entries_before == 0,
    }
    payload["details"]["configs"] = {
        name: {
            "metric": r["metric"],
            "value": r["value"],
            "unit": r["unit"],
            "vs_baseline": r["vs_baseline"],
            # .get: a skipped config (e.g. sharded ring on one device)
            # reports only its skip reason.
            "sites_scanned": r["details"].get("sites_scanned"),
            "sites_per_sec_per_chip": r["details"].get("sites_per_sec_per_chip"),
            "compile_seconds_excluded": r["details"].get(
                "compile_seconds_excluded"
            ),
            "dispatch_padding_waste_fraction": r["details"].get(
                "dispatch_padding_waste_fraction"
            ),
            **(
                {"gramian_ring_bytes": r["details"]["gramian_ring_bytes"]}
                if "gramian_ring_bytes" in r["details"]
                else {}
            ),
            **(
                {
                    "sched_predicted_bytes": r["details"][
                        "sched_predicted_bytes"
                    ],
                    "sched_ring_bytes_delta_fraction": r["details"][
                        "sched_ring_bytes_delta_fraction"
                    ],
                }
                if "sched_predicted_bytes" in r["details"]
                else {}
            ),
            **(
                {"skipped": r["details"]["skipped"]}
                if "skipped" in r["details"]
                else {}
            ),
        }
        for name, r in results.items()
    }
    # The host-side file-ingest data plane rides along: parse scaling by
    # worker count + ingest/compute overlap (see _run_ingest_config).
    with contextlib.redirect_stdout(sys.stderr):
        ingest = _run_ingest_config(device)
    payload["details"]["configs"]["ingest"] = {
        "metric": ingest["metric"],
        "value": ingest["value"],
        "unit": ingest["unit"],
        "vs_baseline": ingest["vs_baseline"],
        "parse_by_workers": ingest["details"]["parse_by_workers"],
        "ingest_compute_overlap": ingest["details"]["ingest_compute_overlap"],
    }
    # The analyses layer rides along too: one manifest-verified artifact
    # per population-genetics workload (GRM/LD/assoc on the same substrate).
    for name in sorted(ANALYSIS_CONFIGS):
        with contextlib.redirect_stdout(sys.stderr):
            r = _run_analysis_config(name, device)
        payload["details"]["configs"][name] = {
            "metric": r["metric"],
            "value": r["value"],
            "unit": r["unit"],
            "vs_baseline": r["vs_baseline"],
            "analysis": r["details"]["analysis"],
            "sites_per_sec": r["details"]["sites_per_sec"],
        }
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
