r"""Serving under mixed traffic, measured on the CPU.

An in-process resident service (``serve/``) with executor slices, driven
through the real HTTP API by concurrent submitters. It reports P50/P99 per
admission class in two phases: small jobs alone (unloaded), then small jobs
while a large job holds the large slice (loaded). So the number that
matters to users ("does a cheap query stall behind a whole-genome run?") is
measured, not argued. Two more phases ride their own single-lane services:
one fused group of identical small jobs against the same jobs back to back
(byte parity asserted), and cost-ordered against FIFO scheduling on one
mixed queue.

Prints one JSON document on stdout; the service's own prints go to stderr.
``ci.sh`` (stage 5b) runs it on four virtual CPU devices and checks its
``details`` against the serving bounds::

    env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
        XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python serve_load.py

Chip speed is measured by ``benchmark/run.py``, not here.
"""

import contextlib
import json
import shutil
import sys
import time

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
    so the harness reports the numbers operators will actually see."""
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
    """The histogram increments one phase contributed: cumulative
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
            raise RuntimeError(f"serve-load submit rejected {status}: {doc}")
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
                raise RuntimeError(f"serve-load timed out waiting on {jid}")
            time.sleep(0.02)
        if job["status"] != "done":
            raise RuntimeError(f"serve-load job failed: {job}")
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


def main() -> None:
    import jax

    from spark_examples_tpu.utils.cache import enable_persistent_compile_cache

    # The one compile cache every entry point shares (utils/cache.py).
    enable_persistent_compile_cache()
    device = jax.devices()[0]
    with contextlib.redirect_stdout(sys.stderr):
        payload = _run_serve_load_config(device)
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
