"""Open-loop served cells: requests go through ``PcaService`` +
``serve/http.py`` + ``ServeClient`` on a fixed schedule, whatever the
service does. One thread submits each request when it is due; one thread
polls the outstanding ones and stamps each result when it is fetched.
Latency runs from the due time to the fetched result."""

from __future__ import annotations

import shutil
import tempfile
import threading
import time

import numpy as np

from benchmark import core, reference, traffic

#: Seconds past the window's close that an accepted request may still
#: finish in; one that has not by then never came.
GRACE_SECONDS = 60.0


class Service:
    """The resident service, its HTTP front end and a client, in this
    process (one process holds the chip)."""

    def __init__(self, cell: dict):
        from spark_examples_tpu.serve.client import ServeClient
        from spark_examples_tpu.serve.daemon import PcaService
        from spark_examples_tpu.serve.http import start_server

        cfg, trf = cell["config"], cell["traffic"]
        self.cfg, self.trf = cfg, trf
        self.run_dir = tempfile.mkdtemp(prefix="bench-serve-")
        self.service = PcaService(run_dir=self.run_dir, persistent_cache=True).start()
        self.server = start_server(self.service)
        # No client-side retry: a refused request is counted, not resent.
        self.client = ServeClient(self.server.url, max_retries=0)

    def flags(self, references: str) -> list:
        cfg = self.cfg
        return [
            "--variant-set-id", cfg["variant_set_id"],
            "--num-samples", str(cfg["num_samples"]),
            "--seed", str(cfg["cohort_seed"]),
            "--num-pc", str(cfg["num_pc"]),
            "--block-size", str(self.trf["block_size"]),
            "--references", references,
        ]

    def warm_up(self, seed: int) -> None:
        """One group of each size the queue can form (1 … batch max), so no
        stacked program compiles in the window: a linger holds the queue
        open while a group's jobs arrive, then goes back to the default."""
        schedule = traffic.open_schedule(self.trf, seed ^ 0x5EED, 64)
        default = self.service.batch_linger_seconds
        self.service.batch_linger_seconds = float(self.trf["warm_linger_s"])
        try:
            for size in range(1, self.service.batch_max_jobs + 1):
                ids = [
                    self.client.submit(self.flags(refs))["job"]["id"]
                    for _, refs in schedule[:size]
                ]
                for job_id in ids:
                    job = self.client.wait(job_id, timeout=900, poll_cap_seconds=0.05)["job"]
                    if job["status"] != "done":
                        raise core.BenchFailure(f"warm-up job {job_id}: {job.get('error')}")
                core.say(f"warm-up group {size}: fused_size {job.get('fused_size')}")
        finally:
            self.service.batch_linger_seconds = default
        # The worker's pop that began under the linger returns within the
        # daemon's 0.2 s pop timeout; the window must not meet it.
        time.sleep(0.5)

    def close(self) -> None:
        self.server.shutdown()
        drained = self.service.stop(timeout=120)
        shutil.rmtree(self.run_dir, ignore_errors=True)
        if not drained:
            raise core.BenchFailure("service did not drain")


def window(svc: Service, seed: int, seconds: float, poll_s: float) -> dict:
    """Send the schedule, fetch every result, and time each request."""
    from spark_examples_tpu.serve.client import ServeError
    from spark_examples_tpu.serve.protocol import TERMINAL_STATUSES

    schedule = traffic.open_schedule(svc.trf, seed, seconds)
    requests = [{"due": due, "references": refs, "status": None} for due, refs in schedule]
    pending, lock = {}, threading.Lock()
    sent_all = threading.Event()
    t0 = time.perf_counter() + 0.05
    deadline = t0 + seconds + GRACE_SECONDS

    def submit():
        for req in requests:
            delay = t0 + req["due"] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            req["sent"] = time.perf_counter()
            try:
                job_id = svc.client.submit(svc.flags(req["references"]))["job"]["id"]
            except (ServeError, OSError) as e:
                req["status"] = "refused"
                req["error"] = str(e)
                continue
            with lock:
                pending[job_id] = req
        sent_all.set()

    def poll():
        while time.perf_counter() < deadline:
            with lock:
                outstanding = list(pending.items())
            if not outstanding and sent_all.is_set():
                return
            for job_id, req in outstanding:
                try:
                    job = svc.client.status(job_id)["job"]
                except (ServeError, OSError):
                    continue  # asked again on the next sweep
                if job["status"] in TERMINAL_STATUSES:
                    req["done"] = time.perf_counter()
                    req["status"] = job["status"]
                    req["job"] = job
                    with lock:
                        del pending[job_id]
            time.sleep(poll_s)

    threads = [threading.Thread(target=f, name=f"bench-{f.__name__}") for f in (submit, poll)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for req in requests:
        if req["status"] is None:
            req["status"] = "lost"
    return {"t0": t0, "requests": requests, "deadline": deadline}


def latencies(result: dict) -> list:
    """Due time to fetched result; a request with no result counts as
    missing, at the longest wait the run allowed."""
    t0 = result["t0"]
    return [
        (r["done"] - (t0 + r["due"])) if r["status"] == "done" else result["deadline"] - (t0 + r["due"])
        for r in result["requests"]
    ]


def parse_pc_lines(lines: list) -> np.ndarray:
    return np.array([[float(x) for x in line.split("\t")[2:]] for line in lines])


def check(cell: dict, result: dict, seed: int) -> dict:
    """Compare a seed-drawn sample of the finished requests with the plain
    reference; a request that failed or never finished is wrong."""
    import jax

    cfg, trf = cell["config"], cell["traffic"]
    spacing = int(trf["spacing"])
    done = [r for r in result["requests"] if r["status"] == "done"]
    wrong = sum(1 for r in result["requests"] if r["status"] in ("failed", "lost", "cancelled"))
    count = min(len(done), int(trf["sampled_jobs"]))
    picks = np.random.default_rng([seed & ((1 << 64) - 1), 11]).choice(
        len(done), size=count, replace=False
    ) if count else []
    layout = reference.row_layout(int(cfg["num_samples"]), jax.devices()[:1])
    gap = 0.0
    for i in picks:
        req = done[int(i)]
        contig, start, end = req["references"].split(":")
        ranges = [reference.grid_range(int(start), int(end), spacing)]
        tiles = reference.gramian_tiles(cfg, ranges, spacing, layout)
        vals, vecs = reference.reference_eigen(cfg, tiles)
        V = parse_pc_lines(req["job"]["result"]["pc_lines"])
        gap = max(gap, reference.eigenspace_gap(V, vals, vecs))
    return {
        "pc_eigenspace_gap": gap if count else None,
        "unanswered_requests": float(wrong),
    }
