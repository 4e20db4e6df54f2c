"""Closed-loop batch cells: whole PCoA jobs back to back through
``VariantsPcaDriver``, one in flight, each from the driver call to the
fetched (N, num_pc) components."""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark import core, reference, traffic


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


class Job:
    """One whole-genome job as a user runs it."""

    def __init__(self, cell: dict, devices, traced: bool):
        from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

        cfg, trf = cell["config"], cell["traffic"]
        self.cfg, self.trf, self.devices, self.traced = cfg, trf, devices, traced
        self.source = SyntheticGenomicsSource(
            num_samples=int(cfg["num_samples"]),
            seed=int(cfg["cohort_seed"]),
            variant_spacing=int(trf["spacing"]),
            ref_block_fraction=float(cfg["ref_block_fraction"]),
            n_pops=int(cfg["n_pops"]),
        )
        self.flags = [
            "--variant-set-id", cfg["variant_set_id"],
            "--ingest", "device",
            "--block-size", str(trf["block_size"]),
            "--num-pc", str(cfg["num_pc"]),
            "--num-samples", str(cfg["num_samples"]),
            "--seed", str(cfg["cohort_seed"]),
        ] + [str(flag) for flag in cfg.get("flags", [])]

    def __call__(self, references: str) -> dict:
        from spark_examples_tpu.config import PcaConf
        from spark_examples_tpu.obs.metrics import (
            DEVICEGEN_DISPATCHES,
            INGEST_SITES_SCANNED,
        )
        from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver

        traced = self.traced
        start = time.perf_counter()
        with _span("job", traced):
            with _span("driver-setup", traced):
                conf = PcaConf.parse(self.flags + ["--references", references])
                driver = VariantsPcaDriver(conf, self.source, devices=self.devices)
                contigs = conf.get_contigs(self.source, conf.variant_set_id)
            with _span("ingest", traced):
                S = driver.get_similarity_device_gen(contigs)
                if traced:
                    S.block_until_ready()
            with _span("finalize", traced):
                result = driver.compute_pca(S)
        return {
            "seconds": time.perf_counter() - start,
            "S": S,
            "pcs": np.array([pcs for _, pcs in result], dtype=np.float64),
            "ranges": [
                reference.grid_range(c.start, c.end, int(self.trf["spacing"]))
                for c in contigs
            ],
            "dispatches": int(driver.registry.value(DEVICEGEN_DISPATCHES)),
            "sites_scanned": int(driver.registry.value(INGEST_SITES_SCANNED)),
        }


def warm_up(job: Job) -> None:
    from spark_examples_tpu.ops.devicegen import auto_blocks_per_dispatch

    k = auto_blocks_per_dispatch(int(job.cfg["num_samples"]), int(job.trf["block_size"]))
    job(traffic.warmup_references(job.trf, k))


def window(job: Job, seed: int, seconds: float) -> dict:
    """Jobs back to back until ``seconds`` have passed; every started job
    completes, so the measured time spans whole jobs only. The jobs the
    check compares are drawn from the seed and kept on the device."""
    trf = job.trf
    keep = set(
        np.random.default_rng([seed & ((1 << 64) - 1), 7])
        .choice(int(trf["sample_among"]), size=int(trf["sampled_jobs"]), replace=False)
        .tolist()
    )
    jobs, kept = [], {}
    start = time.perf_counter()
    with _span("window", job.traced):
        while not jobs or time.perf_counter() - start < seconds:
            record = job(traffic.closed_job(trf, seed, len(jobs)))
            if len(jobs) in keep:
                kept[len(jobs)] = record
            last = record
            jobs.append({k: v for k, v in record.items() if k not in ("S", "pcs")})
    elapsed = time.perf_counter() - start
    if not kept:
        kept[len(jobs) - 1] = last
    return {"elapsed": elapsed, "jobs": jobs, "kept": kept}


def check(cell: dict, kept: dict) -> dict:
    """Compare the kept jobs with the plain reference: the Gramian exactly,
    tile by tile on the devices that hold it (one scalar fetched per
    shard, no N² array on the host), then the components against the
    reference's float64 eigenpairs. Prints the seconds of each part and the
    process's peak host memory."""
    cfg, spacing = cell["config"], int(cell["traffic"]["spacing"])
    n = int(cfg["num_samples"])
    resident = core.resident_bytes()
    seconds = {"tiles": 0.0, "comparison": 0.0, "eigen": 0.0}
    refs, jobs = {}, []
    gap_g = gap_pc = 0.0
    for index in sorted(kept):
        record = kept.pop(index)
        key = tuple(sorted(record["ranges"]))
        start = time.perf_counter()
        if key not in refs:
            layout = reference.shard_layout(record["S"], n)
            refs[key] = reference.gramian_tiles(cfg, record["ranges"], spacing, layout)
            for tile in refs[key]:
                tile.data.block_until_ready()
            seconds["tiles"] += time.perf_counter() - start
            start = time.perf_counter()
        gap_g = max(gap_g, reference.max_abs_diff(record["S"], refs[key], n))
        seconds["comparison"] += time.perf_counter() - start
        jobs.append((key, record["pcs"]))
        del record
    start = time.perf_counter()
    for key in refs:
        vals, vecs = reference.reference_eigen(cfg, refs[key])
        core.say(f"reference eigenvalues {vals.tolist()}")
        for job_key, V in jobs:
            if job_key == key:
                gap_pc = max(gap_pc, reference.eigenspace_gap(V, vals, vecs))
    seconds["eigen"] = time.perf_counter() - start
    refs.clear()
    core.say(
        "check: " + ", ".join(f"{name} {s:.3f} s" for name, s in seconds.items())
        + f"; host memory {resident / 1e9:.3f} GB resident at its start, "
        f"process peak {core.peak_resident_bytes() / 1e9:.3f} GB"
    )
    return {"gramian_max_abs_diff": gap_g, "pc_eigenspace_gap": gap_pc}
