"""Closed-loop batch cells: whole jobs back to back, one in flight, each
from the driver call to the fetched result.

A cell's job kind is the module ``jobs/<kind>.py`` that its configuration
names under ``"job"`` (``core.job_kind``; ``jobs/devicegen.py`` where it
names none). The module gives:

- ``Job(cell, devices, traced, data)``, with the cell's traffic as ``trf``
  and ``traced``: called with one ``--references`` string, it runs one job
  and returns its record: ``seconds``, ``S`` (the job's Gramian as the
  program left it on the devices), ``pcs``, ``ranges`` (the job's site-grid
  ranges) and, where the kind counts them, ``dispatches`` and
  ``sites_scanned``. ``pcoa`` runs a PCoA job under the harness's
  annotations.
- ``warm_up(job)``: compiles every program of the window's jobs.
- optionally ``prepare(cell, directory) -> data``: writes the kind's data
  set into ``directory``, once per process, from the configuration alone
  (never from ``--seed``); ``data`` goes to ``Job``. Its seconds are left
  out of set-up.
- optionally ``check(cell, kept) -> numbers``; ``check`` where it has none.
"""

from __future__ import annotations

import contextlib
import tempfile
import time

import numpy as np

from benchmark import core, reference, traffic


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


def pcoa(job, references: str, similarity) -> tuple:
    """One PCoA job as a user runs it: ``VariantsPcaDriver`` with
    ``job.flags`` over ``job.source`` on ``job.devices``, under the
    annotations that name the trace's idle gaps alike for every kind:
    ``job`` around ``driver-setup`` (conf, driver, contigs), ``ingest``
    (``similarity(driver, contigs)``, fenced when traced) and ``finalize``
    (the components fetched). Returns the record's ``seconds``, ``S``,
    ``pcs`` and ``ranges``, and the driver, whose counters the kind reads."""
    from spark_examples_tpu.config import PcaConf
    from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver

    traced = job.traced
    start = time.perf_counter()
    with _span("job", traced):
        with _span("driver-setup", traced):
            conf = PcaConf.parse(job.flags + ["--references", references])
            driver = VariantsPcaDriver(conf, job.source, devices=job.devices)
            contigs = conf.get_contigs(job.source, conf.variant_set_id)
        with _span("ingest", traced):
            S = similarity(driver, contigs)
            if traced:
                S.block_until_ready()
        with _span("finalize", traced):
            result = driver.compute_pca(S)
    record = {
        "seconds": time.perf_counter() - start,
        "S": S,
        "pcs": np.array([pcs for _, pcs in result], dtype=np.float64),
        "ranges": [
            reference.grid_range(c.start, c.end, int(job.trf["spacing"])) for c in contigs
        ],
    }
    return record, driver


@contextlib.contextmanager
def set_up(cell: dict, devices, traced: bool):
    """The cell's job kind set up: its data prepared into a temporary
    directory that is removed on leaving, also on a failure; its ``Job``
    built and warmed up. Yields the kind's module, the job and the seconds
    ``prepare`` took (0 where the kind has none), which set-up leaves out."""
    kind = core.load_job(cell["job"])
    with contextlib.ExitStack() as stack:
        data, prepare_s = None, 0.0
        if hasattr(kind, "prepare"):
            directory = stack.enter_context(tempfile.TemporaryDirectory(prefix="bench-data-"))
            start = time.perf_counter()
            data = kind.prepare(cell, directory)
            prepare_s = time.perf_counter() - start
            core.say(f"prepare: {prepare_s:.3f} s, data in {directory}")
        job = kind.Job(cell, devices, traced, data)
        kind.warm_up(job)
        yield kind, job, prepare_s


def window(job, seed: int, seconds: float) -> dict:
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


def compare(kind, cell: dict, kept: dict) -> dict:
    """The kept jobs compared by the kind's own ``check``, or by ``check``."""
    return getattr(kind, "check", check)(cell, kept)


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
