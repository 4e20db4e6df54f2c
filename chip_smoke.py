"""Bring-up check: the PCoA pipeline and the served path on one TPU chip.

One process drives the main path through its user entry points at full
1000 Genomes width (2,504 samples) and checks what comes out:

(a) the backend is a TPU (no accelerator = exit 1, never a CPU fallback);
(b) the BRCA1 region through ``pipeline/pca_driver.run`` with device
    ingest, compared with ``--pca-backend host`` on the same seed up to
    eigenvector sign;
(c) the whole genome at the ``bench.py`` whole-genome config (~39.5M grid
    sites, int32 Gramian): every grid site scanned, the returned PCs are
    eigenvectors of the host-centered Gramian with finite, descending
    eigenvalues, and the 2,504 rows come back in callset order;
(d) the resident service in-process (``PcaService`` + ``serve/http.py``):
    three BRCA1 jobs submitted with ``serve/client.py`` return the rows of
    (b).

``--chips 4`` runs only the sharded path and what it is compared with:
25,000 samples on chr17 through the samples-sharded ring on meshes 1x4 and
2x2 against the dense result on one chip, with each device's peak memory.

Timings and compile seconds go to earlier lines; the last line of stdout
is one JSON object naming the device. Any failed phase exits non-zero and
prints no such line. ``--dry`` (tests only) runs every phase at a tiny
size on whatever backend JAX has, CPU included.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

BRCA1 = "17:41196311:41277499"
CHR17 = "17:0:81195210"
SEED = 42
#: atol for PCs compared across paths, after sign alignment. Components
#: of a 2,504-sample cohort are ~2e-2 in magnitude; f32 subspace iteration
#: against float64 eigh agrees far inside this.
PC_ATOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def say(*parts) -> None:
    print(*parts, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, read from its
    own monitoring events, so a phase's compile time is measured and not
    inferred from a cold/warm difference."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[-1]:
                self.compiles += 1


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, record: dict):
    """Time one phase; its driver prints go to stderr so stdout holds only
    this script's summary lines."""
    compile0, compiles0 = clock.seconds, clock.compiles
    start = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr):
        yield record
    record["wall_seconds"] = time.perf_counter() - start
    record["compile_seconds"] = clock.seconds - compile0
    record["backend_compiles"] = clock.compiles - compiles0
    say(f"phase {name}: " + json.dumps(record, sort_keys=True))


def parse_lines(lines):
    """Emitted PC lines (``name<TAB>dataset<TAB>pc...``) → names, (N, k)."""
    names = [line.split("\t")[0] for line in lines]
    pcs = np.array([[float(x) for x in line.split("\t")[2:]] for line in lines])
    return names, pcs


def pc_difference(a, b) -> float:
    """Max |a - b| after aligning each component's sign (eigenvectors are
    defined up to sign; the rule of ``tests/helpers.py:assert_pcs_match``)."""
    signs = np.sign((a * b).sum(axis=0))
    signs[signs == 0] = 1
    return float(np.abs(a - b * signs).max())


def compare_lines(a_lines, b_lines, what: str) -> float:
    a_names, a = parse_lines(a_lines)
    b_names, b = parse_lines(b_lines)
    check(a_names == b_names, f"{what}: row order differs")
    check(np.isfinite(a).all() and np.isfinite(b).all(), f"{what}: non-finite PCs")
    diff = pc_difference(a, b)
    check(diff <= PC_ATOL, f"{what}: max |dPC| {diff} > {PC_ATOL}")
    return diff


# ------------------------------------------------------------------ phases


def require_tpu(dry: bool, chips: int) -> dict:
    import jax

    devices = jax.devices()
    device = devices[0]
    info = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(devices),
    }
    say(f"phase a: {json.dumps(info)}")
    if not dry:
        check(device.platform == "tpu", f"no TPU: JAX backend is {device.platform!r}")
    check(
        len(devices) >= chips,
        f"--chips {chips} needs {chips} devices, JAX has {len(devices)}",
    )
    return info


def brca1_flags(num_samples: int):
    return [
        "--references", BRCA1,
        "--num-samples", str(num_samples),
        "--seed", str(SEED),
    ]


def phase_brca1(clock, num_samples: int) -> list:
    from spark_examples_tpu.pipeline.pca_driver import run

    record = {}
    with phase("b brca1 device-vs-host", clock, record):
        start = time.perf_counter()
        device_lines = run(brca1_flags(num_samples) + ["--ingest", "device"])
        record["device_seconds"] = time.perf_counter() - start
        start = time.perf_counter()
        host_lines = run(brca1_flags(num_samples) + ["--pca-backend", "host"])
        record["host_seconds"] = time.perf_counter() - start
        check(len(device_lines) == num_samples, "brca1: wrong row count")
        record["max_abs_pc_diff"] = compare_lines(
            device_lines, host_lines, "brca1 device vs host"
        )
        record["rows"] = len(device_lines)
    return device_lines


def phase_whole_genome(clock, num_samples: int, dry: bool) -> None:
    import jax

    import bench
    from spark_examples_tpu.obs.metrics import INGEST_SITES_SCANNED
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    config = bench.CONFIGS["whole-genome"]
    (variant_set,) = config["sets"]
    block = 1024 if dry else bench.BLOCK
    span = ["--references", "21:0:3000000,22:0:3000000"] if dry else config["args"]
    args = [
        "--variant-set-id", variant_set,
        "--ingest", "device",
        "--block-size", str(block),
        "--num-pc", "2",
        "--num-samples", str(num_samples),
    ] + span
    source = SyntheticGenomicsSource(
        num_samples=num_samples, seed=SEED, variant_spacing=bench.VARIANT_SPACING
    )
    record = {"num_samples": num_samples, "block_size": block}
    with phase("c whole-genome", clock, record):
        conf, driver = bench._make_driver(args, source)
        contigs = conf.get_contigs(source, conf.variant_set_id)
        start = time.perf_counter()
        S = driver.get_similarity_device_gen(contigs)
        result = driver.compute_pca(S)
        record["pipeline_seconds"] = time.perf_counter() - start

        grid = sum(source.declared_sites(c) for c in contigs)
        scanned = int(driver.registry.value(INGEST_SITES_SCANNED))
        record.update(grid_sites=grid, sites_scanned=scanned, contigs=len(contigs))
        check(scanned == grid, f"whole genome scanned {scanned} of {grid} grid sites")

        expected = [cs["id"] for cs in source.search_callsets(conf.variant_set_id)]
        check([cs for cs, _ in result] == expected, "whole genome: rows out of order")
        V = np.array([pcs for _, pcs in result])
        check(V.shape == (num_samples, 2) and np.isfinite(V).all(), "whole genome: bad PCs")

        # Independent check on the host: the returned vectors are
        # eigenvectors of the float64-centered Gramian, eigenvalues descending.
        with jax.enable_x64(True):
            G = np.asarray(jax.device_get(S), dtype=np.float64)
        record["gramian_dtype"] = str(S.dtype)
        row = G.mean(axis=1)
        C = G - row[:, None] - row[None, :] + row.mean()
        CV = C @ V
        eig = (V * CV).sum(axis=0) / (V * V).sum(axis=0)
        residual = np.linalg.norm(CV - V * eig, axis=0) / np.abs(eig)
        record["eigenvalues"] = eig.tolist()
        record["eig_residual"] = residual.tolist()
        check(np.isfinite(eig).all(), "whole genome: non-finite eigenvalues")
        check(eig[0] >= eig[1] > 0, f"whole genome: eigenvalues not descending {eig}")
        check((residual < 1e-2).all(), f"whole genome: eigen-residual {residual}")


def phase_served(clock, num_samples: int, cli_lines: list) -> None:
    from spark_examples_tpu.serve.client import ServeClient
    from spark_examples_tpu.serve.daemon import PcaService
    from spark_examples_tpu.serve.http import start_server

    record = {"jobs": 3}
    flags = brca1_flags(num_samples) + ["--ingest", "device"]
    with phase("d served", clock, record), tempfile.TemporaryDirectory(
        prefix="chip-smoke-serve-"
    ) as run_dir:
        service = PcaService(run_dir=run_dir, persistent_cache=True).start()
        server = start_server(service)
        try:
            client = ServeClient(server.url)
            start = time.perf_counter()
            ids = [client.submit(flags)["job"]["id"] for _ in range(3)]
            jobs = [client.wait(i, timeout=600)["job"] for i in ids]
            record["seconds"] = time.perf_counter() - start
            record["job_seconds"] = [job.get("seconds") for job in jobs]
            record["compile_cache"] = [job.get("compile_cache") for job in jobs]
            diffs = []
            for job in jobs:
                check(job["status"] == "done", f"served job {job['id']}: {job['status']} {job.get('error')}")
                lines = job["result"]["pc_lines"]
                diffs.append(compare_lines(lines, cli_lines, f"served {job['id']} vs CLI"))
            record["max_abs_pc_diff"] = max(diffs)
            record["bitwise_equal"] = all(
                job["result"]["pc_lines"] == cli_lines for job in jobs
            )
        finally:
            server.shutdown()
            check(service.stop(timeout=120), "service did not drain")


def peak_bytes(devices) -> list:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return peaks


def phase_sharded(clock, num_samples: int, dry: bool) -> None:
    import jax

    from spark_examples_tpu.config import PcaConf
    from spark_examples_tpu.pipeline.pca_driver import run_pipeline

    devices = jax.devices()[:4]
    region = "17:0:2000000" if dry else CHR17
    base = [
        "--references", region,
        "--num-samples", str(num_samples),
        "--seed", str(SEED),
        "--ingest", "device",
    ]
    results = {}
    for mesh in ("1,4", "2,2"):
        record = {"mesh": mesh, "num_samples": num_samples}
        with phase(f"sharded {mesh}", clock, record):
            conf = PcaConf.parse(
                base + ["--similarity-strategy", "sharded", "--mesh-shape", mesh]
            )
            start = time.perf_counter()
            results[mesh] = run_pipeline(conf, devices=devices).lines
            record["pipeline_seconds"] = time.perf_counter() - start
            record["peak_bytes_per_device"] = peak_bytes(devices)
            samples_axis = int(mesh.split(",")[1])
            tile = num_samples * num_samples * 4 // samples_axis
            record["gramian_tile_bytes"] = tile
            if devices[0].platform == "tpu":
                check(
                    min(record["peak_bytes_per_device"]) >= tile,
                    f"mesh {mesh}: a device peaked below one Gramian tile",
                )
    record = {"mesh": "dense 1 chip", "num_samples": num_samples}
    with phase("dense reference", clock, record):
        conf = PcaConf.parse(base + ["--similarity-strategy", "dense"])
        start = time.perf_counter()
        dense = run_pipeline(conf, devices=devices[:1]).lines
        record["pipeline_seconds"] = time.perf_counter() - start
        for mesh, lines in results.items():
            record[f"max_abs_pc_diff_{mesh}"] = compare_lines(
                lines, dense, f"sharded {mesh} vs dense"
            )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded 1x4/2x2 path and its dense reference",
    )
    parser.add_argument(
        "--dry", action="store_true",
        help="tests only: tiny sizes on any backend, CPU included",
    )
    args = parser.parse_args(argv)

    import spark_examples_tpu

    package = os.path.dirname(os.path.abspath(spark_examples_tpu.__file__))
    check(
        package == os.path.join(HERE, "spark_examples_tpu"),
        f"spark_examples_tpu imported from {package}, not this checkout",
    )
    from spark_examples_tpu.utils.cache import (
        compile_cache_dir,
        compile_cache_entries,
        enable_persistent_compile_cache,
    )

    enable_persistent_compile_cache()
    say(f"compile cache: {compile_cache_dir()} ({compile_cache_entries()} entries)")
    clock = CompileClock()
    device = require_tpu(args.dry, args.chips)
    start = time.perf_counter()
    if args.chips == 4:
        phase_sharded(clock, 64 if args.dry else 25_000, args.dry)
    else:
        samples = 24 if args.dry else 2504
        cli_lines = phase_brca1(clock, samples)
        phase_whole_genome(clock, samples, args.dry)
        phase_served(clock, samples, cli_lines)
    say(
        f"total: {time.perf_counter() - start:.3f} s, compile "
        f"{clock.seconds:.3f} s over {clock.compiles} backend compiles, "
        f"cache {compile_cache_entries()} entries"
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        code = 1
    sys.exit(code)
