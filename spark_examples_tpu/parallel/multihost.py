"""Multi-controller execution harness: a real ``jax.distributed`` run.

The reference's central operational capability is one job spanning machines —
a Spark cluster deployed with bdutil and addressed through a master URL
(``/root/reference/README.md:64-104``; ``GenomicsConf.scala:50-57``
``newSparkContext``). The TPU-native analog is multi-controller JAX: N
processes, each owning a slice of the device fleet, joined through a
coordinator into ONE global mesh, with every collective riding the same XLA
programs as the single-process path.

This module is the *executable proof* of that capability, not more plumbing:

- :func:`child_check` runs inside a coordinator-connected process and
  exercises the real pipeline: the data-parallel device-ingest accumulator
  over the global mesh (``ops/devicegen.py``), the finalize ``psum``-style
  cross-slice reduce, and the multi-controller fetch helpers
  (``parallel/mesh.py:host_value``). It asserts the global Gramian is
  bit-identical to the single-process host oracle *in this process*.
- :func:`verify_multihost` orchestrates the whole thing from one machine:
  spawns ``num_processes`` children with ``--coordinator-address
  127.0.0.1:<port> --num-processes N --process-id i`` and
  ``local_devices`` virtual CPU devices each (the same trick the test suite
  uses for a virtual mesh, ``tests/conftest.py``), collects each child's
  verdict, then re-runs the full ``variants-pca`` CLI across a fresh set of
  coordinator-connected processes and asserts all processes print identical
  principal components.

Run it directly to produce the machine-readable artifact::

    python -m spark_examples_tpu.parallel.multihost --artifact MULTIHOST.json

The same flags work against real multi-host TPU fleets (one process per
host, no ``--local-devices``): the child path calls the public
``distributed_init`` seam the driver itself uses (``config.py:init_distributed``).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

_CHILD_TAG = "MULTIHOST_CHILD "

# The small-but-real workload every child runs: the BRCA1 region of the
# flagship config (``SearchVariantsExampleBRCA1.scala:27``) over a cohort
# small enough for a few-second CPU run.
_REGION = "17:41196311:41277499"
_NUM_SAMPLES = 24
_SEED = 7
_SPACING = 100
_MIN_AF = 0.01

# The fleet-rehearsal region set: four equal-width windows, so the
# host-sharded contig split (``sharding/contig.py:partition_contigs_by_host``)
# has real work to balance and every process of a 2–4 host fleet ingests a
# strict subset of the cohort's sites.
_FLEET_REGIONS = ",".join(
    f"{ref}:41196311:41277499" for ref in ("17", "18", "19", "20")
)


def aggregate_host_counts(values) -> List[int]:
    """Sum small per-process host-side integer counters (I/O stats, ingest
    accounting) across every process of a ``jax.distributed`` run.

    The telemetry analog of the finalize ``psum``: each process's dataset
    layer counts only what ITS host loop streamed, so a whole-fleet manifest
    (``obs/manifest.py``) needs one cross-process reduction for its global
    I/O block. Rides ``process_allgather`` (host-local → global array over
    the same collectives the Gramian reduce uses), so stats parity holds on
    any backend the pipeline itself runs on; with one process it is a plain
    int cast, device-free — single-host runs pay nothing.
    """
    import numpy as np

    arr = np.asarray(list(values), dtype=np.int64)
    if arr.ndim != 1:
        raise ValueError(f"expected a flat counter vector, got shape {arr.shape}")
    import jax

    if jax.process_count() == 1:
        return [int(v) for v in arr]
    from jax.experimental import multihost_utils

    gathered = np.asarray(multihost_utils.process_allgather(arr))
    return [int(v) for v in gathered.reshape(jax.process_count(), -1).sum(axis=0)]


def child_check(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
) -> Dict[str, object]:
    """Run the distributed Gramian check inside one coordinator-connected
    process; returns the verdict dict (also used as the child's JSON line).

    Initializes ``jax.distributed`` through the same seam the driver uses,
    builds the GLOBAL device mesh, streams the site grid through the
    data-parallel device-ingest accumulator (each data slice generating a
    disjoint grid span), reduces across slices, and compares against the
    packed-block host oracle computed independently in this process.
    """
    from spark_examples_tpu.parallel.mesh import distributed_init

    distributed_init(coordinator_address, num_processes, process_id)

    import jax
    import numpy as np

    from spark_examples_tpu.ops.devicegen import DeviceGenGramianAccumulator
    from spark_examples_tpu.parallel.mesh import default_mesh
    from spark_examples_tpu.sharding.contig import Contig
    from spark_examples_tpu.sources.synthetic import (
        SyntheticGenomicsSource,
        af_filter_micro,
    )

    source = SyntheticGenomicsSource(
        num_samples=_NUM_SAMPLES, seed=_SEED, variant_spacing=_SPACING
    )
    variant_set = "synthetic-variantset-1"
    mesh = default_mesh()
    accumulator = DeviceGenGramianAccumulator(
        num_samples=source.num_samples,
        vs_keys=[source.genotype_stream_key(variant_set)],
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        min_af_micro=af_filter_micro(_MIN_AF),
        block_size=64,
        blocks_per_dispatch=2,
        exact_int=True,
        mesh=mesh,
        n_pops=source.n_pops,
    )
    name, start, end = _REGION.split(":")
    contig = Contig(name, int(start), int(end))
    k0, k1 = source.site_grid_range(contig)
    accumulator.add_grid(k0, k1)
    from spark_examples_tpu.parallel.mesh import host_value

    # One finalize reduction, probed for spans then fetched from the same
    # array (``accumulator.finalize()`` would re-run the cross-slice sum);
    # x64 so host_value's replicating jit keeps the promoted int64 result.
    with jax.enable_x64(True):
        gramian_device = accumulator.finalize_device()
        spans_processes = not bool(gramian_device.is_fully_addressable)
        gramian = host_value(gramian_device).astype(np.float64)
    per_set_rows, kept_sites = accumulator.ingest_counters()

    oracle = np.zeros((_NUM_SAMPLES, _NUM_SAMPLES), dtype=np.int64)
    for block in source.genotype_blocks(
        variant_set, contig, block_size=64, min_allele_frequency=_MIN_AF
    ):
        X = np.asarray(block["has_variation"], dtype=np.int64)
        oracle += X.T @ X

    # Second composition: RING ingest over a samples-only mesh spanning all
    # processes — every slice generates ONLY its own sample-column block and
    # the ``ppermute`` ring exchange (``ops/gramian.py:_ring_tiles``) crosses
    # the process boundary on every hop, which the single-process suite and
    # dryrun can never exercise for real.
    from spark_examples_tpu.ops.devicegen import DeviceGenRingGramianAccumulator
    from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS, make_mesh

    ring_mesh = make_mesh({SAMPLES_AXIS: jax.device_count()})
    ring = DeviceGenRingGramianAccumulator(
        num_samples=source.num_samples,
        vs_key=source.genotype_stream_key(variant_set),
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        mesh=ring_mesh,
        min_af_micro=af_filter_micro(_MIN_AF),
        block_size=64,
        blocks_per_dispatch=2,
        exact_int=True,
        n_pops=source.n_pops,
    )
    ring.add_grid(k0, k1)
    # One finalize reduction, probed for spans and fetched from the same
    # array (``ring.finalize()`` would rebuild + re-run the sharded sum).
    # ``finalize_sharded`` promotes the int32 shard accumulators' cross-slice
    # sum to int64 internally; the x64 block here is for ``host_value``,
    # whose replicating jit would otherwise canonicalize the int64 result
    # back to int32 on entry (matching ``finalize``'s own fetch).
    with jax.enable_x64(True):
        ring_sharded = ring.finalize_sharded()
        ring_spans = not bool(ring_sharded.is_fully_addressable)
        ring_full = host_value(ring_sharded)
    ring_gramian = ring_full[: source.num_samples, : source.num_samples]

    # Third composition: the SAME process-spanning samples ring under the
    # HIERARCHICAL schedule — ``reduce_schedule="hier"`` factors the
    # samples axis host-major (host factor = ``jax.process_count()``) and
    # runs the two-level tile exchange (``ops/gramian.py:_hier_ring_tiles``
    # inside ``ops/devicegen.py:_ring_update``), so the inner ring's hops
    # stay inside each process slice and only the outer stage crosses the
    # process boundary. Must be byte-identical to the flat ring above.
    hier = DeviceGenRingGramianAccumulator(
        num_samples=source.num_samples,
        vs_key=source.genotype_stream_key(variant_set),
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        mesh=ring_mesh,
        min_af_micro=af_filter_micro(_MIN_AF),
        block_size=64,
        blocks_per_dispatch=2,
        exact_int=True,
        n_pops=source.n_pops,
        reduce_schedule="hier",
    )
    hier.add_grid(k0, k1)
    hier_block = hier.schedule_block()
    with jax.enable_x64(True):
        hier_sharded = hier.finalize_sharded()
        hier_spans = not bool(hier_sharded.is_fully_addressable)
        hier_full = host_value(hier_sharded)
    hier_gramian = hier_full[: source.num_samples, : source.num_samples]

    # Telemetry parity: the run manifest's cross-process I/O aggregation
    # (``obs/manifest.py`` → :func:`aggregate_host_counts`) must reduce over
    # the same process set as the Gramian collectives — each process
    # contributes (process_id + 1, kept_sites) and every process must read
    # identical, correct global totals.
    aggregated = aggregate_host_counts([process_id + 1, int(kept_sites)])
    counts_ok = aggregated == [
        num_processes * (num_processes + 1) // 2,
        int(kept_sites) * num_processes,
    ]

    return {
        "process_id": process_id,
        "num_processes": num_processes,
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
        "mesh_shape": dict(mesh.shape),
        "platform": jax.default_backend(),
        "result_spans_processes": spans_processes,
        "gramian_ok": bool(np.array_equal(gramian.astype(np.int64), oracle)),
        "gramian_sum": int(gramian.sum()),
        "ring_mesh_shape": dict(ring_mesh.shape),
        "ring_spans_processes": ring_spans,
        "ring_gramian_ok": bool(
            np.array_equal(ring_gramian.astype(np.int64), oracle)
        ),
        "hier_schedule_kind": hier_block.get("kind"),
        "hier_spans_processes": hier_spans,
        "hier_gramian_ok": bool(
            np.array_equal(hier_gramian.astype(np.int64), oracle)
        ),
        "counter_aggregation_ok": bool(counts_ok),
        "variant_rows": [int(v) for v in per_set_rows],
        "kept_sites": int(kept_sites),
    }


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(local_devices: int) -> Dict[str, str]:
    """Environment for a spawned child: ``local_devices`` virtual CPU
    devices, CPU platform, no persistent compile cache. Any inherited device
    count flag (e.g. the test suite's 8) is replaced, not appended — XLA
    honors the first occurrence it parses."""
    env = dict(os.environ)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={local_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_EXAMPLES_TPU_NO_CACHE"] = "1"
    # Children must import this package from the repo, whatever the parent's
    # layout; keep the existing path after it.
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    existing = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = repo_root + (os.pathsep + existing if existing else "")
    return env


def _run_children(
    commands: List[List[str]], env: Dict[str, str], timeout: float
) -> List[subprocess.CompletedProcess]:
    """Run coordinator-connected children concurrently and drain ALL their
    pipes in parallel: a sequential ``communicate()`` loop would deadlock if
    one child fills its pipe (verbose XLA/Gloo output, a large crash trace)
    while a sibling the parent is currently reading waits on it in a
    collective. A timed-out child yields a synthetic returncode -9 result
    instead of raising, so the caller's report survives."""
    from concurrent.futures import ThreadPoolExecutor

    procs = [
        subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True
        )
        for cmd in commands
    ]

    def drain(proc, cmd):
        try:
            out, err = proc.communicate(timeout=timeout)
            return subprocess.CompletedProcess(cmd, proc.returncode, out, err)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
            return subprocess.CompletedProcess(
                cmd, -9, out, (err or "") + f"\n[timed out after {timeout}s]"
            )

    try:
        with ThreadPoolExecutor(max_workers=len(procs)) as pool:
            return list(pool.map(drain, procs, commands))
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()


def verify_multihost(
    num_processes: int = 2,
    local_devices: int = 4,
    timeout: float = 600.0,
    run_cli: bool = True,
) -> Dict[str, object]:
    """Spawn a real N-process ``jax.distributed`` run on localhost and verify
    it end to end; returns the machine-readable report.

    Phase 1 — ``child_check`` in every process: (a) data-parallel device
    ingest over the global mesh with the cross-slice finalize reduce,
    (b) RING ingest over a samples-only mesh whose ``ppermute`` hops cross
    the process boundary, and (c) the same ring under the HIERARCHICAL
    two-level schedule (host factor = process count); all three Gramians
    == host oracle, asserted per process.

    Phase 2 (``run_cli``) — :func:`_fleet_rehearsal`: the unmodified
    ``variants-pca`` CLI over a multi-contig region, solo (oracle) then as
    a coordinator-connected fleet with HOST-SHARDED ingest — each process
    reads only its contig partition (per-process I/O ~1/H of solo,
    manifest-asserted), PC rows byte-identical to solo, per-host
    conformance bounds hold, and the per-process flight-recorder segments
    merge into one valid Chrome trace.
    """
    env = _child_env(local_devices)
    port = _free_port()
    check_cmds = [
        [
            sys.executable,
            "-m",
            "spark_examples_tpu.parallel.multihost",
            "--child",
            "--coordinator-address",
            f"127.0.0.1:{port}",
            "--num-processes",
            str(num_processes),
            "--process-id",
            str(pid),
        ]
        for pid in range(num_processes)
    ]
    check_runs = _run_children(check_cmds, env, timeout)
    children: List[Dict[str, object]] = []
    for run in check_runs:
        verdict: Optional[Dict[str, object]] = None
        for line in run.stdout.splitlines():
            if line.startswith(_CHILD_TAG):
                verdict = json.loads(line[len(_CHILD_TAG):])
        if verdict is None:
            verdict = {
                "gramian_ok": False,
                "error": (run.stderr or "")[-2000:],
                "returncode": run.returncode,
            }
        children.append(verdict)
    gramian_ok = all(c.get("gramian_ok") for c in children) and all(
        r.returncode == 0 for r in check_runs
    )
    ring_ok = all(c.get("ring_gramian_ok") for c in children)
    hier_ok = all(
        c.get("hier_gramian_ok") and c.get("hier_schedule_kind") == "hier"
        for c in children
    )
    counts_ok = all(c.get("counter_aggregation_ok") for c in children)
    spans = all(
        c.get("result_spans_processes")
        and c.get("ring_spans_processes")
        and c.get("hier_spans_processes")
        for c in children
    )

    report: Dict[str, object] = {
        "num_processes": num_processes,
        "local_devices_per_process": local_devices,
        "children": children,
        "gramian_ok": gramian_ok,
        "ring_gramian_ok": ring_ok,
        "hier_gramian_ok": hier_ok,
        "counter_aggregation_ok": counts_ok,
        "result_spans_processes": spans,
    }

    if run_cli:
        report.update(_fleet_rehearsal(num_processes, env, timeout))
        report["ok"] = bool(
            gramian_ok
            and ring_ok
            and hier_ok
            and counts_ok
            and spans
            and report["cli_ok"]
            and report["cli_outputs_identical"]
            and report["fleet_host_sharded"]
            and report["fleet_io_ok"]
            and report["fleet_conformance_ok"]
            and report["fleet_trace_ok"]
        )
    else:
        report["ok"] = bool(
            gramian_ok and ring_ok and hier_ok and counts_ok and spans
        )
    return report


def _pc_rows(text: str) -> List[str]:
    """Emitted PC rows: ``<callset name>\\t<dataset>\\t<pc>...`` with the
    synthetic source's SxxNxxxxx naming (``sources/synthetic.py``) — the
    result surface of a run, independent of per-process telemetry lines
    (I/O stats, host-shard notices, Gloo rank banners) that legitimately
    differ between fleet members."""
    import re

    return [
        line for line in text.splitlines() if re.match(r"^S\d{2}N\d{5}\t", line)
    ]


def _fleet_rehearsal(
    num_processes: int, env: Dict[str, str], timeout: float
) -> Dict[str, object]:
    """The REAL multi-process full-pipeline rehearsal: the unmodified
    ``variants-pca`` CLI over a multi-contig region, run once solo (the
    byte-identity oracle) and once as an N-process coordinator-connected
    fleet with host-sharded ingest engaged.

    Asserts, machine-readably:

    - every process exits 0 and emits PC rows byte-identical to the solo
      oracle (``cli_outputs_identical`` — the merged Gramian is exact);
    - every process ingested a strict subset — per-process
      ``reference_bases`` ≤ ~1/H of solo (plus the one-contig overshoot
      the split rule allows), summing exactly to the solo total;
    - every process's manifest carries the cross-process global I/O block
      and a conformance block with no violated bound (the per-host
      ``host_peak_bytes`` pair included);
    - the per-process flight-recorder segments merge into ONE valid
      Chrome trace spanning every host (``obs/trace.py``).
    """
    import tempfile

    run_dir = tempfile.mkdtemp(prefix="multihost-fleet-")
    fleet_flags = [
        "variants-pca",
        "--source",
        "synthetic",
        "--num-samples",
        str(_NUM_SAMPLES),
        "--references",
        _FLEET_REGIONS,
    ]
    report: Dict[str, object] = {"fleet_run_dir": run_dir}

    solo_manifest_path = os.path.join(run_dir, "solo.manifest.json")
    solo_cmd = [
        sys.executable,
        "-m",
        "spark_examples_tpu",
        *fleet_flags,
        "--metrics-json",
        solo_manifest_path,
    ]
    t0 = time.perf_counter()
    solo = _run_children([solo_cmd], env, timeout)[0]
    solo_seconds = time.perf_counter() - t0
    solo_rows = _pc_rows(solo.stdout)

    port = _free_port()
    manifest_paths = [
        os.path.join(run_dir, f"fleet.{pid}.manifest.json")
        for pid in range(num_processes)
    ]
    cli_cmds = [
        [
            sys.executable,
            "-m",
            "spark_examples_tpu",
            *fleet_flags,
            "--coordinator-address",
            f"127.0.0.1:{port}",
            "--num-processes",
            str(num_processes),
            "--process-id",
            str(pid),
            "--metrics-json",
            manifest_paths[pid],
            "--trace-dir",
            run_dir,
        ]
        for pid in range(num_processes)
    ]
    t0 = time.perf_counter()
    cli_runs = _run_children(cli_cmds, env, timeout)
    fleet_seconds = time.perf_counter() - t0
    # Wall clocks ride along for the bench artifact (subprocess spawn +
    # compile included — the honest operator view of a cold fleet run, not
    # an ingest-only microbenchmark; the ingest-scaling claim rests on the
    # per-process reference_bases fractions below).
    report["fleet_wall_seconds"] = {
        "solo": round(solo_seconds, 3),
        "fleet": round(fleet_seconds, 3),
    }
    cli_ok = solo.returncode == 0 and all(
        run.returncode == 0 for run in cli_runs
    )
    fleet_rows = [_pc_rows(run.stdout) for run in cli_runs]
    identical = bool(solo_rows) and all(
        rows == solo_rows for rows in fleet_rows
    )
    report["cli_ok"] = cli_ok
    report["cli_outputs_identical"] = identical
    report["cli_pc_lines"] = len(solo_rows)
    if not cli_ok:
        report["cli_errors"] = [
            (run.stderr or "")[-2000:]
            for run in [solo, *cli_runs]
            if run.returncode
        ]
    report["fleet_host_sharded"] = all(
        "Host-sharded ingest: process" in run.stdout for run in cli_runs
    )

    manifests: List[Optional[Dict]] = []
    for path in manifest_paths:
        try:
            with open(path) as f:
                manifests.append(json.load(f))
        except (OSError, ValueError):
            manifests.append(None)
    solo_bases = 0
    try:
        with open(solo_manifest_path) as f:
            solo_bases = int(json.load(f)["io_stats"]["reference_bases"])
    except (OSError, ValueError, KeyError, TypeError):
        pass
    local_bases = [
        int((m or {}).get("io_stats", {}).get("reference_bases", -1))
        for m in manifests
    ]
    fractions = [
        (b / solo_bases if solo_bases > 0 else -1.0) for b in local_bases
    ]
    report["fleet_io_reference_bases"] = {
        "solo": solo_bases,
        "per_process": local_bases,
    }
    # Each host's declared-site share overshoots its 1/H fair share by at
    # most the one contig that closes its partition (the split rule's tie
    # walk) — with the four equal rehearsal windows that is ≤ 1/4 + a
    # rounding hair. The partition property itself is exact: the local
    # reads sum to the solo total, and the global block every process
    # aggregated collectively must equal it too.
    global_ok = all(
        int(
            ((m or {}).get("multihost") or {})
            .get("io_stats_global", {})
            .get("reference_bases", -1)
        )
        == solo_bases
        for m in manifests
    )
    report["fleet_io_ok"] = bool(
        solo_bases > 0
        and sum(local_bases) == solo_bases
        and all(0 <= f <= 1.0 / num_processes + 0.26 for f in fractions)
        and global_ok
    )

    conformance_ok = True
    for m in manifests:
        block = (m or {}).get("conformance")
        if not isinstance(block, dict):
            conformance_ok = False
            continue
        hostmem = block.get("hostmem")
        if not isinstance(hostmem, dict) or hostmem.get("ok") is not True:
            # The per-host bound pair must exist AND hold in every process.
            conformance_ok = False
        if any(
            isinstance(pair, dict) and pair.get("ok") is False
            for pair in block.values()
        ):
            conformance_ok = False
    report["fleet_conformance_ok"] = bool(conformance_ok)

    trace_errors: List[str]
    try:
        from spark_examples_tpu.obs.trace import (
            merge_run_trace,
            validate_chrome_trace,
        )

        doc = merge_run_trace(run_dir)
        trace_errors = list(validate_chrome_trace(doc))
        replicas = {
            e.get("args", {}).get("name", "")
            for e in doc.get("traceEvents", [])
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        if len(replicas) != num_processes:
            trace_errors.append(
                f"merged trace spans {len(replicas)} replicas, "
                f"expected {num_processes}: {sorted(replicas)}"
            )
    except Exception as e:  # pragma: no cover - diagnostic path
        trace_errors = [f"{type(e).__name__}: {e}"]
    report["fleet_trace_ok"] = not trace_errors
    if trace_errors:
        report["fleet_trace_errors"] = trace_errors[:20]
    return report


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="2-process jax.distributed verification run"
    )
    parser.add_argument("--child", action="store_true")
    parser.add_argument("--coordinator-address", default=None)
    parser.add_argument("--num-processes", type=int, default=2)
    parser.add_argument("--process-id", type=int, default=0)
    parser.add_argument("--local-devices", type=int, default=4)
    parser.add_argument("--artifact", default=None)
    args = parser.parse_args(argv)

    if args.child:
        verdict = child_check(
            args.coordinator_address, args.num_processes, args.process_id
        )
        print(_CHILD_TAG + json.dumps(verdict), flush=True)
        return (
            0
            if verdict["gramian_ok"]
            and verdict["ring_gramian_ok"]
            and verdict["hier_gramian_ok"]
            and verdict["counter_aggregation_ok"]
            else 1
        )

    report = verify_multihost(
        num_processes=args.num_processes, local_devices=args.local_devices
    )
    print(json.dumps(report, indent=2))
    if args.artifact:
        with open(args.artifact, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
