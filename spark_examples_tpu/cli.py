"""Command-line entry points.

One subcommand per reference main class (SURVEY.md §2.1's L5 applications),
with the reference flag grammar (``GenomicsConf.scala:29-98``):

    python -m spark_examples_tpu variants-pca --references 17:41196311:41277499
    python -m spark_examples_tpu search-variants-klotho
    python -m spark_examples_tpu search-variants-brca1
    python -m spark_examples_tpu search-reads-example-1 .. -4

File-backed runs (``--source file``) parse VCF inputs through the
chunk-parallel native ingest engine; ``--ingest-workers N`` sizes its thread
pool (default min(8, cpu_count); ``0`` = the serial oracle path, identical
output):

    python -m spark_examples_tpu variants-pca --source file \\
        --input-files cohort.vcf.gz --ingest-workers 8

Population-genetics analyses (``analyses/``; README "Analyses"): three
per-site workloads on the same substrate — ``grm`` (allele-frequency-
standardized VanRaden kinship, a reweighting of the PCA Gramian), and two
M-sized-output analyses whose statistics spill window by window:

    python -m spark_examples_tpu grm --num-samples 64 \\
        --references 1:0:400000 --grm-out kinship.tsv
    python -m spark_examples_tpu ld-prune --ld-r2-threshold 0.2 \\
        --ld-window-sites 256 --ld-out kept.tsv
    python -m spark_examples_tpu assoc-scan --phenotypes pheno.tsv \\
        --assoc-out scan.tsv

Static analysis (``check/``; README "graftcheck"): ``graftcheck lint``
(AST JAX-pitfall linter), ``graftcheck ir`` (jaxpr-level audit of the real
Gramian kernels: ring overlap, donation contract, packed-wire dtype flow,
traffic/liveness facts), ``graftcheck ranges`` (abstract-interpretation
overflow & exactness prover over the same traced kernels: bf16/f32
partials < 2^24, int32 accumulation < 2^31, lossy casts, declared input
contracts from ``ops/contracts.py``, conversion-trigger conservativeness),
``graftcheck sched`` (device-free collective-schedule prover: the
communication schedule extracted from the traced kernel jaxprs, simulated
per link class over a declared ``--topology hosts,devices_per_host`` —
flat-ring vs hierarchical two-level ring traffic, overlap, liveness,
critical-path budgets, for a pod that need not exist),
``graftcheck lockgraph`` (static lock-acquisition-order graph of the
threaded ingest layer, DOT artifact), ``graftcheck hostmem`` (host-memory
bound audit of the staging layers: a closed totality proof — every byte
streams through ``sources/stream.py`` and the retired
``hostmem(unbounded)`` hatch syntax is itself a finding), ``graftcheck
plan`` (device-free
flag/geometry/kernel-shape validation; ``--host-mem-budget`` enforces the
static host-RAM bound, exactness-window facts/rejections come from the
ranges prover, and ``--topology``/``--sched-budget-seconds`` add the
schedule proof), ``graftcheck sanitize`` / ``graftcheck typecheck``:

    python -m spark_examples_tpu graftcheck ir --json
    python -m spark_examples_tpu graftcheck ranges --json
    python -m spark_examples_tpu graftcheck sched --topology 32,8
    python -m spark_examples_tpu graftcheck hostmem --json
    python -m spark_examples_tpu graftcheck lockgraph --dot lockorder.dot

Serving (``serve/``; README "Serving"): ``serve`` starts the resident
daemon — executor slices (small jobs concurrent beside a large one),
continuous batching, compile-once with restart-persistent warm state,
journaled job table, admission-controlled — and ``submit`` sends it
jobs expressed as the same PCA flag namespace (everything after ``--``
is forwarded verbatim; ``--wait`` polls with server-paced Retry-After +
full-jitter backoff); plan-invalid requests come back as structured 4xx
bodies carrying the ``graftcheck plan`` facts:

    python -m spark_examples_tpu serve --port 8765 --run-dir /tmp/serve
    python -m spark_examples_tpu submit --url http://127.0.0.1:8765 \\
        -- --num-samples 64 --references 17:41196311:41277499

Observability (``obs/``; README "Observability"): ``--heartbeat-seconds N``
emits a stderr progress line every N seconds (sites/sec, partition ETA,
prefetch queue, dispatch depth, device memory); ``--metrics-json PATH``
writes the schema-versioned run manifest (config echo, stage spans, all
metrics, I/O stats, overlap accounting, prover-conformance pairs) that
CI's manifest checks consume; ``--profile-dir`` adds the ``jax.profiler``
device trace:

    python -m spark_examples_tpu variants-pca --all-references \\
        --heartbeat-seconds 30 --metrics-json run.json

Distributed tracing (``obs/trace.py``/``obs/recorder.py``; README
"Tracing"): every served job carries a trace id from client submit
through journal records and replica steals, every replica daemon keeps a
crash-durable flight recorder under ``<run-dir>/trace/``, and ``trace
export`` merges journal + recorder segments into one Chrome-trace JSON
(replicas as processes, executor slices as threads, steals as flow
arrows — load it in chrome://tracing or https://ui.perfetto.dev):

    python -m spark_examples_tpu trace export --run-dir /tmp/serve \\
        --out fleet.trace.json

Cost observatory (``obs/report.py``; README "Fleet stats & cost
calibration"): every admitted job carries a predicted cost, every
finished job appends a measured one to the crash-durable calibration
ledger, and ``obs report`` folds journal + ledger + recorder segments
into a post-mortem fleet report (per-job predicted vs measured under
one trace id, per-class latency quantiles, calibration ratios) —
purely from run-dir artifacts, so it works on a dead fleet:

    python -m spark_examples_tpu obs report --run-dir /tmp/serve --json
"""

from __future__ import annotations

import sys
from typing import Optional, Sequence

from spark_examples_tpu.analyses import reads_examples, variants_examples
from spark_examples_tpu.config import GenomicsConf
from spark_examples_tpu.pipeline import pca_driver


def _source(conf: GenomicsConf):
    return pca_driver.make_source(conf)  # type: ignore[arg-type]


def _readset_kwargs(conf: GenomicsConf, names: Sequence[str]) -> dict:
    """For ``--source file``, route the file-derived set ids into the reads
    examples' readset parameters (``names``, in ``--input-files`` order) —
    the hardcoded Google public readset ids only exist on the sunset API."""
    if conf.source != "file":
        return {}
    from spark_examples_tpu.sources.files import file_set_ids

    ids = file_set_ids(conf.input_files or [])
    if len(ids) < len(names):
        raise ValueError(
            f"this analysis needs {len(names)} --input-files "
            f"({', '.join(names)} in order); got {len(ids)}"
        )
    return dict(zip(names, ids))


def _variants_cmd(run_fn):
    def invoke(argv):
        conf = GenomicsConf.parse(argv)
        return run_fn(conf, _source(conf))

    return invoke


def _reads_cmd(run_fn, readset_params: Sequence[str]):
    def invoke(argv):
        conf = GenomicsConf.parse(argv)
        return run_fn(conf, _source(conf), **_readset_kwargs(conf, readset_params))

    return invoke


def _graftcheck(argv):
    # Static analysis must not pay (or trigger) backend/platform/cache
    # configuration — dispatched before the real-command setup in main().
    from spark_examples_tpu.check.cli import main as graftcheck_main

    return graftcheck_main(argv)


def _serve(argv):
    # The resident daemon (serve/http.py): platform/cache setup happens in
    # main() like any real command, then the service owns the process.
    from spark_examples_tpu.serve.http import serve_main

    return serve_main(argv)


def _grm(argv):
    # Population-genetics analyses (analyses/; README "Analyses"):
    # imported lazily so `--help` and graftcheck stay import-light.
    from spark_examples_tpu.analyses import grm

    return grm.run(argv)


def _ld_prune(argv):
    from spark_examples_tpu.analyses import ld

    return ld.run(argv)


def _assoc_scan(argv):
    from spark_examples_tpu.analyses import assoc

    return assoc.run(argv)


def _submit(argv):
    # Pure HTTP client: submitting to a remote daemon must not initialize
    # a local jax backend — dispatched before the real-command setup.
    from spark_examples_tpu.serve.client import submit_main

    return submit_main(argv)


def _trace(argv):
    # Post-mortem tooling (obs/trace.py): merges a serve fleet's journal
    # + flight-recorder segments into one Chrome-trace JSON. Pure file
    # I/O — dispatched before the platform/cache setup like graftcheck.
    from spark_examples_tpu.obs.trace import export_main

    return export_main(argv)


def _obs(argv):
    # Post-mortem cost observatory (obs/report.py): folds a fleet's
    # journal + calibration ledger + recorder segments into one report.
    # Pure file I/O — dispatched before the platform/cache setup.
    from spark_examples_tpu.obs.report import report_main

    return report_main(argv)


COMMANDS = {
    "variants-pca": lambda argv: pca_driver.run(argv),
    "grm": _grm,
    "ld-prune": _ld_prune,
    "assoc-scan": _assoc_scan,
    "graftcheck": _graftcheck,
    "serve": _serve,
    "submit": _submit,
    "trace": _trace,
    "obs": _obs,
    "search-variants-klotho": _variants_cmd(variants_examples.run_klotho),
    "search-variants-brca1": _variants_cmd(variants_examples.run_brca1),
    "search-reads-example-1": _reads_cmd(reads_examples.run_example1, ["readset"]),
    "search-reads-example-2": _reads_cmd(reads_examples.run_example2, ["readset"]),
    "search-reads-example-3": _reads_cmd(reads_examples.run_example3, ["readset"]),
    "search-reads-example-4": _reads_cmd(
        reads_examples.run_example4, ["normal_readset", "tumor_readset"]
    ),
}


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m spark_examples_tpu <command> [flags]")
        print("commands:")
        for name in COMMANDS:
            print(f"  {name}")
        return 0
    command, rest = argv[0], argv[1:]
    if command not in COMMANDS:
        print(f"unknown command: {command}", file=sys.stderr)
        return 2
    if command in ("graftcheck", "submit", "trace", "obs"):
        # Analysis-only / client-only: no platform override, no compile
        # cache — graftcheck must run identically on devices-free CI
        # boxes, `submit` talks to a (possibly remote) daemon without
        # initializing a local backend, and `trace export` / `obs
        # report` are pure file I/O over a run dir. Exit codes
        # propagate.
        return int(COMMANDS[command](rest))
    # After the help/unknown early-outs: only real commands pay (and benefit
    # from) the process-global compile cache configuration.
    from spark_examples_tpu.utils.cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    if command == "serve":
        # The daemon's exit code IS the drain verdict (ci.sh gates on it).
        return int(COMMANDS[command](rest))
    COMMANDS[command](rest)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
