"""Run one benchmark cell once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prepares the data set of the cell's job kind where the kind has one
(``batch.py``; its seconds are printed and left out of set-up), loads and
warms up the cell (set-up), measures for ``--seconds`` with the profiler
off (``--trace 0``: the cell's end-to-end metrics) or on for a shorter
traced window (``--trace 1``: its per-layer metrics), checks what the
timed path produced (by the kind's check; by default against the plain
reference, ``reference.py``), and prints one JSON object as the last line
of standard output. Everything else, the compared numbers last, goes to
standard error. With no TPU, or fewer chips than the cell asks for, it
exits 1 and prints no result. ``--dry`` (tests only) runs the cell's
``dry`` sizes on any backend.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path[0] != ROOT:
    sys.path.insert(0, ROOT)

from benchmark import core  # noqa: E402


class Run:
    """What the per-layer metric readers see of one run."""

    def __init__(self, cell, device_kind, jobs, trace=None):
        self.cell = cell
        self.device_kind = device_kind
        self.jobs = jobs
        self.trace = trace


def _check_checkout() -> None:
    import spark_examples_tpu

    package = os.path.dirname(os.path.abspath(spark_examples_tpu.__file__))
    if package != os.path.join(ROOT, "spark_examples_tpu"):
        raise core.BenchFailure(f"spark_examples_tpu imported from {package}, not {ROOT}")


def _start_trace(directory: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)


def _stop_trace(directory: str):
    import jax

    from benchmark.trace import Trace

    jax.profiler.stop_trace()
    return Trace.load(directory)


def run_batch(cell, args, devices, clock, trace_dir):
    from benchmark import batch

    with batch.set_up(cell, devices, bool(args.trace)) as (kind, job, prepare_s):
        setup_s = core.process_age_s() - prepare_s
        before = clock.snapshot()
        seconds = min(args.seconds, cell["traffic"]["trace_seconds"]) if args.trace else args.seconds
        if args.trace:
            _start_trace(trace_dir)
        result = batch.window(job, args.seed, seconds)
        trace = _stop_trace(trace_dir) if args.trace else None
        compiles = clock.since(before)
        device = core.device_block(devices)
        jobs = result["jobs"]
        e2e = {"job_s": result["elapsed"] / len(jobs), "setup_s": setup_s}
        counts = "".join(
            f", {label} {jobs[0][key]}"
            for key, label in (("dispatches", "dispatches"), ("sites_scanned", "sites"))
            if key in jobs[0]
        )
        core.say(
            f"window: {len(jobs)} jobs in {result['elapsed']:.3f} s, job seconds "
            f"{[round(j['seconds'], 4) for j in jobs]}{counts}"
        )
        run = Run(cell, device["kind"], jobs, trace)
        numbers = batch.compare(kind, cell, result["kept"])
    return run, e2e, numbers, device, compiles, len(jobs), 0


def run_served(cell, args, devices, clock, trace_dir):
    from benchmark import served

    svc = served.Service(cell)
    try:
        svc.warm_up(args.seed)
        setup_s = core.process_age_s()
        before = clock.snapshot()
        seconds = min(args.seconds, cell["traffic"]["trace_seconds"]) if args.trace else args.seconds
        if args.trace:
            _start_trace(trace_dir)
            import jax

            with jax.profiler.TraceAnnotation("bench:window"):
                mark = time.perf_counter()
                result = served.window(svc, args.seed, seconds, cell["traffic"]["poll_s"])
        else:
            result = served.window(svc, args.seed, seconds, cell["traffic"]["poll_s"])
    finally:
        svc.close()
    trace = None
    if args.trace:
        from benchmark.trace import Trace

        trace = _stop_trace(trace_dir)
        # Requests in flight, moved onto the trace's clock by the window
        # span (opened at ``mark`` on this thread's clock), name the
        # device's idle gaps; a gap outside them had no request in flight.
        origin = trace.spans_named("window")[0][0]

        def at(t):
            return origin + (t - mark) * 1e9

        flights = [
            ("bench:request-in-flight", at(r["sent"]), at(r.get("done", result["deadline"])))
            for r in result["requests"] if "sent" in r
        ]
        trace = Trace(trace.ops, trace.modules, trace.spans + flights, window=trace.window)
    compiles = clock.since(before)
    device = core.device_block(devices)
    lat = served.latencies(result)
    requests = result["requests"]
    e2e = {
        "served_p50_s": core.nearest_rank(lat, 0.50),
        "served_p95_s": core.nearest_rank(lat, 0.95),
        "setup_s": setup_s,
    }
    late = [r["sent"] - (result["t0"] + r["due"]) for r in requests if "sent" in r]
    statuses = {}
    for r in requests:
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    core.say(
        f"window: {len(requests)} requests, statuses {statuses}, generator lateness "
        f"p50 {core.nearest_rank(late, 0.5):.6f} s max {max(late):.6f} s, fused sizes "
        f"{sorted(r['job'].get('fused_size') or 1 for r in requests if r.get('job'))[-5:]}, "
        f"latencies {[round(x, 4) for x in served.latencies(result)]}"
    )
    jobs = [
        {
            "queue_wait_seconds": (r["job"].get("cost") or {}).get("queue_wait_seconds"),
            "seconds": r["job"].get("seconds"),
        }
        for r in requests if r["status"] == "done"
    ]
    run = Run(cell, device["kind"], jobs, trace)
    numbers = served.check(cell, result, args.seed)
    failed = sum(1 for r in requests if r["status"] != "done")
    return run, e2e, numbers, device, compiles, len(requests), failed


LOOPS = {"closed": run_batch, "open": run_served}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--dry", action="store_true", help="tests only: tiny sizes, any backend")
    args = parser.parse_args(argv)

    doc = core.manifest()
    cell = core.cell(args.workload, doc)
    if args.dry:
        cell = core.dry_overrides(cell)
    _check_checkout()
    import jax

    from spark_examples_tpu.utils.cache import enable_persistent_compile_cache

    enable_persistent_compile_cache(persist_all=True)
    clock = core.CompileClock()
    devices = core.require_chips(cell["chips"], args.dry)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        with jax.default_device(devices[0]):
            loop = LOOPS[cell["traffic"]["loop"]]
            run, e2e, numbers, device, compiles, attempted, failed = loop(
                cell, args, devices, clock, trace_dir
            )
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    core.say(f"set-up {e2e['setup_s']:.3f} s; in the window: {compiles}")
    if args.trace:
        metrics = {}
        for m in cell["per_layer"]:
            value = core.load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()
    else:
        metrics = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]} for m in cell["end_to_end"]
        }
    limits = cell["limits"]
    correct = core.judge(numbers, limits)
    line = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        line["breakdown"] = {
            "device_ops": run.trace.top_ops(10),
            "idle_gaps": run.trace.idle_gaps(10),
        }
    line["compared"] = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
    print(json.dumps(line), file=sys.__stdout__, flush=True)
    return 0


if __name__ == "__main__":
    sys.stdout = sys.stderr  # the program's prints; the result line goes to the real stdout
    try:
        code = main()
    except core.BenchFailure as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr, flush=True)
        code = 1
    sys.exit(code)
