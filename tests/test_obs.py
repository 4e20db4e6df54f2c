"""The structured telemetry subsystem (``spark_examples_tpu/obs/``):
registry semantics and thread-safety, span nesting, heartbeat lifecycle,
manifest schema round-trip, and end-to-end parity between the printed
epilogue and the machine-readable manifest across ingest paths."""

import json
import re
import threading

import numpy as np
import pytest

from spark_examples_tpu.obs.heartbeat import Heartbeat
from spark_examples_tpu.obs.manifest import (
    build_run_manifest,
    manifest_metric_value,
    read_manifest,
    validate_manifest,
    write_manifest,
)
from spark_examples_tpu.obs.metrics import MetricError, MetricsRegistry
from spark_examples_tpu.obs.spans import SpanRecorder
from spark_examples_tpu.pipeline.stats import VariantsDatasetStats
from spark_examples_tpu.sources.base import ClientCounters
from spark_examples_tpu.utils.tracing import StageTimes

# ------------------------------------------------------------------ registry


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "Requests.")
    c.inc()
    c.inc(4)
    assert reg.value("requests_total") == 5
    with pytest.raises(MetricError):
        c.inc(-1)

    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert reg.value("depth") == 2
    g.set_function(lambda: 42)
    assert reg.value("depth") == 42

    h = reg.histogram("latency_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.value
    assert snap["count"] == 3
    assert snap["sum"] == pytest.approx(5.55)
    assert snap["buckets"] == {"0.1": 1, "1": 2, "+Inf": 3}


def test_labeled_series_and_registration_conflicts():
    reg = MetricsRegistry()
    fam = reg.counter("flushes_total", labelnames=("strategy",))
    fam.labels(strategy="dense").inc(2)
    fam.labels(strategy="sharded").inc(1)
    assert reg.value("flushes_total", {"strategy": "dense"}) == 2
    assert reg.value("flushes_total", {"strategy": "sharded"}) == 1
    # Labeled family refuses label-free use; wrong label names refuse.
    with pytest.raises(MetricError):
        fam.inc()
    with pytest.raises(MetricError):
        fam.labels(mode="dense")
    # Idempotent re-registration; kind/label mismatch raises.
    assert reg.counter("flushes_total", labelnames=("strategy",)) is fam
    with pytest.raises(MetricError):
        reg.gauge("flushes_total")
    with pytest.raises(MetricError):
        reg.counter("flushes_total", labelnames=("other",))


def test_registry_thread_safety_under_concurrent_workers():
    """The concurrent-ingest shape: many worker threads incrementing the
    same counters (directly and through VariantsDatasetStats) must lose no
    updates."""
    reg = MetricsRegistry()
    stats = VariantsDatasetStats(reg)
    counter = reg.counter("parallel_total")
    n_threads, n_iter = 8, 2000

    def work():
        client = ClientCounters()
        for _ in range(n_iter):
            counter.inc()
            stats.add_variants(2)
            stats.add_partition(10)
            client.add_request()
        stats.add_client(client)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.value("parallel_total") == n_threads * n_iter
    assert stats.variants == 2 * n_threads * n_iter
    assert stats.partitions == n_threads * n_iter
    assert stats.reference_bases == 10 * n_threads * n_iter
    assert stats.requests == n_threads * n_iter


def test_prometheus_text_export():
    reg = MetricsRegistry()
    reg.counter("io_requests_total", "Requests issued.").inc(3)
    reg.histogram(
        "flush_seconds", labelnames=("strategy",), buckets=(1.0,)
    ).labels(strategy="dense").observe(0.5)
    text = reg.prometheus_text()
    assert "# TYPE io_requests_total counter" in text
    assert "io_requests_total 3" in text
    assert 'flush_seconds_bucket{le="1",strategy="dense"} 1' in text
    assert 'flush_seconds_count{strategy="dense"} 1' in text


# ------------------------------------------------------------- stats shim


def test_stats_report_format_unchanged_and_writes_forbidden():
    stats = VariantsDatasetStats()
    stats.add_partition(1000)
    stats.add_variants(7)
    stats.add_requests(3)
    stats.add_client(
        ClientCounters(
            initialized_requests=2, unsuccessful_responses=1, io_exceptions=1
        )
    )
    assert str(stats) == (
        "Variants API stats:\n"
        "-------------------------------\n"
        "# of partitions: 1\n"
        "# of bases requested: 1000\n"
        "# of variants read: 7\n"
        "# of API requests: 5\n"
        "# of unsuccessful responses: 1\n"
        "# of IO exceptions: 1\n"
    )
    # The satellite contract: the old lock-bypassing mutation now fails.
    with pytest.raises(AttributeError, match="add_requests"):
        stats.requests += 1
    with pytest.raises(AttributeError):
        stats.variants = 0
    assert stats.as_dict() == {
        "partitions": 1,
        "reference_bases": 1000,
        "variants": 7,
        "requests": 5,
        "unsuccessful_responses": 1,
        "io_exceptions": 1,
        "io_retries": 0,
    }


# ---------------------------------------------------------------- spans


def test_span_nesting_and_ordering():
    rec = SpanRecorder()
    with rec.span("run"):
        with rec.span("ingest"):
            rec.add("chunk-parse", 0.25)
            with rec.span("dispatch"):
                pass
        with rec.span("pca", sync=lambda: None):
            pass
    (root,) = rec.as_list()
    assert root["name"] == "run"
    assert [c["name"] for c in root["children"]] == ["ingest", "pca"]
    ingest, pca = root["children"]
    assert [c["name"] for c in ingest["children"]] == ["chunk-parse", "dispatch"]
    assert ingest["children"][0]["seconds"] == 0.25
    assert pca["synced"] is True and ingest["synced"] is False
    paths = [row["path"] for row in rec.flat()]
    assert paths == [
        "run", "run/ingest", "run/ingest/chunk-parse",
        "run/ingest/dispatch", "run/pca",
    ]
    assert rec.find("run/ingest/dispatch") is not None
    assert rec.find("run/nope") is None
    # Durations nest sanely: the parent covers its children.
    assert root["seconds"] >= ingest["seconds"] + pca["seconds"] - 1e-6


def test_span_survives_raising_sync():
    """A sync fetch that raises (device error — the case sync exists for)
    must still close the span and pop the stack, or every later span on
    the thread would nest under a dead parent."""
    rec = SpanRecorder()
    with pytest.raises(RuntimeError):
        with rec.span("stage", sync=lambda: (_ for _ in ()).throw(
            RuntimeError("fetch failed")
        )):
            pass
    assert rec.find("stage").seconds is not None
    with rec.span("next"):
        pass
    # "next" rooted independently — not swallowed as a child of "stage".
    assert [s["name"] for s in rec.as_list()] == ["stage", "next"]


def test_span_records_on_exception_and_across_threads():
    rec = SpanRecorder()
    with pytest.raises(RuntimeError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise RuntimeError("boom")
    outer = rec.find("outer")
    assert outer is not None and outer.seconds is not None
    assert rec.find("outer/inner").seconds is not None

    # A second thread's spans root independently (no cross-thread nesting).
    def other():
        with rec.span("worker"):
            pass

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert [s["name"] for s in rec.as_list()] == ["outer", "worker"]


def test_closed_spans_reach_the_process_buffer():
    from spark_examples_tpu.obs.spans import recent_spans

    rec = SpanRecorder(run_id="run-a")
    with rec.span("ingest") as ingest:
        with rec.span("enqueue"):
            rec.add("stats", 0.125)
        ingest.attrs.update(sites_valid=10, sites_capacity=16)
    mine = [r for r in recent_spans() if r["run_id"] == "run-a"]
    assert [r["path"] for r in mine] == [
        "ingest/enqueue/stats", "ingest/enqueue", "ingest",
    ]
    assert [r["parent"] for r in mine] == ["ingest/enqueue", "ingest", None]
    assert mine[-1]["attrs"] == {"sites_valid": 10, "sites_capacity": 16}
    assert mine[0]["seconds"] == 0.125
    assert all(isinstance(r["started_unix_ns"], int) for r in mine)
    # The manifest's tree carries the attributes too.
    assert rec.as_list()[0]["attrs"] == {"sites_valid": 10, "sites_capacity": 16}


def test_span_buffer_stays_bounded():
    from spark_examples_tpu.obs import spans

    limit = spans._RECENT.maxlen
    rec = SpanRecorder(run_id="flood")
    for _ in range(limit + 50):
        with rec.span("tick"):
            pass
    assert len(spans.recent_spans()) == limit
    assert all(r["run_id"] == "flood" for r in spans.recent_spans())


def test_span_self_time_excludes_children():
    rec = SpanRecorder()
    with rec.span("enqueue"):
        rec.add("stats", 0.5)
        with rec.span("fetch"):
            pass
    enqueue = rec.find("enqueue")
    fetch = rec.find("enqueue/fetch")
    # The pre-measured aggregate may exceed the real elapsed time; self
    # time is duration minus every closed child, whatever they claim.
    assert enqueue.self_seconds == pytest.approx(
        enqueue.seconds - 0.5 - fetch.seconds
    )
    assert fetch.self_seconds == fetch.seconds


def test_device_gen_spans_land_in_the_profiler_trace(tmp_path):
    """Under a profiler session, a tiny device-generation job's stage
    spans are host events of the xplane, named ``sxt:<path>`` and nested
    in time."""
    import jax
    from jax.profiler import ProfileData

    from spark_examples_tpu.config import PcaConf
    from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    source = SyntheticGenomicsSource(num_samples=8, seed=3)
    conf = PcaConf.parse(
        ["--ingest", "device", "--num-samples", "8", "--block-size", "64",
         "--references", "1:0:40000"]
    )
    driver = VariantsPcaDriver(conf, source, devices=jax.devices()[:1])
    contigs = conf.get_contigs(source, conf.variant_set_id)
    with jax.profiler.trace(str(tmp_path)):
        driver.get_similarity_device_gen(contigs)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sxt:"):
                    events[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    assert {"sxt:ingest", "sxt:ingest/enqueue"} <= set(events)
    outer, inner = events["sxt:ingest"], events["sxt:ingest/enqueue"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    ingest = driver.spans.find("ingest")
    assert ingest.run_id == driver.spans.run_id and ingest.run_id
    assert [c.name for c in ingest.children] == [
        "accumulator-init", "enqueue", "sync",
    ]
    enqueue = driver.spans.find("ingest/enqueue")
    assert {c.name for c in enqueue.children} >= {"dispatch", "stats"}
    assert 0 < driver.spans.find("ingest/enqueue/dispatch").seconds <= enqueue.seconds
    # A dense run on the CPU: no ring bytes, no device memory stats.
    assert set(ingest.attrs) == {
        "sites_valid", "sites_capacity", "pop_segments",
        "gramian_bytes_per_device", "gramian_copies_max",
    }
    assert 0 < ingest.attrs["sites_valid"] <= ingest.attrs["sites_capacity"]
    assert ingest.attrs["pop_segments"] == 0  # 8 samples gather their thresholds


def test_stage_times_format_and_recorder_shim():
    rec = SpanRecorder()
    times = StageTimes(recorder=rec)
    with times.stage("ingest+similarity"):
        pass
    with times.stage("center+pca", sync=lambda: None):
        pass
    text = str(times)
    lines = text.splitlines()
    assert lines[0] == "Stage timings:"
    assert lines[1] == "-------------------------------"
    assert re.fullmatch(r"ingest\+similarity: \d+\.\d{3} s", lines[2])
    assert re.fullmatch(r"center\+pca: \d+\.\d{3} s", lines[3])
    assert re.fullmatch(r"total: \d+\.\d{3} s", lines[4])
    # Every stage is also a span; the two views agree numerically.
    assert times.as_dict() == {
        s["name"]: s["seconds"] for s in rec.as_list()
    }


# ------------------------------------------------------------- heartbeat


def test_heartbeat_emits_and_stops_cleanly_on_error():
    reg = MetricsRegistry()
    reg.gauge("ingest_sites_scanned").set(1000)
    reg.counter("io_partitions_total").inc(2)
    reg.gauge("ingest_partitions_planned").set(8)
    emitted = []
    hb = Heartbeat(0.01, reg, emit=emitted.append)
    with pytest.raises(RuntimeError):
        with hb:
            deadline = threading.Event()
            for _ in range(500):
                if emitted:
                    break
                deadline.wait(0.01)
            raise RuntimeError("driver failed mid-run")
    assert not hb.running  # stopped by the context manager despite the error
    assert len(emitted) >= 1
    count_after_stop = len(emitted)
    threading.Event().wait(0.05)
    assert len(emitted) == count_after_stop  # silence after stop()
    line = emitted[0]
    assert line.startswith("heartbeat[")
    assert "1,000 sites scanned" in line
    assert "partitions 2/8" in line
    hb.stop()  # idempotent


def test_heartbeat_rate_and_eta_segments():
    reg = MetricsRegistry()
    sites = reg.gauge("ingest_sites_scanned")
    done = reg.counter("io_partitions_total")
    reg.gauge("ingest_partitions_planned").set(4)
    clock = [0.0]
    hb = Heartbeat(10.0, reg, emit=lambda line: None, clock=lambda: clock[0])
    hb._started_at = 0.0
    sites.set(0)
    hb.line()  # prime the rate baseline
    clock[0] = 10.0
    sites.set(50_000)
    done.inc(1)
    line = hb.line()
    assert "(5.0k sites/s)" in line
    assert "partitions 1/4 (ETA 30s)" in line
    assert "no progress metrics" not in line


def test_heartbeat_rejects_nonpositive_interval():
    with pytest.raises(ValueError):
        Heartbeat(0.0, MetricsRegistry())


def test_function_backed_gauge_rejects_inc():
    reg = MetricsRegistry()
    g = reg.gauge("occupancy")
    g.set_function(lambda: 3)
    with pytest.raises(MetricError, match="function-backed"):
        g.inc()
    g.set(1)  # set() detaches the sampler; deltas work again
    g.inc(2)
    assert reg.value("occupancy") == 3


def test_heartbeat_prefers_streaming_partitions_done_gauge():
    """The streamed ingest flushes io_partitions_total only after the full
    pass; its live ingest_partitions_done gauge must drive the heartbeat's
    progress segment instead of a run-long 0/N."""
    reg = MetricsRegistry()
    reg.gauge("ingest_partitions_planned").set(10)
    reg.counter("io_partitions_total")  # still 0 — flushed at stream end
    reg.gauge("ingest_partitions_done").set(4)
    clock = [100.0]
    hb = Heartbeat(10.0, reg, emit=lambda line: None, clock=lambda: clock[0])
    hb._started_at = 0.0
    assert "partitions 4/10 (ETA 150s)" in hb.line()


def test_stream_counters_publish_live_progress_gauges():
    from spark_examples_tpu.sources.files import StreamCounters

    reg = MetricsRegistry()
    counters = StreamCounters(5, registry=reg)
    counters.add_shard_rows(0, 30)
    counters.add_shard_rows(0, 10)
    counters.add_shard_rows(2, 20)
    assert reg.value("ingest_sites_scanned") == 60
    assert reg.value("ingest_partitions_done") == 2
    # Empty windows the cursor passed count as reached too — otherwise
    # done/planned would never converge and the ETA would grow forever.
    counters.mark_window_reached(1)
    assert reg.value("ingest_partitions_done") == 3
    assert 1 not in counters.shard_rows  # reached, but contributed no rows


# -------------------------------------------------------------- manifest


def test_manifest_round_trip_and_validation(tmp_path):
    reg = MetricsRegistry()
    reg.counter("io_requests_total").inc(3)
    reg.histogram("gramian_flush_seconds", labelnames=("strategy",)).labels(
        strategy="dense"
    ).observe(0.01)
    rec = SpanRecorder()
    with rec.span("ingest+similarity"):
        rec.add("dispatch", 0.5)
    stats = VariantsDatasetStats(reg)
    stats.add_partition(100)
    doc = build_run_manifest(
        conf={"num_pc": 2},
        spans=rec,
        registry=reg,
        io_stats=stats,
        overlap={"parse_busy_seconds": 0.1, "blocks": 4},
    )
    assert validate_manifest(doc) == []
    path = tmp_path / "out" / "manifest.json"
    write_manifest(str(path), doc)
    loaded = read_manifest(str(path))
    assert validate_manifest(loaded) == []
    assert loaded["io_stats"]["partitions"] == 1
    assert loaded["config"]["num_pc"] == 2
    assert manifest_metric_value(loaded, "io_requests_total") == 3
    # Histogram series read back as the bare snapshot (no labels key).
    snap = manifest_metric_value(
        loaded, "gramian_flush_seconds", {"strategy": "dense"}
    )
    assert snap["count"] == 1 and "labels" not in snap
    assert manifest_metric_value(loaded, "nope", default=-1) == -1
    assert loaded["spans"][0]["children"][0]["name"] == "dispatch"
    # JSON round-trip is loss-free for the metric payload.
    assert json.loads(json.dumps(doc["metrics"])) == loaded["metrics"]
    # Rewrites are atomic and leave no temp debris behind.
    write_manifest(str(path), doc)
    assert [p.name for p in path.parent.iterdir()] == [path.name]


def test_manifest_validation_catches_tampering():
    doc = build_run_manifest(conf={}, spans=SpanRecorder(), registry=MetricsRegistry())
    assert validate_manifest(doc) == []
    bad = json.loads(json.dumps(doc))
    bad["schema"]["version"] = 99
    assert any("version" in e for e in validate_manifest(bad))
    bad = json.loads(json.dumps(doc))
    del bad["metrics"]
    assert any("metrics" in e for e in validate_manifest(bad))
    bad = json.loads(json.dumps(doc))
    bad["spans"] = [{"name": 3, "seconds": -1, "children": []}]
    errors = validate_manifest(bad)
    assert any("name" in e for e in errors)
    assert any("seconds" in e for e in errors)
    bad = json.loads(json.dumps(doc))
    bad["io_stats"] = {"partitions": "many"}
    assert any("io_stats.partitions" in e for e in validate_manifest(bad))
    assert validate_manifest([]) == ["manifest is not a JSON object"]


def test_manifest_validation_treats_io_retries_as_additive():
    """``io_retries`` joined IO_STAT_FIELDS after schema v2 shipped:
    archived v2 manifests without it must still validate (the additive
    contract), while the other fields stay required."""
    from spark_examples_tpu.obs.manifest import IO_STAT_FIELDS

    doc = build_run_manifest(
        conf={}, spans=SpanRecorder(), registry=MetricsRegistry()
    )
    doc = json.loads(json.dumps(doc))
    doc["io_stats"] = {f: 0 for f in IO_STAT_FIELDS}
    assert validate_manifest(doc) == []
    del doc["io_stats"]["io_retries"]  # a pre-0.6 archived manifest
    assert validate_manifest(doc) == []
    del doc["io_stats"]["requests"]  # required fields stay enforced
    assert any("io_stats.requests" in e for e in validate_manifest(doc))


# ------------------------------------------------- end-to-end driver parity


def _parse_epilogue(out: str) -> dict:
    """The printed I/O stats block → dict (the operator-facing numbers)."""
    patterns = {
        "partitions": r"# of partitions: (\d+)",
        "reference_bases": r"# of bases requested: (\d+)",
        "variants": r"# of variants read: (\d+)",
        "requests": r"# of API requests: (\d+)",
        "unsuccessful_responses": r"# of unsuccessful responses: (\d+)",
        "io_exceptions": r"# of IO exceptions: (\d+)",
    }
    return {k: int(re.search(p, out).group(1)) for k, p in patterns.items()}


def test_manifest_matches_printed_epilogue_exactly(tmp_path, capsys):
    """The acceptance contract: a synthetic run with --metrics-json and a
    heartbeat produces a schema-valid manifest whose io stats and stage
    spans match the printed epilogue exactly."""
    from spark_examples_tpu.pipeline import pca_driver

    path = tmp_path / "manifest.json"
    pca_driver.run(
        [
            "--num-samples", "6",
            "--references", "1:0:40000",
            "--metrics-json", str(path),
            "--heartbeat-seconds", "1",
            "--profile-dir", str(tmp_path / "trace"),
        ]
    )
    out = capsys.readouterr().out
    doc = read_manifest(str(path))
    assert validate_manifest(doc) == []
    # io_retries rides the manifest only (the printed report keeps the
    # reference's six-line format, pipeline/stats.py).
    assert doc["io_stats"] == {**_parse_epilogue(out), "io_retries": 0}
    # Stage spans match the printed Stage timings block to the 3 printed
    # decimals (both are views of one measurement).
    printed = dict(
        re.findall(r"^([\w+]+): (\d+\.\d{3}) s$", out, flags=re.M)
    )
    spans = {s["name"]: s["seconds"] for s in doc["spans"]}
    for name in ("ingest+similarity", "center+pca"):
        assert f"{spans[name]:.3f}" == printed[name]
    assert doc["config"]["num_samples"] == 6
    assert manifest_metric_value(doc, "ingest_sites_scanned") > 0


def test_unwritable_manifest_path_does_not_destroy_the_run(tmp_path, capsys):
    """A typo'd --metrics-json path must not throw away hours of completed
    compute: the results return, the failure is reported on stderr."""
    from spark_examples_tpu.pipeline import pca_driver

    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    lines = pca_driver.run(
        [
            "--num-samples", "5",
            "--references", "1:0:30000",
            "--metrics-json", str(blocker / "manifest.json"),
        ]
    )
    captured = capsys.readouterr()
    assert len(lines) == 5  # the PCA result survived
    assert "Run manifest NOT written" in captured.err
    assert "Run manifest written" not in captured.out


def test_sharded_accumulator_finalize_paths_record_telemetry():
    import jax

    from spark_examples_tpu.ops.gramian import ShardedGramianAccumulator
    from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS, make_mesh

    mesh = make_mesh({SAMPLES_AXIS: min(2, jax.device_count())})
    for finalize in ("finalize", "finalize_device_padded", "finalize_sharded"):
        reg, rec = MetricsRegistry(), SpanRecorder()
        acc = ShardedGramianAccumulator(
            8, mesh, block_size=4, registry=reg, spans=rec
        )
        acc.add_rows(np.ones((6, 8), dtype=np.uint8))
        with rec.span("ingest+similarity"):
            getattr(acc, finalize)()
        assert reg.value("gramian_rows_total", {"strategy": "sharded"}) == 6
        (ingest,) = rec.as_list()
        assert [c["name"] for c in ingest["children"]] == [
            "dispatch",
            "reduce-flush",
        ], finalize


def test_stdout_byte_identical_with_telemetry_off(capsys):
    """Telemetry defaults (no heartbeat, no manifest) leave stdout exactly
    as a telemetry-free run prints it."""
    from spark_examples_tpu.pipeline import pca_driver

    args = ["--num-samples", "5", "--references", "1:0:30000"]
    pca_driver.run(args)
    first = capsys.readouterr()
    pca_driver.run(args)
    second = capsys.readouterr()
    assert first.out == second.out
    assert "heartbeat" not in first.out + first.err
    assert "manifest" not in first.out.lower()


def _write_small_vcf(tmp_path) -> str:
    rng = np.random.default_rng(7)
    lines = [
        "##fileformat=VCFv4.2",
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\t"
        + "\t".join(f"S{i}" for i in range(5)),
    ]
    for k in range(90):
        gts = rng.choice(["0|0", "0|1", "1|1"], size=5)
        info = f"AF={rng.random():.4f}" if k % 4 else "NS=2"
        lines.append(
            f"17\t{100 + 29 * k}\t.\tA\tG\t.\t.\t{info}\tGT\t" + "\t".join(gts)
        )
    path = tmp_path / "cohort.vcf"
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def test_stats_parity_packed_streaming_wire_in_manifest(tmp_path, capsys):
    """The I/O stats block of the manifest is identical across the packed,
    streaming, and wire ingest paths of the same file — the parity the
    printed reports have always had, now asserted on the structured form."""
    from spark_examples_tpu.pipeline import pca_driver

    vcf = _write_small_vcf(tmp_path)
    base = [
        "--source", "file", "--input-files", vcf,
        "--references", "17:0:2700",
        "--min-allele-frequency", "0.05",
        "--block-size", "32",
    ]
    docs = {}
    for mode, extra in {
        "packed": ["--ingest", "packed", "--stream-chunk-bytes", "0"],
        "streamed": ["--stream-chunk-bytes", "256"],
        "wire": ["--ingest", "wire", "--stream-chunk-bytes", "0"],
    }.items():
        path = tmp_path / f"{mode}.json"
        pca_driver.run(base + extra + ["--metrics-json", str(path)])
        capsys.readouterr()
        docs[mode] = read_manifest(str(path))
        assert validate_manifest(docs[mode]) == []
    # Packed and streamed agree on the full block. The wire path's
    # `variants` deliberately counts pre-filter records seen (the
    # reference's RDD accounting, ``rdd/VariantsRDD.scala:214-224``), so it
    # bounds the packed count from above; every other field agrees.
    assert docs["packed"]["io_stats"] == docs["streamed"]["io_stats"]
    wire = dict(docs["wire"]["io_stats"])
    packed = dict(docs["packed"]["io_stats"])
    assert wire.pop("variants") >= packed.pop("variants")
    assert wire == packed
    # The overlap block lands in the manifest on the prefetching paths.
    for mode in ("packed", "streamed"):
        overlap = docs[mode]["overlap"]
        assert overlap is not None
        assert overlap["blocks"] >= 1
        assert (
            manifest_metric_value(docs[mode], "prefetch_blocks_total")
            == overlap["blocks"]
        )


def test_prefetch_overlap_structured_and_report_formats_it():
    from spark_examples_tpu.pipeline.datasets import PrefetchIterator

    reg = MetricsRegistry()
    prefetch = PrefetchIterator(iter(range(5)), depth=2, registry=reg)
    assert list(prefetch) == [0, 1, 2, 3, 4]
    prefetch.close()
    stats = prefetch.overlap_stats()
    assert stats["blocks"] == 5 and stats["queue_depth"] == 2
    report = prefetch.overlap_report()
    assert report == (
        f"ingest overlap: parse {stats['parse_busy_seconds']:.3f}s busy, "
        f"{stats['parse_blocked_on_feed_seconds']:.3f}s blocked on device "
        f"feed (backpressure); feeder waited "
        f"{stats['feeder_waited_on_parse_seconds']:.3f}s on parse; 5 blocks "
        f"through a depth-2 queue"
    )
    assert reg.value("prefetch_blocks_total") == 5
    assert reg.value("prefetch_queue_depth") == 2
    assert reg.value("ingest_overlap_parse_busy_seconds") == pytest.approx(
        stats["parse_busy_seconds"]
    )
    # close() froze the live occupancy gauge (sampler detached): the value
    # is the final queue size, and deltas no longer raise as they would on
    # a function-backed gauge.
    occupancy = reg.gauge("prefetch_queue_occupancy")
    assert reg.value("prefetch_queue_occupancy") == 0
    occupancy.inc(0)  # would raise MetricError if still function-backed


def test_gramian_flush_telemetry():
    from spark_examples_tpu.ops.gramian import GramianAccumulator

    reg = MetricsRegistry()
    rec = SpanRecorder()
    acc = GramianAccumulator(8, block_size=4, registry=reg, spans=rec)
    rows = np.ones((10, 8), dtype=np.uint8)
    with rec.span("ingest+similarity"):
        acc.add_rows(rows)
        acc.finalize_device()
    # 10 rows through a 4-row staging block: flushes of 4 + 4 + 2 (the
    # finalize flush); padding rows are not counted.
    assert reg.value("gramian_rows_total", {"strategy": "dense"}) == 10
    assert reg.value("gramian_flushes_total", {"strategy": "dense"}) == 3
    hist = reg.value("gramian_flush_seconds", {"strategy": "dense"})
    assert hist["count"] == 3
    (ingest,) = [s for s in rec.as_list() if s["name"] == "ingest+similarity"]
    names = [c["name"] for c in ingest["children"]]
    assert names == ["dispatch", "reduce-flush"]


# ----------------------------------------------- exposition-format escaping


def test_prometheus_label_value_escaping():
    """Regression: label values carrying the three characters the text
    exposition format names — backslash, double-quote, newline — must
    escape per the spec, backslash first (so the later replacements
    cannot double-escape their own output)."""
    reg = MetricsRegistry()
    gauge = reg.gauge("escape_test", "", labelnames=("path",))
    gauge.labels(path='C:\\temp\\"quoted"\nnext').set(1)
    text = reg.prometheus_text()
    line = next(l for l in text.splitlines() if l.startswith("escape_test"))
    assert line == (
        'escape_test{path="C:\\\\temp\\\\\\"quoted\\"\\nnext"} 1'
    )
    # Exactly one physical line: the raw newline never leaks through.
    assert sum(1 for l in text.splitlines() if "escape_test" in l) == 2
    # A literal backslash-n sequence stays distinguishable from a real
    # newline after escaping (the round-trip-ability the spec is for).
    gauge2 = reg.gauge("escape_test_2", "", labelnames=("v",))
    gauge2.labels(v="a\\nb").set(1)
    assert 'escape_test_2{v="a\\\\nb"} 1' in reg.prometheus_text()


def test_prometheus_help_text_escaping():
    """HELP lines escape backslash and newline (a raw newline would
    terminate the comment mid-help and leave an unparseable line)."""
    reg = MetricsRegistry()
    reg.counter("help_test", "line one\nline two \\ backslash").inc()
    text = reg.prometheus_text()
    assert "# HELP help_test line one\\nline two \\\\ backslash" in text
    for line in text.splitlines():
        assert line.startswith(("#", "help_test"))


def test_escape_helpers_are_exact():
    from spark_examples_tpu.obs.metrics import (
        escape_help_text,
        escape_label_value,
    )

    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"
    assert escape_label_value("\\\n\"") == '\\\\\\n\\"'
    assert escape_help_text('a"b') == 'a"b'  # quotes legal in help
    assert escape_help_text("a\\\nb") == "a\\\\\\nb"


# ----------------------------------------- span recorder under concurrency


def test_span_recorder_thread_safety_under_concurrent_slices():
    """The serve daemon's slice workers nest spans concurrently in ONE
    recorder (each slice its own thread): per-thread stacks must keep
    every tree correctly nested with zero cross-thread adoption and zero
    lost spans under a start-barrier stampede."""
    rec = SpanRecorder()
    workers, jobs_per_worker = 8, 25
    barrier = threading.Barrier(workers)
    errors = []

    def slice_worker(idx):
        try:
            barrier.wait(timeout=10)
            for j in range(jobs_per_worker):
                with rec.span(f"job w{idx}-{j}") as outer:
                    with rec.span("admission"):
                        pass
                    with rec.span("device"):
                        with rec.span("flush"):
                            pass
                # Closed and attached as this thread's root: never
                # adopted by another thread's open span.
                assert outer.seconds is not None
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=slice_worker, args=(i,))
        for i in range(workers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    roots = rec.as_list()
    assert len(roots) == workers * jobs_per_worker
    for root in roots:
        assert re.fullmatch(r"job w\d+-\d+", root["name"])
        assert [c["name"] for c in root["children"]] == [
            "admission",
            "device",
        ]
        assert [c["name"] for c in root["children"][1]["children"]] == [
            "flush"
        ]
        assert root["seconds"] is not None
    # Per-worker ordering survives the interleaving (roots attach at
    # close time, but each worker's own jobs close in order).
    for idx in range(workers):
        mine = [
            r["name"] for r in roots if r["name"].startswith(f"job w{idx}-")
        ]
        assert mine == [f"job w{idx}-{j}" for j in range(jobs_per_worker)]
    # The per-thread stacks drained: nothing left open.
    assert rec._stacks == {}


def test_span_recorder_concurrent_add_and_span():
    """Pre-measured add() aggregates from worker threads land as roots
    (or under that thread's open span), never under another thread's."""
    rec = SpanRecorder()
    stop = threading.Event()

    def adder():
        while not stop.is_set():
            rec.add("flush-aggregate", 0.001)

    t = threading.Thread(target=adder)
    t.start()
    try:
        for _ in range(50):
            with rec.span("driver-stage"):
                pass
    finally:
        stop.set()
        t.join(timeout=10)
    for root in rec.as_list():
        if root["name"] == "driver-stage":
            assert root["children"] == []


# --------------------------------------------- heartbeat replica segments


def test_heartbeat_replica_lease_steal_segments():
    from spark_examples_tpu.obs.metrics import (
        SERVE_JOBS_STOLEN,
        SERVE_LEASE_RENEWALS,
        SERVE_REPLICAS_ALIVE,
        well_known_counter,
        well_known_gauge,
    )

    reg = MetricsRegistry()
    hb = Heartbeat(60.0, reg)
    well_known_gauge(reg, SERVE_REPLICAS_ALIVE).set(0)
    # Solo mode (0 replicas heartbeating): the segment stays silent.
    assert "replicas" not in hb.line()
    well_known_gauge(reg, SERVE_REPLICAS_ALIVE).set(2)
    assert "replicas 2 alive" in hb.line()
    well_known_counter(reg, SERVE_JOBS_STOLEN).inc(3)
    well_known_counter(reg, SERVE_LEASE_RENEWALS).inc(17)
    line = hb.line()
    assert "replicas 2 alive (stolen 3, lease renewals 17)" in line
