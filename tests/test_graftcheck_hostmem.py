"""``graftcheck hostmem``: golden fixtures per GH rule (id + line), the
clean-tree gate over the shipped host-staging layers, escape-hatch
honoring, the ``host_peak_bytes`` formula, the ``graftcheck plan
--host-mem-budget`` accept/reject matrix, the chunked-checkpoint
round-trip regression, and the measured-peak <= static-bound e2e parity
run that proves the formula against reality.

Fixtures are inline sources (the auditor works on text), keeping each
violation's expected LINE NUMBER adjacent to the code that produces it —
the same layout as ``tests/test_graftcheck.py``.
"""

import gzip
import json
import os
import subprocess
import sys
import textwrap

import pytest

from spark_examples_tpu.check.hostmem import (
    audit_paths,
    audit_source,
    conf_host_peak_bytes,
    default_hostmem_paths,
    parse_hostmem_hatches,
)
from spark_examples_tpu.check.plan import validate_plan
from spark_examples_tpu.check.rules import HOSTMEM_RULES
from spark_examples_tpu.config import PcaConf
from spark_examples_tpu.parallel.mesh import (
    HOST_RUNTIME_BASELINE_BYTES,
    host_peak_bytes,
)

_PACKAGE_DIR = os.path.dirname(
    os.path.abspath(__import__("spark_examples_tpu").__file__)
)
_REPO_ROOT = os.path.dirname(_PACKAGE_DIR)


def _ids(findings):
    return [(f.rule_id, f.line) for f in findings]


def _audit(src, relpath="sources/fixture.py"):
    return audit_source(textwrap.dedent(src), relpath)


# --------------------------------------------------------------------------
# Golden fixtures: one violation per rule, asserting id AND line number.
# --------------------------------------------------------------------------


def test_gh001_whole_file_read():
    findings, declared = _audit(
        """
        def load(path):
            with open(path, "rb") as f:
                return f.read()
        """
    )
    assert _ids(findings) == [("GH001", 4)]
    assert declared == []


def test_gh001_readlines_and_clean_windowed_read():
    findings, _ = _audit(
        """
        import gzip
        def load(path):
            f = gzip.open(path, "rb")
            lines = f.readlines()
            return lines
        def windowed(path):
            with open(path, "rb") as f:
                while True:
                    piece = f.read(1 << 20)
                    if not piece:
                        return
                    yield piece
        """
    )
    # The sized read in `windowed` is the bounded idiom — no finding.
    assert _ids(findings) == [("GH001", 5)]


def test_gh002_append_of_stream_items_in_read_loop():
    findings, _ = _audit(
        """
        def parse(path):
            rows = []
            with open(path, "rt") as f:
                for line in f:
                    rows.append(line.split())
            return rows
        """
    )
    assert _ids(findings) == [("GH002", 6)]


def test_gh002_byte_buffer_augassign_and_enumerate_wrapper():
    findings, _ = _audit(
        """
        import gzip
        def slurp(path):
            buf = b""
            with gzip.open(path, "rb") as f:
                while True:
                    piece = f.read(4096)
                    if not piece:
                        break
                    buf += piece
            return buf
        def count(path):
            out = []
            with open(path) as f:
                for i, line in enumerate(f):
                    out.append((i, line))
            return out
        """
    )
    assert _ids(findings) == [("GH002", 10), ("GH002", 16)]


def test_gh002_scalar_extractors_launder_taint():
    findings, _ = _audit(
        """
        def total(path):
            sizes = []
            n = 0
            with open(path, "rb") as f:
                while True:
                    piece = f.read(4096)
                    if not piece:
                        break
                    n += len(piece)
                    sizes.append(len(piece))
            return n, sizes
        """
    )
    # Accounting (len of the chunk) is O(1) per item — not accumulation.
    assert findings == []


def test_gh003_stream_materialization():
    findings, _ = _audit(
        """
        def eager(source, shards):
            blocks = list(source.stream_genotype_blocks("s", shards))
            return blocks
        def lazy(source, shards):
            for block in source.stream_genotype_blocks("s", shards):
                yield block["has_variation"]
        """
    )
    assert _ids(findings) == [("GH003", 3)]


def test_gh003_file_handle_materialization():
    findings, _ = _audit(
        """
        def slurp(path):
            with open(path) as f:
                return list(f)
        """
    )
    assert _ids(findings) == [("GH003", 4)]


def test_gh004_whole_buffer_decompress():
    findings, _ = _audit(
        """
        import gzip
        def load(data):
            return gzip.decompress(data)
        """
    )
    assert _ids(findings) == [("GH004", 4)]


def test_gh005_numpy_staging_of_file_buffer():
    findings, _ = _audit(
        """
        import numpy as np
        def stage(path):
            with open(path, "rb") as f:
                raw = f.read()
            return np.frombuffer(raw, dtype=np.uint8)
        def accumulate(path, chunks):
            parts = []
            with open(path) as f:
                for line in f:
                    parts.append(line)
            return np.stack(parts)
        """
    )
    # The whole-file read fires GH001 at its site and GH005 where the
    # buffer stages into numpy; the stream-accumulated list fires GH002
    # at the append and GH005 at the stack.
    assert _ids(findings) == [
        ("GH001", 5),
        ("GH005", 6),
        ("GH002", 11),
        ("GH005", 12),
    ]


def test_bounded_parser_shapes_stay_clean():
    findings, declared = _audit(
        """
        import numpy as np
        def per_chunk(path, chunk_bytes):
            carry = b""
            with open(path, "rb") as f:
                while True:
                    data = f.read(chunk_bytes)
                    if not data:
                        break
                    data = carry + data
                    cut = data.rfind(b"\\n")
                    if cut < 0:
                        carry = data
                        continue
                    carry = data[cut + 1:]
                    yield np.frombuffer(data[:cut + 1], dtype=np.uint8)
        """
    )
    # One window in, one window out: sized reads, a partial-line carry,
    # and per-chunk numpy staging are the bounded idiom — no findings.
    assert findings == []
    assert declared == []


def test_scope_limited_to_host_staging_layers():
    src = """
    def load(path):
        with open(path, "rb") as f:
            return f.read()
    """
    findings, _ = _audit(src, relpath="utils/fixture.py")
    assert findings == []
    findings, _ = _audit(src, relpath="ops/fixture.py")
    assert _ids(findings) == [("GH001", 4)]


# --------------------------------------------------------------------------
# Escape hatches are FORBIDDEN (GH006): the hatch line itself is a finding,
# justified or not. A justified hatch still routes its underlying GH00x
# finding into the declared inventory so the report says what it hides —
# but the audit fails either way.
# --------------------------------------------------------------------------


def test_justified_hatch_is_a_gh006_finding_with_inventory_context():
    findings, declared = _audit(
        """
        def load(path):
            with open(path, "rb") as f:
                return f.read()  # graftcheck: hostmem(unbounded) -- whole-file parse by contract
        """
    )
    # GH006 fires ON the hatch line; the suppressed GH001 is still
    # surfaced in the declared inventory for context.
    assert _ids(findings) == [("GH006", 4)]
    assert [(d.rule_id, d.line) for d in declared] == [("GH001", 4)]
    assert declared[0].justification == "whole-file parse by contract"


def test_unjustified_hatch_fires_both_rules():
    findings, declared = _audit(
        """
        def load(path):
            with open(path, "rb") as f:
                return f.read()  # graftcheck: hostmem(unbounded)
        """
    )
    assert _ids(findings) == [("GH001", 4), ("GH006", 4)]
    assert declared == []


def test_comment_only_hatch_flagged_and_declares_next_line():
    source = textwrap.dedent(
        """
        def load(path):
            with open(path, "rb") as f:
                # graftcheck: hostmem(unbounded) -- long justification on its own line
                return f.read()
        """
    )
    assert parse_hostmem_hatches(source) == {
        5: "long justification on its own line"
    }
    findings, declared = audit_source(source, "sources/fixture.py")
    assert [(f.rule_id, f.line) for f in findings] == [("GH006", 4)]
    assert [(d.rule_id, d.line) for d in declared] == [("GH001", 5)]


def test_hatch_does_not_leak_to_other_lines():
    findings, _ = _audit(
        """
        def load(path):
            with open(path, "rb") as f:
                a = f.read()  # graftcheck: hostmem(unbounded) -- declared here only
            with open(path, "rb") as g:
                return a + g.read()
        """
    )
    assert _ids(findings) == [("GH006", 4), ("GH001", 6)]


def test_gh006_scope_matches_hostmem_globs():
    # Outside the host-staging layers the hatch comment is inert text.
    findings, declared = _audit(
        """
        def load(path):
            with open(path, "rb") as f:
                return f.read()  # graftcheck: hostmem(unbounded) -- not our layer
        """,
        relpath="utils/fixture.py",
    )
    assert findings == []
    assert declared == []


# --------------------------------------------------------------------------
# The clean-tree gate: the shipped host-staging layers audit clean with a
# ZERO declared-unbounded inventory — every source streams through
# sources/stream.py, and GH006 makes any future hatch a finding.
# --------------------------------------------------------------------------


def test_shipped_tree_audits_clean_with_empty_inventory():
    report = audit_paths(default_hostmem_paths())
    assert report.ok, "\n".join(f.format() for f in report.findings)
    assert report.checked_files > 10
    # TOTAL: zero declared sites. A regression re-adding a hatch fails
    # twice — GH006 on the hatch line AND a non-empty inventory here.
    assert report.declared == []
    assert report.findings == []


def test_hostmem_cli_exit_codes(tmp_path):
    from spark_examples_tpu.check import cli

    assert cli.main(["hostmem"]) == 0
    # A nested package mirror so the scope globs (sources/*) resolve the
    # fixture exactly as they resolve the shipped tree.
    pkg = tmp_path / "pkg"
    dirty = pkg / "sources"
    dirty.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (dirty / "__init__.py").write_text("")
    (dirty / "bad.py").write_text(
        "def f(path):\n    g = open(path)\n    return g.read()\n"
    )
    assert cli.main(["hostmem", str(pkg)]) == 1
    assert cli.main(["hostmem", str(tmp_path / "missing")]) == 2


def test_hostmem_json_report_schema(capsys):
    from spark_examples_tpu.check import cli

    assert cli.main(["hostmem", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["tool"] == "graftcheck-hostmem"
    assert doc["ok"] is True
    assert doc["finding_count"] == 0
    # TOTAL: the declared-unbounded inventory is asserted EMPTY — this is
    # the machine-checked "zero declared sites" acceptance gate (ci.sh
    # re-asserts the same field against the shipped tree).
    assert doc["declared_unbounded"] == []


# --------------------------------------------------------------------------
# The closed-form budget formula and its configuration resolver.
# --------------------------------------------------------------------------


def test_host_peak_bytes_closed_form():
    # Term-by-term arithmetic, pinned: baseline + parse window
    # ((workers+2) * 2 * chunk) + prefetch (depth * B*N) + staging
    # (data * B*N) + flush copies ((1+depth) * staging).
    n, b = 64, 32
    got = host_peak_bytes(
        num_samples=n,
        block_size=b,
        data_axis=2,
        ingest_workers=4,
        chunk_bytes=1 << 20,
        prefetch_depth=2,
        pipeline_depth=2,
        baseline_bytes=0,
    )
    staging = 2 * b * n
    expected = (4 + 2) * 2 * (1 << 20) + 2 * b * n + staging + 3 * staging
    assert got == expected


def test_host_peak_bytes_monotone_and_baselined():
    base = host_peak_bytes(num_samples=64, block_size=32)
    assert base >= HOST_RUNTIME_BASELINE_BYTES
    assert host_peak_bytes(num_samples=128, block_size=32) > base
    assert host_peak_bytes(num_samples=64, block_size=64) > base
    assert (
        host_peak_bytes(num_samples=64, block_size=32, chunk_bytes=1 << 20)
        > base
    )
    host = host_peak_bytes(num_samples=64, block_size=32, host_accumulator=True)
    assert host == base + 2 * 64 * 64 * 8


def test_conf_resolver_is_total():
    # Every configuration shape that used to return None — in-memory/auto
    # file parse, wire ingest, JSONL/SAM, multi-set joins, checkpoint
    # resume, REST — now resolves to a finite positive bound.
    synthetic = PcaConf(num_samples=64, block_size=32)
    assert conf_host_peak_bytes(synthetic, device_count=1) > 0

    streamed = PcaConf(
        source="file",
        input_files=["cohort.vcf"],
        variant_set_id=["cohort"],
        stream_chunk_bytes=1 << 20,
        num_samples=64,
        block_size=32,
    )
    bound = conf_host_peak_bytes(streamed, device_count=1)
    assert bound > 0
    # The chunk term is in the bound: a bigger window raises it.
    streamed.stream_chunk_bytes = 8 << 20
    assert conf_host_peak_bytes(streamed, device_count=1) > bound

    for conf in (
        PcaConf(source="file", input_files=["c.vcf"], variant_set_id=["c"]),
        PcaConf(
            source="file",
            input_files=["c.vcf"],
            variant_set_id=["c"],
            stream_chunk_bytes=0,
        ),
        PcaConf(input_path="/tmp/ckpt"),
        PcaConf(
            source="file",
            input_files=["c.vcf"],
            variant_set_id=["c"],
            stream_chunk_bytes=1 << 20,
            ingest="wire",
        ),
        PcaConf(
            source="file",
            input_files=["c.jsonl"],
            variant_set_id=["c"],
            stream_chunk_bytes=1 << 20,
        ),
        PcaConf(
            source="file",
            input_files=["c.sam"],
            variant_set_id=["c"],
            stream_chunk_bytes=1 << 20,
        ),
        PcaConf(
            source="file",
            input_files=["a.vcf", "b.vcf"],
            variant_set_id=["a", "b"],
            stream_chunk_bytes=1 << 20,
        ),
        PcaConf(source="rest"),
    ):
        b = conf_host_peak_bytes(conf, device_count=1)
        assert isinstance(b, int) and b > 0
        # Monotone in the cohort width: growing N never shrinks the bound.
        import dataclasses

        wider = dataclasses.replace(conf, num_samples=conf.num_samples * 2)
        assert conf_host_peak_bytes(wider, device_count=1) >= b


def test_conf_resolver_wire_bound_tracks_bytes_on_disk(tmp_path):
    # A REAL (statable) wire input is bounded by its size on disk, not
    # the declared geometry ceiling: a small file proves a small bound.
    small = tmp_path / "c.jsonl"
    small.write_text('{"referenceName": "1"}\n' * 50)
    conf = PcaConf(
        source="file",
        input_files=[str(small)],
        variant_set_id=[small.name[:-6]],
        ingest="wire",
        num_samples=8,
        block_size=8,
    )
    bound = conf_host_peak_bytes(conf, device_count=1)
    assert bound > 0
    # Far under the geometry-ceiling bound of an unstatable path.
    ceiling_conf = PcaConf(
        source="file",
        input_files=["/nonexistent/c.jsonl"],
        variant_set_id=["c"],
        ingest="wire",
        num_samples=8,
        block_size=8,
    )
    assert bound < conf_host_peak_bytes(ceiling_conf, device_count=1)
    # And provable under a modest budget: the smoke ci.sh runs.
    assert bound < 8 << 30


# --------------------------------------------------------------------------
# graftcheck plan --host-mem-budget accept/reject matrix.
# --------------------------------------------------------------------------


def _plan(args, budget=None, devices=1):
    conf = PcaConf.parse(args)
    return validate_plan(conf, plan_devices=devices, host_mem_budget=budget)


def test_plan_reports_host_peak_fact_without_budget():
    report = _plan(["--num-samples", "64", "--references", "1:0:50000"])
    assert report.ok
    assert report.geometry["host_peak_bytes"] > HOST_RUNTIME_BASELINE_BYTES


def test_plan_accepts_within_budget():
    report = _plan(
        ["--num-samples", "64", "--references", "1:0:50000"],
        budget=8 << 30,
    )
    assert report.ok


def test_plan_rejects_over_budget():
    report = _plan(
        ["--num-samples", "64", "--references", "1:0:50000"],
        budget=1 << 20,
    )
    assert not report.ok
    assert any(i.code == "host-mem-over-budget" for i in report.issues)


def test_plan_every_path_gets_a_bound_fact():
    # The "host-mem-unprovable" rejection class is GONE: a file config
    # with no explicit streaming still proves a finite bound (from the
    # geometry ceiling when the path cannot be statted), recorded as a
    # geometry fact with no warning attached.
    report = _plan(
        [
            "--source", "file", "--input-files", "cohort.vcf",
            "--references", "1:0:50000",
        ]
    )
    assert report.ok
    assert not any(
        i.code in ("host-mem-unprovable", "host-mem-unbounded-path")
        for i in report.issues
    )
    assert report.geometry["host_peak_bytes"] > 0
    # Under a budget the only possible outcome is over-budget — the
    # unstatable path's geometry-ceiling bound exceeds 8 GiB honestly.
    report = _plan(
        [
            "--source", "file", "--input-files", "cohort.vcf",
            "--references", "1:0:50000",
        ],
        budget=8 << 30,
    )
    assert not report.ok
    assert any(i.code == "host-mem-over-budget" for i in report.issues)
    assert not any(i.code == "host-mem-unprovable" for i in report.issues)


def test_plan_streamed_file_config_is_provable():
    report = _plan(
        [
            "--source", "file", "--input-files", "cohort.vcf",
            "--num-samples", "64",
            "--references", "1:0:50000", "--stream-chunk-bytes", "1048576",
        ],
        budget=64 << 30,
    )
    assert report.ok
    assert report.geometry["host_peak_bytes"] > 0


def test_plan_proves_wire_jsonl_under_budget(tmp_path):
    # Previously the exit-2 "unprovable" class: a JSONL wire input under
    # --host-mem-budget. With the total resolver a REAL file proves a
    # tight bound from its bytes on disk and passes a modest budget.
    path = tmp_path / "cohort.jsonl"
    path.write_text('{"referenceName": "1"}\n' * 100)
    report = _plan(
        [
            "--source", "file", "--input-files", str(path),
            "--references", "1:0:50000", "--ingest", "wire",
        ],
        budget=8 << 30,
    )
    assert report.ok, [i.code for i in report.issues]
    assert report.geometry["host_peak_bytes"] <= 8 << 30


def test_plan_rejects_nonpositive_budget():
    report = _plan(
        ["--num-samples", "64", "--references", "1:0:50000"], budget=0
    )
    assert not report.ok
    assert any(i.code == "host-mem-budget" for i in report.issues)


def test_plan_budget_flag_via_cli():
    from spark_examples_tpu.check import cli

    args = ["plan", "--num-samples", "64", "--references", "1:0:50000"]
    assert cli.main(args + ["--host-mem-budget", str(8 << 30)]) == 0
    assert cli.main(args + ["--host-mem-budget", "1048576"]) == 2


# --------------------------------------------------------------------------
# Chunked checkpoint round trip: byte-identical artifacts, streaming read.
# --------------------------------------------------------------------------


def _checkpoint_records(n=300):
    from spark_examples_tpu.models.variant import VariantKey, VariantsBuilder

    records = []
    for i in range(n):
        wire = {
            "referenceName": "1",
            "variantSetId": "s",
            "id": f"v{i}",
            "start": 100 + i,
            "end": 101 + i,
            "referenceBases": "A",
            "alternateBases": ["T"],
            "info": {"AF": [f"0.{i % 9 + 1}"]},
            "calls": [
                {"callSetId": "s-0", "callSetName": "S0", "genotype": [0, 1]}
            ],
        }
        built = VariantsBuilder.build(wire)
        assert built is not None
        records.append((VariantKey("1", 100 + i), built[1]))
    return records


def test_checkpoint_chunked_round_trip_byte_identical(tmp_path):
    from spark_examples_tpu.pipeline import checkpoint as cp

    records = _checkpoint_records()
    path = tmp_path / "ckpt"
    total = cp.save_variants(str(path), [records[:150], records[150:]])
    assert total == len(records)

    # Decompressed artifact bytes == the per-record reference encoding
    # (the coalescing write buffer must not change a single byte).
    part_paths = sorted(p for p in os.listdir(path) if p.startswith("part-"))
    assert part_paths == ["part-00000.jsonl.gz", "part-00001.jsonl.gz"]
    for part, shard in zip(part_paths, [records[:150], records[150:]]):
        expected = "".join(
            json.dumps(
                {
                    "key": {"contig": k.contig, "position": k.position},
                    "variant": v.to_json(),
                }
            )
            + "\n"
            for k, v in shard
        )
        with gzip.open(path / part, "rt") as f:
            assert f.read() == expected

    # Streaming reader (fixed-size window + carry) round-trips exactly,
    # through both the part-list API and whole-checkpoint iteration.
    loaded = cp.load_variants(str(path))
    streamed = list(loaded)
    assert [k for k, _ in streamed] == [k for k, _ in records]
    assert [v.to_json() for _, v in streamed] == [
        v.to_json() for _, v in records
    ]
    first_part = loaded.partitions()[0]
    assert [k for k, _ in loaded.compute(first_part)] == [
        k for k, _ in records[:150]
    ]


def test_checkpoint_reader_window_smaller_than_line(tmp_path):
    from spark_examples_tpu.pipeline.checkpoint import _iter_jsonl_lines

    path = tmp_path / "tiny.jsonl.gz"
    rows = [{"i": i, "pad": "x" * 500} for i in range(20)]
    with gzip.open(path, "wt") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    # A window far below one encoded line exercises the carry path.
    assert list(_iter_jsonl_lines(str(path), chunk_bytes=64)) == rows


# --------------------------------------------------------------------------
# Manifest schema v2: the host_memory block.
# --------------------------------------------------------------------------


def test_manifest_v2_host_memory_block_and_validation():
    from spark_examples_tpu.obs.manifest import (
        MANIFEST_VERSION,
        build_manifest,
        validate_manifest,
    )

    assert MANIFEST_VERSION == 2
    doc = build_manifest()
    assert validate_manifest(doc) == []
    assert doc["host_memory"]["peak_rss_bytes"] > 0
    # ALWAYS a real bound: outside a driver run the block carries the
    # runtime-baseline bound, never null — and the validator REQUIRES a
    # positive int (a "no bound" manifest is a schema error now).
    from spark_examples_tpu.parallel.mesh import HOST_RUNTIME_BASELINE_BYTES

    assert doc["host_memory"]["static_bound_bytes"] >= (
        HOST_RUNTIME_BASELINE_BYTES
    )

    bad = build_manifest()
    del bad["host_memory"]
    assert any("host_memory" in e for e in validate_manifest(bad))
    bad = build_manifest()
    bad["host_memory"] = {"peak_rss_bytes": -1, "static_bound_bytes": True}
    errors = validate_manifest(bad)
    assert any("peak_rss_bytes" in e for e in errors)
    assert any("static_bound_bytes" in e for e in errors)
    bad = build_manifest()
    bad["host_memory"]["static_bound_bytes"] = None
    assert any("static_bound_bytes" in e for e in validate_manifest(bad))


def test_driver_registers_host_memory_pair():
    from spark_examples_tpu.obs.manifest import build_run_manifest
    from spark_examples_tpu.obs.metrics import (
        HOST_PEAK_RSS_BYTES,
        HOST_STATIC_BOUND_BYTES,
    )
    from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver

    conf = PcaConf(num_samples=8, block_size=8)
    driver = VariantsPcaDriver(conf)
    peak = driver.registry.value(HOST_PEAK_RSS_BYTES)
    bound = driver.registry.value(HOST_STATIC_BOUND_BYTES)
    assert peak and peak > 0
    assert bound and bound >= HOST_RUNTIME_BASELINE_BYTES
    doc = build_run_manifest(conf=conf, registry=driver.registry)
    assert doc["host_memory"]["peak_rss_bytes"] > 0
    assert doc["host_memory"]["static_bound_bytes"] == int(bound)


# --------------------------------------------------------------------------
# The e2e parity proof: measured peak RSS <= host_peak_bytes(config) on a
# real streamed run, recorded in the run manifest — the formula is proven
# against reality, the way GI005 proves ring_traffic_bytes.
# --------------------------------------------------------------------------


def _write_sorted_vcf(path, n_sites=400, n_samples=8):
    names = "\t".join(f"S{i}" for i in range(n_samples))
    with open(path, "w") as f:
        f.write("##fileformat=VCFv4.2\n")
        f.write(
            "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\t"
            + names
            + "\n"
        )
        for i in range(n_sites):
            gts = "\t".join(
                "0|1" if (i + j) % 3 == 0 else "0|0" for j in range(n_samples)
            )
            f.write(
                f"1\t{1000 + i * 10}\tv{i}\tA\tT\t.\tPASS\t"
                f"AF=0.{i % 9 + 1}\tGT\t{gts}\n"
            )


def test_e2e_streamed_peak_rss_within_static_bound(tmp_path):
    """Subprocess (fresh RSS high-water mark) streamed-file PCA run: the
    manifest must record measured peak <= the static bound, and the bound
    must be the same number ``conf_host_peak_bytes`` computes."""
    vcf = tmp_path / "cohort.vcf"
    _write_sorted_vcf(str(vcf))
    manifest_path = tmp_path / "manifest.json"
    env = dict(os.environ)
    env.update(
        {
            # Without it this subprocess grabs an accelerator backend,
            # whose runtime maps gigabytes of host RSS into the measurement.
            "JAX_PLATFORMS": "cpu",
            "SPARK_EXAMPLES_TPU_NO_CACHE": "1",
        }
    )
    chunk = 4096
    proc = subprocess.run(
        [
            sys.executable, "-m", "spark_examples_tpu", "variants-pca",
            "--source", "file", "--input-files", str(vcf),
            "--all-references", "--stream-chunk-bytes", str(chunk),
            "--ingest-workers", "2", "--block-size", "64",
            "--mesh-shape", "1,1",  # pin the data axis: the parity
            # assertion below must not depend on the host's device count
            "--metrics-json", str(manifest_path),
        ],
        capture_output=True,
        text=True,
        env=env,
        cwd=_REPO_ROOT,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(manifest_path.read_text())
    from spark_examples_tpu.obs.manifest import validate_manifest

    assert validate_manifest(doc) == []
    hm = doc["host_memory"]
    assert hm["peak_rss_bytes"] and hm["peak_rss_bytes"] > 0
    assert hm["static_bound_bytes"] and hm["static_bound_bytes"] > 0
    assert hm["peak_rss_bytes"] <= hm["static_bound_bytes"], (
        "measured peak RSS exceeds the static host-memory bound: "
        f"{hm['peak_rss_bytes']} > {hm['static_bound_bytes']}"
    )
    conf = PcaConf(
        source="file",
        input_files=[str(vcf)],
        variant_set_id=["cohort"],
        stream_chunk_bytes=chunk,
        ingest_workers=2,
        block_size=64,
        mesh_shape="1,1",
    )
    # The driver resolves the bound against the DISCOVERED cohort (8
    # samples from the header), not the flag default.
    expected = conf_host_peak_bytes(conf, device_count=1, num_samples=8)
    assert hm["static_bound_bytes"] == expected
