"""Bounded-memory streaming VCF ingest (``sources/files.py:_StreamedVcf``).

The reference's paging architecture streamed arbitrarily large datasets one
page per executor (``rdd/VariantsRDD.scala:198-225``); the streamed packed
path restates that for the TPU ingest: one pass over the file in fixed-size
decompressed chunks, peak host memory O(chunk), results identical to the
in-memory parser.
"""

import gzip
import os
import tempfile
import tracemalloc

import numpy as np
import pytest
from helpers import assert_pcs_match

# hypothesis is declared only under the `test` extra; every handwritten test
# here must still collect and run on the bare seed image, so only the fuzz
# test (defined conditionally below) depends on it.
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False
if HAVE_HYPOTHESIS:
    from test_files_fuzz import _vcf_documents

from spark_examples_tpu.pipeline import pca_driver
from spark_examples_tpu.sharding.contig import Contig
from spark_examples_tpu.sources.files import (
    FileGenomicsSource,
    StreamCounters,
    _iter_vcf_chunks,
)


def _make_vcf(
    tmp_path,
    name="big.vcf",
    n_samples=7,
    rows_per_contig=120,
    contigs=("1", "17", "GL000229.1"),
    spacing=37,
    compress=False,
    shuffle_contig=None,
):
    """A deterministic multi-contig VCF with AF-carrying and AF-less rows,
    multi-allele genotypes, and missing calls — coordinate-sorted unless
    ``shuffle_contig`` swaps two rows of that contig."""
    rng = np.random.default_rng(123)
    header = ["##fileformat=VCFv4.2"]
    cols = "\t".join(f"S{i:03d}" for i in range(n_samples))
    header.append(
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\t" + cols
    )
    lines = []
    for contig in contigs:
        contig_lines = []
        for k in range(rows_per_contig):
            pos = 101 + k * spacing
            af = rng.random()
            info = f"AF={af:.4f}" if k % 5 else "NS=3"
            gts = []
            for _ in range(n_samples):
                draw = rng.random()
                if draw < 0.1:
                    gts.append("./.")
                elif draw < 0.5:
                    gts.append("0|0")
                elif draw < 0.8:
                    gts.append("0|1")
                else:
                    gts.append("1|2")
            contig_lines.append(
                f"{contig}\t{pos}\trs{contig}_{k}\tAC\tG,T\t50\tPASS\t"
                f"{info}\tGT\t" + "\t".join(gts)
            )
        if shuffle_contig == contig and len(contig_lines) > 3:
            contig_lines[1], contig_lines[3] = contig_lines[3], contig_lines[1]
        lines.extend(contig_lines)
    text = "\n".join(header + lines) + "\n"
    path = tmp_path / (name + (".gz" if compress else ""))
    if compress:
        with gzip.open(path, "wt") as f:
            f.write(text)
    else:
        path.write_text(text)
    return str(path)


def _blocks_concat(blocks):
    blocks = list(blocks)
    if not blocks:
        return (
            np.empty(0, np.int64),
            np.zeros((0, 0), np.uint8),
            np.empty(0, np.float64),
        )
    return (
        np.concatenate([b["positions"] for b in blocks]),
        np.concatenate([b["has_variation"] for b in blocks]),
        np.concatenate([b["af"] for b in blocks]),
    )


def test_chunk_iterator_reassembles_exactly(tmp_path):
    path = _make_vcf(tmp_path, rows_per_contig=40)
    raw = open(path, "rb").read()
    chunks = list(_iter_vcf_chunks(path, 1))  # clamps to the 64-byte floor
    assert len(chunks) > 1
    assert b"".join(chunks) == raw
    for chunk in chunks[:-1]:
        assert chunk.endswith(b"\n")


@pytest.mark.parametrize("compress", [False, True])
def test_streamed_blocks_match_in_memory(tmp_path, compress):
    """The streamed pass and the in-memory packed view produce identical
    rows for every window — gz and plain, AF filter on and off."""
    path = _make_vcf(tmp_path, compress=compress)
    plain = FileGenomicsSource([path], stream_chunk_bytes=0)
    streamed = FileGenomicsSource([path], stream_chunk_bytes=1)  # force
    set_id = plain.set_ids[0]
    assert not plain.wants_streaming(set_id)
    assert streamed.wants_streaming(set_id)

    windows = [
        Contig("17", 0, 10_000),
        Contig("17", 2_000, 3_000),
        Contig("1", 101, 102),
        Contig("GL000229.1", 0, 1 << 40),
        Contig("absent", 0, 1000),
    ]
    for min_af in (None, 0.3):
        for window in windows:
            want = _blocks_concat(
                plain.genotype_blocks(
                    set_id, window, block_size=16, min_allele_frequency=min_af
                )
            )
            got = _blocks_concat(
                streamed.genotype_blocks(
                    set_id, window, block_size=16, min_allele_frequency=min_af
                )
            )
            for w, g in zip(want, got):
                np.testing.assert_array_equal(w, g)


def test_streamed_python_fallback_matches_native(tmp_path):
    """Without the native library the streamed chunks parse through the
    shared wire-parser semantics — identical blocks."""
    from spark_examples_tpu.utils import native as native_mod

    if native_mod.vcf_library() is None:
        pytest.skip("no native build")
    path = _make_vcf(tmp_path)
    window = Contig("17", 0, 1 << 40)

    native_src = FileGenomicsSource([path], stream_chunk_bytes=1)
    want = _blocks_concat(
        native_src.genotype_blocks(native_src.set_ids[0], window)
    )
    original = native_mod.vcf_library
    try:
        native_mod.vcf_library = lambda: None
        py_src = FileGenomicsSource([path], stream_chunk_bytes=1)
        got = _blocks_concat(
            py_src.genotype_blocks(py_src.set_ids[0], window)
        )
    finally:
        native_mod.vcf_library = original
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


def test_stream_counters_match_random_access_accounting(tmp_path):
    """One-pass per-shard page/variant accounting == the random-access
    path's ``page_requests`` + kept-row counts."""
    path = _make_vcf(tmp_path)
    plain = FileGenomicsSource([path], stream_chunk_bytes=0)
    streamed = FileGenomicsSource([path], stream_chunk_bytes=1)
    set_id = plain.set_ids[0]
    bpp = 1500
    window = Contig("17", 0, 4600)
    shards = window.get_shards(bpp)

    counters = StreamCounters(len(shards), page_size=2)
    blocks = list(
        streamed.stream_genotype_blocks(
            set_id, shards, block_size=16, counters=counters
        )
    )
    want_requests = 0
    for shard in shards:
        rows = len(plain.packed(set_id).window(shard)[0])
        want_requests += max(1, -(-rows // 2))
    assert counters.requests() == want_requests
    want_variants = sum(
        len(b["positions"])
        for shard in shards
        for b in plain.genotype_blocks(set_id, shard, block_size=16)
    )
    assert counters.variants == want_variants == sum(
        len(b["positions"]) for b in blocks
    )


def test_lazy_contig_discovery_streams_no_table(tmp_path):
    """--all-references discovery on a streamed VCF: bounds from the
    site-only pass, identical to the packed view's, with neither the wire
    table nor the packed arrays ever materialized."""
    path = _make_vcf(tmp_path)
    streamed = FileGenomicsSource([path], stream_chunk_bytes=1)
    set_id = streamed.set_ids[0]
    got = streamed.get_contigs(set_id)
    assert streamed._tables == {} and streamed._packed == {}

    plain = FileGenomicsSource([path], stream_chunk_bytes=0)
    want = plain.get_contigs(set_id)
    assert [(c.reference_name, c.start, c.end) for c in got] == [
        (c.reference_name, c.start, c.end) for c in want
    ]


def test_header_only_callsets(tmp_path):
    path = _make_vcf(tmp_path, n_samples=4)
    source = FileGenomicsSource([path], stream_chunk_bytes=1)
    callsets = source.search_callsets(source.set_ids)
    assert [c["name"] for c in callsets] == ["S000", "S001", "S002", "S003"]
    assert source._tables == {}  # no wire parse happened


def test_gz_auto_threshold_accounts_for_compression(tmp_path, monkeypatch):
    """The auto-streaming threshold is defined in DECOMPRESSED bytes: a
    compressed .gz whose on-disk size is below the raw threshold but whose
    expansion clearly is not must stream (the standard compressed 1000
    Genomes distribution), while the same on-disk size uncompressed need
    not."""
    gz = _make_vcf(tmp_path, name="a.vcf", compress=True)
    plain = _make_vcf(tmp_path, name="b.vcf", compress=False)
    source = FileGenomicsSource([gz, plain])  # auto mode
    fake = 20 << 20  # 20 MB on disk: > 128 MB decompressed only if .gz
    monkeypatch.setattr(
        "spark_examples_tpu.sources.files.os.path.getsize", lambda p: fake
    )
    assert source.wants_streaming(source.set_ids[0])  # .gz → ~200 MB text
    assert not source.wants_streaming(source.set_ids[1])


def test_headerless_vcf_keeps_working(tmp_path):
    """A VCF with no #CHROM row (sites-only) still runs: header-only
    callset discovery yields the empty cohort exactly like the wire parser,
    instead of rejecting a file the data parse accepts."""
    vcf = "17\t101\t.\tA\tG\t50\tPASS\tAF=0.5\n17\t205\t.\tT\tC\t50\tPASS\tAF=0.3\n"
    path = tmp_path / "headerless.vcf"
    path.write_text(vcf)
    for chunk_bytes in (0, 1):  # in-memory and streamed
        source = FileGenomicsSource([str(path)], stream_chunk_bytes=chunk_bytes)
        assert source.search_callsets(source.set_ids) == []
        contigs = source.get_contigs(source.set_ids[0])
        # POS 205 (1-based) → start 204, end = 204 + len("T") = 205.
        assert [(c.reference_name, c.end) for c in contigs] == [("17", 205)]


def test_native_site_scan_rejects_short_lines_like_python(tmp_path):
    """vcf_scan_sites must reject <8-field data lines exactly like the
    Python fallback — contig discovery must not be environment-dependent."""
    from spark_examples_tpu.utils import native as native_mod

    if native_mod.vcf_library() is None:
        pytest.skip("no native build")
    short = b"17\t101\t.\tA\tG\n"
    with pytest.raises(ValueError, match="data line #1"):
        native_mod.scan_vcf_sites_chunk(short)


def test_unsorted_vcf_fails_loudly_in_streaming_mode(tmp_path):
    path = _make_vcf(tmp_path, shuffle_contig="17")
    streamed = FileGenomicsSource([path], stream_chunk_bytes=1)
    set_id = streamed.set_ids[0]
    with pytest.raises(ValueError, match="coordinate-sorted"):
        list(
            streamed.genotype_blocks(set_id, Contig("17", 0, 1 << 40))
        )
    # The in-memory path has no ordering requirement.
    plain = FileGenomicsSource([path], stream_chunk_bytes=0)
    assert list(plain.genotype_blocks(set_id, Contig("17", 0, 1 << 40)))


def test_noncontiguous_contig_fails_loudly(tmp_path):
    text = (
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS1\n"
        "1\t101\t.\tA\tG\t1\t.\tAF=0.5\tGT\t0|1\n"
        "2\t101\t.\tA\tG\t1\t.\tAF=0.5\tGT\t0|1\n"
        "1\t201\t.\tA\tG\t1\t.\tAF=0.5\tGT\t0|1\n"
    )
    path = tmp_path / "split.vcf"
    path.write_text(text)
    source = FileGenomicsSource([str(path)], stream_chunk_bytes=1)
    with pytest.raises(ValueError, match="not contiguous"):
        list(
            source.genotype_blocks(
                source.set_ids[0], Contig("1", 0, 1 << 40)
            )
        )


def _coordinate_sort(document: str) -> str:
    """A streaming-legal equivalent of a fuzzed VCF document: contigs made
    contiguous (first-seen order), positions sorted stably within each —
    exactly the layout `bcftools sort` would emit."""
    eol = "\r\n" if "\r\n" in document else "\n"
    lines = [l for l in document.split(eol) if l]
    head = [l for l in lines if l.startswith("#")]
    groups: dict = {}
    for line in lines:
        if line.startswith("#"):
            continue
        groups.setdefault(line.split("\t")[0], []).append(line)
    for group in groups.values():
        group.sort(key=lambda l: int(l.split("\t")[1]))
    data = [line for name in groups for line in groups[name]]
    return eol.join(head + data) + eol


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        document=_vcf_documents(),
        chunk=st.integers(min_value=64, max_value=512),
        min_af=st.sampled_from([None, 0.05]),
    )
    def test_fuzz_streamed_matches_in_memory(document, chunk, min_af):
        """Property: for ANY (sorted) fuzzed VCF document and ANY chunk size
        — including chunks smaller than one line — the streamed pass produces
        the same blocks and the same contig bounds as the in-memory parser.
        This is the chunk-boundary/carry torture test."""
        doc = _coordinate_sort(document)
        fd, path = tempfile.mkstemp(suffix=".vcf")
        try:
            with os.fdopen(fd, "w", newline="") as f:
                f.write(doc)
            plain = FileGenomicsSource([path], stream_chunk_bytes=0)
            streamed = FileGenomicsSource([path], stream_chunk_bytes=chunk)
            set_id = plain.set_ids[0]
            plain_contigs = plain.get_contigs(set_id)
            streamed_contigs = streamed.get_contigs(set_id)
            assert [
                (c.reference_name, c.start, c.end) for c in streamed_contigs
            ] == [(c.reference_name, c.start, c.end) for c in plain_contigs]
            for c in plain_contigs:
                window = Contig(c.reference_name, 0, 1 << 40)
                want = _blocks_concat(
                    plain.genotype_blocks(
                        set_id, window, block_size=4, min_allele_frequency=min_af
                    )
                )
                got = _blocks_concat(
                    streamed.genotype_blocks(
                        set_id, window, block_size=4, min_allele_frequency=min_af
                    )
                )
                for w, g in zip(want, got):
                    np.testing.assert_array_equal(w, g)
        finally:
            os.unlink(path)

else:

    @pytest.mark.skip(reason="hypothesis not installed (test extra)")
    def test_fuzz_streamed_matches_in_memory():
        pass


def test_cli_streamed_run_matches_in_memory(tmp_path, capsys):
    """variants-pca end to end: the streamed run (auto-selected packed via
    --stream-chunk-bytes) prints byte-identical output — PCs AND I/O stats —
    to the in-memory packed run and the wire run."""
    path = _make_vcf(tmp_path, n_samples=5, rows_per_contig=80)
    base = [
        "--source", "file", "--input-files", path,
        "--references", "17:0:2500",
        "--min-allele-frequency", "0.1",
        "--block-size", "32",
    ]

    def run(extra):
        lines = pca_driver.run(base + extra)
        return lines, capsys.readouterr().out

    streamed_lines, streamed_out = run(["--stream-chunk-bytes", "1"])
    packed_lines, packed_out = run(
        ["--ingest", "packed", "--stream-chunk-bytes", "0"]
    )
    wire_lines, _ = run(["--ingest", "wire", "--stream-chunk-bytes", "0"])
    assert streamed_lines == packed_lines == wire_lines
    assert streamed_out == packed_out


def test_cli_streamed_sharded_strategy_matches_wire(tmp_path, capsys):
    """Streamed file ingest composed with the SHARDED similarity strategy:
    the streamed blocks feed the row-tile-sharded Gramian + sharded
    centering/eigensolve and the principal components match the wire run."""
    path = _make_vcf(tmp_path, n_samples=6, rows_per_contig=90)
    base = [
        "--source", "file", "--input-files", path,
        "--references", "17:0:3000",
        "--block-size", "32",
    ]
    wire = pca_driver.run(base + ["--ingest", "wire", "--stream-chunk-bytes", "0"])
    capsys.readouterr()
    streamed_sharded = pca_driver.run(
        base
        + [
            "--stream-chunk-bytes", "1",
            "--similarity-strategy", "sharded",
            "--mesh-shape", "1,8",
        ]
    )
    capsys.readouterr()
    assert_pcs_match(wire, streamed_sharded)


def test_streamed_ingest_memory_is_bounded_by_chunk(tmp_path):
    """The capability claim, measured: peak traced host allocations during a
    full streamed ingest stay a small multiple of the chunk size — far under
    the file size — while the in-memory parse necessarily holds O(file).
    (tracemalloc sees every chunk buffer and numpy array; the enforced-cap
    equivalent of an rlimit without its JAX address-space fragility.)"""
    path = _make_vcf(
        tmp_path, n_samples=40, rows_per_contig=6000, contigs=("1", "2")
    )
    file_bytes = int(np.int64(__import__("os").path.getsize(path)))
    assert file_bytes > 2_000_000  # the claim is vacuous on a tiny file
    chunk = 1 << 16
    source = FileGenomicsSource([path], stream_chunk_bytes=chunk)
    set_id = source.set_ids[0]
    shards = [Contig("1", 0, 1 << 40), Contig("2", 0, 1 << 40)]

    tracemalloc.start()
    tracemalloc.reset_peak()
    rows = 0
    for block in source.stream_genotype_blocks(set_id, shards, block_size=64):
        rows += len(block["positions"])
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert rows > 0
    # Generous bound: a handful of chunk-sized buffers plus parsed arrays
    # for one chunk. The whole-file path would need >= file_bytes.
    assert peak < 16 * chunk + (1 << 20), (
        f"streamed ingest peak {peak} bytes vs chunk {chunk} "
        f"(file is {file_bytes} bytes)"
    )
    assert peak < file_bytes // 2


def test_cli_streamed_run_memory_is_bounded(tmp_path):
    """The VERDICT-r4 'Done' criterion, literally: a VCF ingested THROUGH
    ``variants-pca --source file`` with streaming on keeps peak traced host
    memory far below the file size (tracemalloc sees every chunk buffer and
    parse array; device buffers are O(N²), not O(file)). The wire path on
    the same file allocates a multiple of the file size in Python records —
    asserted as the contrast so the bound stays meaningful."""
    path = _make_vcf(
        tmp_path, n_samples=30, rows_per_contig=4000, contigs=("1", "2")
    )
    file_bytes = os.path.getsize(path)
    assert file_bytes > 1_000_000
    chunk = 1 << 16
    argv = [
        "--source", "file", "--input-files", path,
        "--all-references",
        "--block-size", "64",
    ]

    # The parse pool keeps up to workers + 2 chunks in flight, and the
    # default worker count follows the host's cores (up to 8: ten 64 KiB
    # chunks, half this file). Pin the count so the bound measures the
    # streaming window, not the host.
    streamed_argv = argv + [
        "--stream-chunk-bytes", str(chunk), "--ingest-workers", "2",
    ]
    # Warm pass: jit tracing allocates ~20 MB of one-time Python objects
    # that tracemalloc would otherwise attribute to the measured run; the
    # second identical run reuses the compiled programs, so its peak is the
    # parse memory this test is about.
    pca_driver.run(streamed_argv)
    tracemalloc.start()
    tracemalloc.reset_peak()
    streamed_lines = pca_driver.run(streamed_argv)
    _, streamed_peak = tracemalloc.get_traced_memory()
    tracemalloc.reset_peak()
    wire_lines = pca_driver.run(
        argv + ["--stream-chunk-bytes", "0", "--ingest", "wire"]
    )
    _, wire_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    assert streamed_lines == wire_lines
    assert streamed_peak < file_bytes // 2, (
        f"streamed CLI peak {streamed_peak} vs file {file_bytes}"
    )
    assert wire_peak > file_bytes  # the bound distinguishes the two paths
