"""End-to-end flagship pipeline: TPU backend vs. the literal host replication
of the reference algorithm, multi-dataset join/merge, checkpoint resume,
emit formats."""

import os

import numpy as np
import pytest
from helpers import assert_pcs_match

from spark_examples_tpu.config import PcaConf
from spark_examples_tpu.pipeline import pca_driver
from spark_examples_tpu.pipeline.checkpoint import load_variants, save_variants
from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver, extract_call_info
from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource


def _conf(**kw):
    base = dict(
        references="17:0:20000",
        variant_set_id=["vs-a"],
        num_samples=30,
        seed=7,
        bases_per_partition=5000,
        block_size=64,
    )
    base.update(kw)
    conf = PcaConf()
    for k, v in base.items():
        setattr(conf, k, v)
    return conf


def _source(conf):
    return SyntheticGenomicsSource(num_samples=conf.num_samples, seed=conf.seed)


def test_extract_call_info_semantics(small_source):
    conf = _conf(num_samples=40)
    driver = VariantsPcaDriver(conf, small_source)
    data = driver.get_data()
    variant = next(data[0].variants())
    calls = extract_call_info(variant, driver.indexes)
    assert len(calls) == 40
    for call, model_call in zip(calls, variant.calls):
        assert call.has_variation == any(g > 0 for g in model_call.genotype)
        assert call.callset_id == driver.indexes[model_call.callset_id]


def test_similarity_tpu_matches_host_reference():
    conf = _conf()
    driver = VariantsPcaDriver(conf, _source(conf))
    calls = list(driver.iter_calls(driver.get_data()))
    assert calls
    tpu = driver.get_similarity_matrix(calls)

    conf_host = _conf(pca_backend="host")
    driver_host = VariantsPcaDriver(conf_host, _source(conf_host))
    host = driver_host.get_similarity_matrix(iter(calls))
    np.testing.assert_array_equal(tpu, host)
    # Diagonal counts = per-sample variant counts.
    assert (np.diag(host) > 0).any()


def test_pca_tpu_matches_host_reference():
    conf = _conf(references="17:0:40000")
    driver = VariantsPcaDriver(conf, _source(conf))
    calls = list(driver.iter_calls(driver.get_data()))
    S = driver.get_similarity_matrix(calls)
    ours = driver.compute_pca(S)

    conf_host = _conf(references="17:0:40000", pca_backend="host")
    driver_host = VariantsPcaDriver(conf_host, _source(conf_host))
    theirs = driver_host.compute_pca(S)

    A = np.array([pcs for _, pcs in ours])
    B = np.array([pcs for _, pcs in theirs])
    # Align arbitrary eigenvector signs, then compare.
    signs = np.sign((A * B).sum(axis=0))
    signs[signs == 0] = 1
    np.testing.assert_allclose(A, B * signs, atol=5e-3)
    assert [cid for cid, _ in ours] == [cid for cid, _ in theirs]


def test_pca_separates_populations():
    conf = _conf(references="17:0:100000", num_samples=24)
    source = SyntheticGenomicsSource(num_samples=24, seed=3, n_pops=2)
    driver = VariantsPcaDriver(conf, source)
    calls = list(driver.iter_calls(driver.get_data()))
    S = driver.get_similarity_matrix(calls)
    result = driver.compute_pca(S)
    pc1 = np.array([pcs[0] for _, pcs in result])
    pops = np.asarray(source._pops)
    # PC1 separates the two synthetic populations almost perfectly.
    means = [pc1[pops == p].mean() for p in (0, 1)]
    spread = max(pc1[pops == p].std() for p in (0, 1))
    assert abs(means[0] - means[1]) > 3 * spread


def test_min_allele_frequency_filters():
    conf = _conf(min_allele_frequency=0.2)
    driver = VariantsPcaDriver(conf, _source(conf))
    filtered = list(driver.iter_calls(driver.get_data()))
    conf2 = _conf()
    driver2 = VariantsPcaDriver(conf2, _source(conf2))
    unfiltered = list(driver2.iter_calls(driver2.get_data()))
    assert 0 < len(filtered) < len(unfiltered)


def test_two_dataset_join_doubles_matrix():
    conf = _conf(variant_set_id=["vs-a", "vs-b"])
    driver = VariantsPcaDriver(conf, _source(conf))
    assert len(driver.indexes) == 60  # 30 + 30 columns
    calls = list(driver.iter_calls(driver.get_data()))
    assert calls
    # Joined rows may contain indices from both datasets.
    flat = {i for row in calls for i in row}
    assert min(flat) < 30 <= max(flat)
    S = driver.get_similarity_matrix(calls)
    assert S.shape == (60, 60)
    # Cross-dataset co-occurrence exists (shared sites).
    assert S[:30, 30:].sum() > 0


def test_three_dataset_merge_intersects():
    conf = _conf(variant_set_id=["vs-a", "vs-b", "vs-c"], references="17:0:10000")
    driver = VariantsPcaDriver(conf, _source(conf))
    calls = list(driver.iter_calls(driver.get_data()))
    assert calls
    assert len(driver.indexes) == 90
    flat = {i for row in calls for i in row}
    assert max(flat) >= 60  # third dataset contributes


def test_merge_equals_join_on_shared_sites():
    """For synthetic data every site exists in every dataset exactly once, so
    2-dataset join and 3-dataset merge (restricted to two sets) agree."""
    conf2 = _conf(variant_set_id=["vs-a", "vs-b"], references="17:0:8000")
    d2 = VariantsPcaDriver(conf2, _source(conf2))
    joined = sorted(tuple(sorted(r)) for r in d2.iter_calls(d2.get_data()))

    # Force the merge path with the same two datasets by monkey-patching the
    # dataset count check is not possible; instead verify merge on 3 sets
    # restricted to the first two datasets' columns matches the join rows.
    conf3 = _conf(variant_set_id=["vs-a", "vs-b", "vs-c"], references="17:0:8000")
    d3 = VariantsPcaDriver(conf3, _source(conf3))
    merged = [
        tuple(sorted(i for i in row if i < 60))
        for row in d3.iter_calls(d3.get_data())
    ]
    merged = sorted(t for t in merged if t)
    assert merged == [t for t in joined if t]


def test_checkpoint_round_trip(tmp_path):
    conf = _conf()
    driver = VariantsPcaDriver(conf, _source(conf))
    data = driver.get_data()
    shards = [records for _, records in data[0].iter_shards()]
    path = str(tmp_path / "variants-ckpt")
    n = save_variants(path, shards)
    assert n == sum(len(s) for s in shards)

    loaded = load_variants(path)
    original = [kv for shard in shards for kv in shard]
    assert list(loaded) == original

    # Driver resume path: --input-path replaces the API read
    # (VariantsPca.scala:112-113) and disables stats (:332-335).
    conf2 = _conf(input_path=path)
    driver2 = VariantsPcaDriver(conf2, _source(conf2))
    assert driver2.io_stats is None
    calls_resumed = list(driver2.iter_calls(driver2.get_data()))
    calls_fresh = list(driver.iter_calls(data))
    assert calls_resumed == calls_fresh


def test_cli_save_variants_round_trip(tmp_path, capsys):
    """--save-variants end to end: ingest → save while streaming → resume
    via --input-path produces identical principal components, with no
    Python in between (the writer the reference's objectFile resume never
    had, VariantsPca.scala:112-113)."""
    ckpt = str(tmp_path / "saved-variants")
    base = [
        "--references", "17:0:30000",
        "--variant-set-id", "vs",
        "--num-samples", "12",
        "--seed", "5",
        "--block-size", "32",
        "--min-allele-frequency", "0.05",
    ]
    saved_lines = pca_driver.run(base + ["--save-variants", ckpt])
    out = capsys.readouterr().out
    assert "Saved " in out and ckpt in out
    # The checkpoint holds UNFILTERED records (filters re-apply on resume):
    # more records than AF-kept rows.
    total = sum(1 for _ in load_variants(ckpt))
    assert total > 0

    resumed_lines = pca_driver.run(base + ["--input-path", ckpt])
    capsys.readouterr()
    assert resumed_lines == saved_lines

    # A different threshold still works against the saved (unfiltered) data.
    loose = pca_driver.run(
        [a for a in base if a not in ("--min-allele-frequency", "0.05")]
        + ["--input-path", ckpt]
    )
    capsys.readouterr()
    fresh_loose = pca_driver.run(
        [a for a in base if a not in ("--min-allele-frequency", "0.05")]
    )
    capsys.readouterr()
    assert loose == fresh_loose


def test_save_variants_refuses_streaming_scale_file(tmp_path):
    """A VCF the auto logic would STREAM must not silently revert to the
    O(file) wire parse because --save-variants was added."""
    vcf = (
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS1\n"
        "17\t101\t.\tA\tG\t1\t.\tAF=0.5\tGT\t0|1\n"
    )
    path = tmp_path / "tiny.vcf"
    path.write_text(vcf)
    with pytest.raises(ValueError, match="streaming-scale"):
        pca_driver.run(
            [
                "--source", "file", "--input-files", str(path),
                "--stream-chunk-bytes", "1",  # force streaming eligibility
                "--save-variants", str(tmp_path / "ckpt"),
                "--references", "17:0:1000",
            ]
        )


def test_save_variants_flag_guards():
    for argv, message in [
        (["--save-variants", "/tmp/x", "--ingest", "device"], "wire"),
        (["--save-variants", "/tmp/x", "--input-path", "/tmp/y"], "re-save"),
        (
            [
                "--save-variants", "/tmp/x",
                "--variant-set-id", "vs-a,vs-b",
            ],
            "single variant set",
        ),
    ]:
        with pytest.raises(ValueError, match=message):
            pca_driver.run(argv)


def test_emit_result_formats(tmp_path, capsys):
    conf = _conf(output_path=str(tmp_path / "out"))
    driver = VariantsPcaDriver(conf, _source(conf))
    result = [
        (driver_id, [0.125, -0.5])
        for driver_id in list(driver.indexes)[:3]
    ]
    lines = driver.emit_result(result)
    # Console: name<TAB>dataset<TAB>pc1<TAB>pc2, sorted by name.
    names = [l.split("\t")[0] for l in lines]
    assert names == sorted(names)
    assert all(l.split("\t")[1] == "vs" for l in lines)
    # Saved: name, pcs..., dataset (the reference's saved column order).
    saved = open(str(tmp_path / "out-pca.tsv" / "part-00000")).read().splitlines()
    assert len(saved) == 3
    assert saved[0].split("\t")[-1] == "vs"


def test_full_run_entrypoint(tmp_path, capsys):
    lines = pca_driver.run(
        [
            "--references", "17:0:20000",
            "--variant-set-id", "vs-a",
            "--num-samples", "12",
            "--seed", "5",
            "--bases-per-partition", "5000",
            "--block-size", "32",
            "--output-path", str(tmp_path / "run"),
        ]
    )
    assert len(lines) == 12
    captured = capsys.readouterr().out
    assert "Matrix size: 12." in captured
    assert "Non zero rows in matrix:" in captured
    assert "Variants API stats:" in captured
    assert os.path.exists(str(tmp_path / "run-pca.tsv" / "part-00000"))


def test_packed_run_matches_wire_run(tmp_path):
    """The packed fast path (run()) and the wire-record path produce the
    same similarity matrix, hence the same result lines."""
    argv = [
        "--references", "17:0:20000",
        "--variant-set-id", "vs-a",
        "--num-samples", "12",
        "--seed", "5",
        "--bases-per-partition", "5000",
    ]
    fast = pca_driver.run(argv)
    conf = PcaConf.parse(argv)
    driver = VariantsPcaDriver(conf)
    calls = driver.iter_calls(driver.get_data())
    S = driver.get_similarity_matrix(calls)
    slow = driver.emit_result(driver.compute_pca(S))
    assert fast == slow


def test_device_ingest_similarity_matches_wire_similarity():
    """The fused device generation path produces the identical Gramian to the
    wire-record path, single dataset."""
    import jax

    conf = _conf(ingest="device")
    driver = VariantsPcaDriver(conf, _source(conf))
    contigs = conf.get_contigs(driver.source, conf.variant_set_id)
    S_dev = np.asarray(jax.device_get(driver.get_similarity_device_gen(contigs)))

    conf2 = _conf()
    driver2 = VariantsPcaDriver(conf2, _source(conf2))
    calls = list(driver2.iter_calls(driver2.get_data()))
    S_wire = np.asarray(jax.device_get(driver2.get_similarity_matrix(calls)))
    np.testing.assert_array_equal(S_dev, S_wire)


@pytest.mark.parametrize("n_sets", [2, 3])
def test_device_ingest_matches_wire_multiset(n_sets):
    """2-set join and 3-set merge-intersect collapse to column concatenation
    on the device path — must equal the wire join/merge Gramian exactly."""
    import jax

    sets = ["vs-a", "vs-b", "vs-c"][:n_sets]
    conf = _conf(variant_set_id=sets, references="17:0:12000", ingest="device")
    driver = VariantsPcaDriver(conf, _source(conf))
    contigs = conf.get_contigs(driver.source, conf.variant_set_id)
    S_dev = np.asarray(jax.device_get(driver.get_similarity_device_gen(contigs)))

    conf2 = _conf(variant_set_id=sets, references="17:0:12000")
    driver2 = VariantsPcaDriver(conf2, _source(conf2))
    calls = list(driver2.iter_calls(driver2.get_data()))
    S_wire = np.asarray(jax.device_get(driver2.get_similarity_matrix(calls)))
    np.testing.assert_array_equal(S_dev, S_wire)


def test_multiset_wire_join_runs_windows_concurrently():
    """The ≥2-set wire join streams windows through the shard thread pool:
    with --num-workers N and a blocking source, multiple windows' record
    builds must be in flight at once (round-2 ask: the join previously
    computed every dataset's window serially per index)."""
    import threading
    import time

    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    class SlowSource(SyntheticGenomicsSource):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.lock = threading.Lock()
            self.active = 0
            self.max_active = 0

        def client(self):
            outer = self

            class SlowClient(type(super().client())):
                def search_variants(self, request, *a, **kw):
                    with outer.lock:
                        outer.active += 1
                        outer.max_active = max(outer.max_active, outer.active)
                    time.sleep(0.05)
                    try:
                        yield from super().search_variants(request, *a, **kw)
                    finally:
                        with outer.lock:
                            outer.active -= 1

            return SlowClient(outer)

    source = SlowSource(num_samples=8, seed=5, variant_spacing=100)
    conf = _conf(
        variant_set_id=["vs-a", "vs-b"],
        references="17:0:40000",
        num_samples=8,
        bases_per_partition=5000,  # 8 windows
        num_workers=4,
    )
    driver = VariantsPcaDriver(conf, source)
    rows = list(driver.iter_calls(driver.get_data()))
    assert rows  # the join produced records
    assert source.max_active >= 2  # windows overlapped, not serial


def test_asymmetric_joint_cohort_device_matches_wire():
    """The reference's ACTUAL joint-cohort scenario — a large cohort joined
    with a small deep-call cohort (1KG × Platinum,
    ``VariantsPca.scala:155-168``; ``SearchVariantsExample.scala:28``): a
    2-set join with DIFFERENT column counts per set, identical between the
    fused device ingest and the wire-record join path."""
    argv = [
        "--references", "17:0:20000",
        "--variant-set-id", "vs-a,vs-b",
        "--num-samples", "30,7",
        "--seed", "5",
        "--bases-per-partition", "5000",
    ]
    device_lines = pca_driver.run(argv + ["--ingest", "device"])
    wire_lines = pca_driver.run(argv + ["--ingest", "wire"])
    assert device_lines == wire_lines
    assert len(device_lines) == 37  # 30 + 7 columns


def test_asymmetric_cohort_callsets_and_populations():
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    source = SyntheticGenomicsSource(
        num_samples=30, seed=5, cohort_sizes={"vs-b": 7}
    )
    callsets = source.search_callsets(["vs-a", "vs-b"])
    assert len(callsets) == 37
    assert source.num_samples_for("vs-a") == 30
    assert source.num_samples_for("vs-b") == 7
    # A cohort smaller than n_pops still spans the populations it can:
    # population assignment is s*n_pops//N within EACH cohort.
    pops_b = source.populations_for("vs-b")
    assert len(pops_b) == 7 and pops_b.max() < source.n_pops


def test_device_run_entrypoint_matches_wire(tmp_path, capsys):
    argv = [
        "--references", "17:0:20000",
        "--variant-set-id", "vs-a",
        "--num-samples", "12",
        "--seed", "5",
        "--bases-per-partition", "5000",
    ]
    device_lines = pca_driver.run(argv + ["--ingest", "device"])
    wire_lines = pca_driver.run(argv + ["--ingest", "wire"])
    assert device_lines == wire_lines
    captured = capsys.readouterr().out
    assert "Variants API stats:" in captured


def test_same_set_join_accumulates_multiplicity():
    """Joining a variant set with itself: duplicate callset columns must
    contribute k² per entry (reference pair-loop semantics), on both the host
    oracle and the TPU path."""
    conf = _conf(variant_set_id=["vs-a", "vs-a"], references="17:0:8000")
    driver = VariantsPcaDriver(conf, _source(conf))
    assert len(driver.indexes) == 30  # duplicate ids collapse columns
    calls = list(driver.iter_calls(driver.get_data()))
    assert any(len(row) != len(set(row)) for row in calls)
    S_tpu = np.asarray(driver.get_similarity_matrix(iter(calls)))

    conf_host = _conf(variant_set_id=["vs-a", "vs-a"], references="17:0:8000",
                      pca_backend="host")
    driver_host = VariantsPcaDriver(conf_host, _source(conf_host))
    S_host = driver_host.get_similarity_matrix(iter(calls))
    np.testing.assert_array_equal(S_tpu, S_host)
    # k duplicates ⇒ diagonal gets k² > k somewhere.
    row = next(r for r in calls if len(r) != len(set(r)))
    assert S_host.max() >= 4 or len(calls) < 5


def test_ingest_flag_guards():
    with pytest.raises(ValueError, match="ingest device"):
        pca_driver.run(["--ingest", "device", "--source", "rest",
                        "--references", "17:0:1000"])
    with pytest.raises(ValueError, match="ingest packed"):
        pca_driver.run(["--ingest", "packed", "--pca-backend", "host",
                        "--references", "17:0:1000"])
    with pytest.raises(ValueError, match="single variant set"):
        pca_driver.run(["--ingest", "packed", "--variant-set-id", "a,b",
                        "--references", "17:0:1000", "--num-samples", "8"])


def test_sharded_strategy_end_to_end_matches_dense(tmp_path):
    """--similarity-strategy sharded (row-tile Gramian + sharded centering +
    sharded subspace PCA) equals the dense strategy end to end, at a padded
    non-divisible cohort size (21 samples on a samples-axis-8 mesh)."""
    argv = [
        "--references", "17:0:30000",
        "--variant-set-id", "vs-a",
        "--num-samples", "21",
        "--seed", "5",
        "--bases-per-partition", "10000",
        "--block-size", "32",
        "--ingest", "packed",
    ]
    dense = pca_driver.run(argv + ["--similarity-strategy", "dense"])
    sharded = pca_driver.run(
        argv + ["--similarity-strategy", "sharded", "--mesh-shape", "1,8"]
    )
    assert_pcs_match(dense, sharded)


def test_sharded_strategy_guard_without_mesh():
    with pytest.raises(ValueError, match="samples axis"):
        conf = _conf(similarity_strategy="sharded", mesh_shape="8,1")
        driver = VariantsPcaDriver(conf, _source(conf))
        driver.get_similarity_matrix(iter([[0, 1]]))


def test_sharded_device_ingest_run_matches_dense_run():
    """Single-set sharded strategy now stays on the device ingest path
    (ring accumulator) end to end; result equals the dense device run."""
    argv = [
        "--references", "17:0:30000",
        "--variant-set-id", "vs-a",
        "--num-samples", "21",
        "--seed", "5",
        "--bases-per-partition", "10000",
        "--block-size", "32",
    ]
    dense = pca_driver.run(argv + ["--similarity-strategy", "dense"])
    sharded = pca_driver.run(
        argv + ["--similarity-strategy", "sharded", "--mesh-shape", "1,8"]
    )
    assert_pcs_match(dense, sharded)


def test_merged_sharded_run_stays_on_device_and_matches_wire(capsys):
    """A past cliff, closed: a merged (asymmetric 2-set) config
    under the SHARDED strategy — the joint-cohort-past-the-dense-HBM-rule
    scenario (``VariantsPca.scala:155-168``) — now runs the multi-set ring
    device path instead of silently falling back to wire ingest, and its
    principal components match the wire oracle."""
    argv = [
        "--references", "17:0:30000",
        "--variant-set-id", "vs-a,vs-b",
        "--num-samples", "13,6",
        "--seed", "5",
        "--block-size", "32",
    ]
    wire = pca_driver.run(argv + ["--ingest", "wire"])
    capsys.readouterr()
    sharded = pca_driver.run(
        argv + ["--similarity-strategy", "sharded", "--mesh-shape", "1,8"]
    )
    out = capsys.readouterr().out
    # Loud-fallback guard: the run must NOT have taken the wire path.
    assert "using wire ingest" not in out
    assert_pcs_match(wire, sharded)


def test_io_stats_parity_across_ingest_paths(capsys):
    """partitions / requests / variants agree between the device, packed and
    wire ingest paths for the same single-set configuration."""
    argv = [
        "--references", "17:0:20000",
        "--variant-set-id", "vs-a",
        "--num-samples", "12",
        "--seed", "5",
        "--bases-per-partition", "5000",
        "--block-size", "32",
    ]

    def stats_of(ingest):
        pca_driver.run(argv + ["--ingest", ingest])
        out = capsys.readouterr().out
        fields = {}
        for line in out.splitlines():
            if line.startswith("# of"):
                key, value = line.split(": ")
                fields[key] = int(value)
        return fields

    device = stats_of("device")
    packed = stats_of("packed")
    wire = stats_of("wire")
    for key in ("# of partitions", "# of bases requested", "# of API requests"):
        assert device[key] == packed[key] == wire[key], (key, device, packed, wire)
    # Variants: device/packed count kept rows after the nonzero drop; wire
    # counts every record built (ref blocks included) — a documented
    # divergence, but device and packed must agree exactly.
    assert device["# of variants read"] == packed["# of variants read"]
    assert wire["# of variants read"] >= device["# of variants read"]
