"""The graftcheck static-analysis subsystem: linter golden fixtures (rule
IDs + line numbers), the clean-tree gate, escape hatches, the device-free
plan validator's accept/reject matrix, and the sanitizer corpus/harness.

The fixtures are inline sources (not importable files): the linter works on
text, and inline keeps each violation's expected LINE NUMBER adjacent to
the code that produces it.
"""

import json
import os
import subprocess
import textwrap
import warnings

import numpy as np
import pytest

from spark_examples_tpu.check.linter import json_report, lint_paths, lint_source
from spark_examples_tpu.check.plan import validate_plan
from spark_examples_tpu.check.rules import RULES
from spark_examples_tpu.config import PcaConf

_PACKAGE_DIR = os.path.dirname(
    os.path.abspath(__import__("spark_examples_tpu").__file__)
)


def _ids(findings):
    return [(f.rule_id, f.line) for f in findings]


# --------------------------------------------------------------------------
# Golden fixtures: one violation per rule, asserting id AND line number.
# --------------------------------------------------------------------------


def test_gc001_item_sync_in_hot_path():
    src = textwrap.dedent(
        """
        def f(x):
            return x.mean().item()
        """
    )
    assert _ids(lint_source(src, "ops/fixture.py")) == [("GC001", 3)]


def test_gc001_float_of_jnp_value():
    src = textwrap.dedent(
        """
        import jax.numpy as jnp
        def f(x):
            y = jnp.sum(x)
            return float(y)
        """
    )
    assert _ids(lint_source(src, "pipeline/fixture.py")) == [("GC001", 5)]


def test_gc001_scoped_to_hot_paths_only():
    src = textwrap.dedent(
        """
        import jax.numpy as jnp
        def f(x):
            y = jnp.sum(x)
            return float(y)
        """
    )
    # The same code outside ops/ and pipeline/ is legitimate (tests,
    # oracles, benchmark reporting).
    assert lint_source(src, "utils/fixture.py") == []


def test_gc002_branch_on_traced_param():
    src = textwrap.dedent(
        """
        import jax
        @jax.jit
        def f(x, n):
            if x > 0:
                return x
            while n:
                n = n - 1
            return n
        """
    )
    assert _ids(lint_source(src, "anywhere.py")) == [
        ("GC002", 5),
        ("GC002", 7),
    ]


def test_gc002_static_and_identity_tests_pass():
    src = textwrap.dedent(
        """
        import functools, jax
        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            if n > 0:
                return x
            if x is None:
                return x
            return x
        """
    )
    assert lint_source(src, "anywhere.py") == []


def test_gc003_jit_inside_loop():
    src = textwrap.dedent(
        """
        import jax
        def f(xs):
            out = []
            for x in xs:
                g = jax.jit(lambda v: v + 1)
                out.append(g(x))
            return out
        """
    )
    assert _ids(lint_source(src, "anywhere.py")) == [("GC003", 6)]


def test_gc004_jnp_at_import_time():
    src = textwrap.dedent(
        """
        import jax.numpy as jnp
        TABLE = jnp.arange(16)
        """
    )
    assert _ids(lint_source(src, "anywhere.py")) == [("GC004", 3)]
    # Inside a function: fine.
    fn = "import jax.numpy as jnp\ndef f():\n    return jnp.arange(16)\n"
    assert lint_source(fn, "anywhere.py") == []
    # A module-level lambda BODY runs at call time, not import time.
    lam = "import jax.numpy as jnp\nf = lambda x: jnp.sum(x)\n"
    assert lint_source(lam, "anywhere.py") == []


def test_gc005_update_without_donation_and_with():
    bad = textwrap.dedent(
        """
        import jax
        @jax.jit
        def gram_update(G, X):
            return G + X
        """
    )
    assert _ids(lint_source(bad, "ops/fixture.py")) == [("GC005", 4)]
    good = textwrap.dedent(
        """
        import functools, jax
        @functools.partial(jax.jit, donate_argnums=(0,))
        def gram_update(G, X):
            return G + X
        """
    )
    assert lint_source(good, "ops/fixture.py") == []
    # Outside ops/: not this rule's business.
    assert lint_source(bad, "pipeline/fixture.py") == []


def test_gc006_lock_without_ordering_comment():
    bad = textwrap.dedent(
        """
        import threading
        class A:
            def __init__(self):
                self._lock = threading.Lock()
        """
    )
    assert _ids(lint_source(bad, "sources/fixture.py")) == [("GC006", 5)]
    good = textwrap.dedent(
        """
        import threading
        class A:
            def __init__(self):
                # lock order: leaf lock, never held across another acquire
                self._lock = threading.Lock()
        """
    )
    assert lint_source(good, "sources/fixture.py") == []


def test_gc007_block_until_ready_in_loop():
    src = textwrap.dedent(
        """
        import jax
        def feed(blocks, G):
            for b in blocks:
                G = G + b
                jax.block_until_ready(G)
            return G
        """
    )
    assert _ids(lint_source(src, "ops/fixture.py")) == [("GC007", 6)]


def test_gc008_print_under_jit():
    src = textwrap.dedent(
        """
        from jax import jit
        @jit
        def f(x):
            print("tracing", x)
            return x
        """
    )
    assert _ids(lint_source(src, "anywhere.py")) == [("GC008", 5)]


def test_gc009_ad_hoc_stats_mutation():
    bad = textwrap.dedent(
        """
        def account(self, io_stats, n):
            io_stats.requests += n
            self.counters.initialized_requests += 1
            self.stream_counters.variants += n
        """
    )
    assert _ids(lint_source(bad, "pipeline/fixture.py")) == [
        ("GC009", 3),
        ("GC009", 4),
        ("GC009", 5),
    ]
    # Methods on the owner (`self.x += n` inside the stats class) and
    # non-stats objects stay clean, as does out-of-scope code.
    good = textwrap.dedent(
        """
        class StreamCounters:
            def add_variants(self, n):
                self.variants += n

        def feed(acc, io_stats, n):
            acc.rows_seen += n
            io_stats.add_requests(n)
        """
    )
    assert lint_source(good, "sources/fixture.py") == []
    assert lint_source(bad, "utils/fixture.py") == []


def test_gc009_disable_escape_hatch():
    src = (
        "def f(io_stats):\n"
        "    io_stats.requests += 1  # graftcheck: disable=GC009 -- oracle\n"
    )
    assert lint_source(src, "pipeline/fixture.py") == []


def test_gc010_host_numpy_under_jit():
    bad = textwrap.dedent(
        """
        import jax
        import numpy as np
        @jax.jit
        def kernel(G, X):
            mask = np.asarray(X)
            return G + np.sum(mask)
        """
    )
    assert _ids(lint_source(bad, "ops/fixture.py")) == [
        ("GC010", 6),
        ("GC010", 7),
    ]


def test_gc010_shard_map_decoration_and_scope():
    bad = textwrap.dedent(
        """
        import functools
        import numpy as np
        from jax import shard_map
        @functools.partial(shard_map, mesh=None, in_specs=(), out_specs=())
        def per_device(x):
            return np.packbits(x)
        """
    )
    assert _ids(lint_source(bad, "ops/fixture.py")) == [("GC010", 7)]
    # The same code outside ops/ (tests, host staging) is legitimate.
    assert lint_source(bad, "sources/fixture.py") == []
    # Undecorated host code in ops/ is the normal staging path.
    host = textwrap.dedent(
        """
        import numpy as np
        def stage(rows):
            return np.packbits(rows, axis=-1)
        """
    )
    assert lint_source(host, "ops/fixture.py") == []


def test_gc010_dtype_constructors_and_escape_hatch():
    # np dtype constructors are trace-time metadata, not host compute.
    ok = textwrap.dedent(
        """
        import jax
        import numpy as np
        @jax.jit
        def kernel(G, X):
            return G + X.astype(np.dtype("float32"))
        """
    )
    assert lint_source(ok, "ops/fixture.py") == []
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def kernel(G):\n"
        "    return G + np.sum(G)  # graftcheck: disable=GC010 -- trace-time constant, measured\n"
    )
    assert lint_source(src, "ops/fixture.py") == []


# --------------------------------------------------------------------------
# Escape hatches.
# --------------------------------------------------------------------------


def test_disable_comment_silences_named_rule_only():
    src = (
        "def f(x):\n"
        "    return x.mean().item()  # graftcheck: disable=GC001 -- oracle\n"
    )
    assert lint_source(src, "ops/fixture.py") == []
    wrong_id = (
        "def f(x):\n"
        "    return x.mean().item()  # graftcheck: disable=GC007\n"
    )
    assert _ids(lint_source(wrong_id, "ops/fixture.py")) == [("GC001", 2)]


def test_disable_file_and_disable_all():
    src = (
        "# graftcheck: disable-file=GC001\n"
        "def f(x):\n"
        "    return x.mean().item()\n"
    )
    assert lint_source(src, "ops/fixture.py") == []
    src_all = (
        "def f(x):\n"
        "    return x.mean().item()  # graftcheck: disable=all\n"
    )
    assert lint_source(src_all, "ops/fixture.py") == []


# --------------------------------------------------------------------------
# The merged tree lints clean, and the report is machine-readable.
# --------------------------------------------------------------------------


def test_package_tree_is_lint_clean():
    findings, checked = lint_paths([_PACKAGE_DIR])
    assert checked > 40  # the whole package was walked, not a subtree
    assert findings == [], "\n".join(f.format() for f in findings)


def test_json_report_schema():
    src = "def f(x):\n    return x.mean().item()\n"
    findings = lint_source(src, "ops/fixture.py")
    report = json.loads(json_report(findings, checked=1))
    assert report["tool"] == "graftcheck"
    assert report["checked_files"] == 1
    assert report["finding_count"] == 1
    [entry] = report["findings"]
    assert entry["rule"] == "GC001"
    assert entry["path"] == "ops/fixture.py"
    assert entry["line"] == 2
    assert entry["name"] == RULES["GC001"].name


def test_cli_exit_codes(tmp_path):
    from spark_examples_tpu.check.cli import main

    assert main(["lint", _PACKAGE_DIR]) == 0
    bad = tmp_path / "ops"
    bad.mkdir()
    (bad / "fixture.py").write_text("def f(x):\n    return x.item()\n")
    assert main(["lint", str(tmp_path)]) == 1
    assert main(["lint", str(tmp_path / "missing")]) == 2
    assert main(["nonsense"]) == 2


def test_single_file_lint_keeps_scoped_rules():
    """Linting ONE file must apply the same scoped rules as the tree walk
    (per-changed-file invocations — hooks, editors — must not silently
    drop GC001/GC005/GC006/GC007)."""
    findings, checked = lint_paths(
        [os.path.join(_PACKAGE_DIR, "ops", "gramian.py")]
    )
    assert checked == 1
    assert findings == []  # clean WITH its disables honored…
    # …and the scoped rule genuinely ran: the same file with the GC005
    # disables stripped must flag again under its package relpath.
    from spark_examples_tpu.check.linter import _package_relpath

    relpath = _package_relpath(os.path.join(_PACKAGE_DIR, "ops", "gramian.py"))
    assert relpath == "ops/gramian.py"
    with open(os.path.join(_PACKAGE_DIR, "ops", "gramian.py")) as f:
        stripped = f.read().replace("# graftcheck: disable=GC005", "#")
    assert any(
        f.rule_id == "GC005" for f in lint_source(stripped, relpath)
    )


# --------------------------------------------------------------------------
# Plan validator: accepts runnable configs, rejects impossible ones —
# without touching a device (asserted via live array count).
# --------------------------------------------------------------------------


def _plan(argv, devices=None):
    conf = PcaConf.parse(argv)
    return validate_plan(conf, plan_devices=devices)


def _error_codes(report):
    return {i.code for i in report.issues if i.severity == "error"}


def test_plan_accepts_default_config():
    report = _plan([])
    assert report.ok, report.format()
    assert any("dense update" in c for c in report.shape_checks)


def test_plan_accepts_sharded_mesh_with_enough_devices():
    report = _plan(
        ["--mesh-shape", "4,2", "--similarity-strategy", "sharded"],
        devices=8,
    )
    assert report.ok, report.format()
    assert any("abstract 4x2 mesh" in c for c in report.shape_checks)


def test_plan_rejects_mesh_exceeding_declared_devices():
    report = _plan(["--mesh-shape", "4,2"], devices=4)
    assert not report.ok
    assert "mesh-exceeds-devices" in _error_codes(report)


def test_plan_rejects_sharded_without_samples_axis():
    report = _plan(
        ["--similarity-strategy", "sharded", "--mesh-shape", "4,1"],
        devices=4,
    )
    assert not report.ok
    assert "sharded-needs-samples-axis" in _error_codes(report)


def test_plan_rejects_data_axis_past_reduce_partitions():
    report = _plan(
        ["--mesh-shape", "8,1", "--num-reduce-partitions", "4"], devices=8
    )
    assert not report.ok
    assert "data-axis-exceeds-reduce-partitions" in _error_codes(report)


def test_plan_rejects_num_pc_past_cohort():
    report = _plan(["--num-pc", "500", "--num-samples", "100"])
    assert not report.ok
    assert "num-pc-exceeds-cohort" in _error_codes(report)


def test_plan_rejects_flag_contract_via_cli():
    from spark_examples_tpu.check.cli import main

    assert main(["plan", "--blocks-per-dispatch", "0"]) == 2
    # argparse-level flag errors must ALSO come back as an int plan
    # rejection, never a SystemExit out of main().
    assert main(["plan", "--ingest", "bogus"]) == 2
    assert main(["plan", "--no-such-flag"]) == 2


def test_plan_warns_on_cohort_padding():
    report = _plan(
        [
            "--similarity-strategy", "sharded", "--mesh-shape", "2,3",
            "--num-samples", "100",
        ],
        devices=6,
    )
    assert report.ok
    assert any(i.code == "cohort-padding" for i in report.issues)


def test_plan_touches_no_device_arrays():
    import jax

    before = len(jax.live_arrays())
    report = _plan(
        ["--mesh-shape", "2,2", "--similarity-strategy", "sharded"],
        devices=4,
    )
    assert report.ok
    assert len(jax.live_arrays()) == before  # eval_shape only — no buffers


def test_plan_rejects_negative_heartbeat():
    # The parse path rejects it as a flag contract…
    from spark_examples_tpu.check.cli import main

    assert main(["plan", "--heartbeat-seconds", "-5"]) == 2
    # …and programmatic PcaConf construction (which bypasses
    # _from_namespace) is caught by validate_plan itself.
    conf = PcaConf()
    conf.heartbeat_seconds = -1.0
    report = validate_plan(conf)
    assert not report.ok
    assert "heartbeat-seconds" in _error_codes(report)


def test_plan_rejects_unwritable_metrics_json(tmp_path):
    report = _plan(
        ["--metrics-json", str(tmp_path / "no_such_dir" / "m.json")]
    )
    assert not report.ok
    assert "metrics-json-parent" in _error_codes(report)
    # A directory path can't receive the manifest either.
    report = _plan(["--metrics-json", str(tmp_path)])
    assert not report.ok
    assert "metrics-json-parent" in _error_codes(report)
    # A writable parent passes.
    report = _plan(["--metrics-json", str(tmp_path / "m.json")])
    assert report.ok, report.format()
    from spark_examples_tpu.check.cli import main

    assert (
        main(["plan", "--metrics-json", str(tmp_path / "x" / "m.json")]) == 2
    )


def test_plan_surfaces_ir_facts_for_sharded_configs():
    """The sharded plan report carries the jaxpr-derived ring traffic and
    static liveness facts, and the jaxpr traffic equals the formula-derived
    fact the report already had — cross-validated every plan run."""
    report = _plan(
        ["--mesh-shape", "1,2", "--similarity-strategy", "sharded"],
        devices=2,
    )
    assert report.ok, report.format()
    geometry = report.geometry
    assert (
        geometry["ring_bytes_per_flush_jaxpr"]
        == geometry["ring_bytes_per_flush"]
    )
    assert geometry["ring_peak_live_bytes_per_device"] > 0
    assert geometry["ring_permute_steps"] == 1  # samples axis 2 -> D-1 = 1
    assert any("ring IR audit" in c for c in report.shape_checks)


# --------------------------------------------------------------------------
# Sanitizer corpus + harness.
# --------------------------------------------------------------------------


def test_corpus_is_deterministic_and_covers_edges():
    from spark_examples_tpu.check.corpus import corpus_documents

    a = corpus_documents()
    b = corpus_documents()
    assert a == b
    assert len(a) >= 30
    joined = b"\n".join(a)
    assert b"" in a  # empty buffer
    assert b"\r\n" in joined  # CRLF documents
    assert any(doc and not doc.startswith(b"#") for doc in a)  # headerless


def test_corpus_parses_match_python_oracle():
    """Every non-malformed corpus document parses identically through the
    native and Python paths (the sanitize replay checks memory/race safety;
    this pins semantic parity over the same corpus)."""
    from spark_examples_tpu.check.corpus import corpus_documents
    from spark_examples_tpu.utils import native as native_mod

    if native_mod.vcf_library() is None:
        pytest.skip(f"no native build: {native_mod.native_unavailable_reason()}")
    import tempfile

    from spark_examples_tpu.sources.files import _python_vcf_arrays

    # One comparison semantics for every parity tier: the grouping and the
    # NaN-aware array equality live in the fuzz module.
    from test_files_fuzz import _assert_same_arrays, _group_by_contig

    checked = 0
    for doc in corpus_documents():
        try:
            native = native_mod.parse_vcf_arrays(doc)
        except ValueError:
            continue  # malformed by design; parity on errors is tested elsewhere
        fd, path = tempfile.mkstemp(suffix=".vcf")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(doc)
            try:
                python = _python_vcf_arrays(path, "corpus")
            except ValueError:
                continue
        finally:
            os.unlink(path)
        grouped_native = _group_by_contig(*native)
        grouped_python = _group_by_contig(*python)
        assert set(grouped_native) == set(grouped_python)
        for contig in grouped_native:
            _assert_same_arrays(grouped_native[contig], grouped_python[contig])
        checked += 1
    assert checked >= 10  # the corpus is mostly well-formed by design


def _compiler_available():
    from spark_examples_tpu.utils.native import _compiler

    return _compiler() is not None


@pytest.mark.skipif(not _compiler_available(), reason="no C++ compiler")
def test_asan_harness_replays_mini_corpus_clean():
    """Tier-1 smoke: the ASan build replays a corpus subset clean (the full
    3-mode replay is the slow test below / `ci.sh --sanitize`)."""
    from spark_examples_tpu.check.corpus import corpus_documents
    from spark_examples_tpu.check.sanitize import replay_corpus

    proc = replay_corpus("asan", corpus=corpus_documents()[:8])
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.slow
@pytest.mark.skipif(not _compiler_available(), reason="no C++ compiler")
@pytest.mark.parametrize("mode", ["asan", "ubsan", "tsan"])
def test_sanitizer_full_corpus_replay(mode):
    from spark_examples_tpu.check.sanitize import replay_corpus

    proc = replay_corpus(mode)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_typecheck_gate_skips_or_passes():
    """On images without mypy the gate must SKIP (exit 0); with mypy it
    must pass against the committed baseline — either way the lint stage
    stays green on the merged tree."""
    from spark_examples_tpu.check.typecheck import run_typecheck

    assert run_typecheck(strict=False) == 0


# --------------------------------------------------------------------------
# The gz auto-streaming sortedness fallback (ADVICE.md sharp edge).
# --------------------------------------------------------------------------

_VCF_HEADER = (
    "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS0\tS1\n"
)


def _write(tmp_path, name, text):
    path = tmp_path / name
    path.write_text(text)
    return str(path)


def test_auto_stream_falls_back_on_unsorted(tmp_path, monkeypatch):
    import spark_examples_tpu.sources.files as files_mod
    from spark_examples_tpu.sharding.contig import Contig
    from spark_examples_tpu.sources.files import (
        FileGenomicsSource,
        StreamCounters,
    )

    monkeypatch.setattr(files_mod, "STREAM_THRESHOLD_BYTES", 1)
    path = _write(
        tmp_path,
        "unsorted.vcf",
        _VCF_HEADER
        + "1\t30\t.\tA\tG\t.\t.\tAF=0.5\tGT\t0|1\t1|1\n"
        + "1\t5\t.\tA\tG\t.\t.\tAF=0.5\tGT\t1|0\t0|0\n",
    )
    src = FileGenomicsSource([path])
    set_id = src.set_ids[0]
    assert src.wants_streaming(set_id)  # the size heuristic chose streaming
    shards = [Contig("1", 0, 100)]
    counters = StreamCounters(len(shards))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        blocks = list(
            src.stream_genotype_blocks(
                set_id, shards, block_size=16, counters=counters
            )
        )
    assert any("unsorted" in str(w.message) for w in caught)
    # The in-memory fallback served the SAME data (position-sorted).
    [block] = blocks
    assert block["positions"].tolist() == [4, 29]
    assert counters.shard_rows == {0: 2}
    assert counters.variants == 2
    # The set is now pinned to the in-memory path.
    assert not src.wants_streaming(set_id)
    assert [(c.reference_name, c.end) for c in src.get_contigs(set_id)] == [
        ("1", 30)
    ]


def test_explicit_streaming_keeps_hard_error(tmp_path):
    from spark_examples_tpu.sharding.contig import Contig
    from spark_examples_tpu.sources.files import (
        FileGenomicsSource,
        UnsortedVcfError,
    )

    path = _write(
        tmp_path,
        "unsorted.vcf",
        _VCF_HEADER
        + "1\t30\t.\tA\tG\t.\t.\t.\tGT\t0|1\t1|1\n"
        + "1\t5\t.\tA\tG\t.\t.\t.\tGT\t1|0\t0|0\n",
    )
    src = FileGenomicsSource([path], stream_chunk_bytes=64)
    with pytest.raises(UnsortedVcfError):
        list(
            src.stream_genotype_blocks(
                src.set_ids[0], [Contig("1", 0, 100)]
            )
        )


def test_auto_stream_sorted_file_still_streams(tmp_path, monkeypatch):
    import spark_examples_tpu.sources.files as files_mod
    from spark_examples_tpu.sharding.contig import Contig
    from spark_examples_tpu.sources.files import FileGenomicsSource

    monkeypatch.setattr(files_mod, "STREAM_THRESHOLD_BYTES", 1)
    path = _write(
        tmp_path,
        "sorted.vcf",
        _VCF_HEADER
        + "".join(
            f"1\t{p}\t.\tA\tG\t.\t.\tAF=0.5\tGT\t0|1\t1|1\n"
            for p in (5, 10, 20, 30)
        ),
    )
    src = FileGenomicsSource([path])
    set_id = src.set_ids[0]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        blocks = list(
            src.stream_genotype_blocks(
                set_id, [Contig("1", 0, 100)], block_size=2
            )
        )
    assert not [w for w in caught if "unsorted" in str(w.message)]
    assert sum(len(b["positions"]) for b in blocks) == 4
    assert src.wants_streaming(set_id)  # still the streaming path
