"""``graftcheck ranges``: the abstract-interpretation overflow/exactness
prover. Golden audits across the mesh/dtype matrix (the shipped kernels
must PROVE clean, with the ring's disjoint-slice refinement engaged),
broken-kernel fixtures per GR rule, the interpreter's interval lattice,
the shared contract registry (``ops/contracts.py``), the ``graftcheck
plan`` exactness accept/reject matrix including the exact boundary
geometry, the GC011 narrowing-cast lint rule, the ``--check-ranges``
runtime sampling pair and its manifest block, and the zero-live-arrays
contract."""

import dataclasses
import json
import textwrap

import numpy as np
import pytest

from spark_examples_tpu.check.linter import lint_source
from spark_examples_tpu.check.plan import validate_plan
from spark_examples_tpu.check.ranges import (
    AbsVal,
    Interpreter,
    RangeKernelSpec,
    audit_range_kernel,
    counts_range_spec,
    default_specs,
    dense_range_spec,
    ring_range_spec,
    run_audit,
)
from spark_examples_tpu.check.rules import RANGES_RULES, RULES
from spark_examples_tpu.config import PcaConf
from spark_examples_tpu.ops.contracts import (
    COUNT_ROW,
    HAS_VARIATION,
    PACKED_BYTE,
    RangeContract,
    exact_int_window,
    exactness_headroom_sites,
    flush_entry_increment,
)

INT32_WINDOW = exact_int_window(np.int32)
F32_WINDOW = exact_int_window(np.float32)


def _rule_ids(audit):
    return sorted({f.rule_id for f in audit.findings})


# --------------------------------------------------------------------------
# The contract registry.
# --------------------------------------------------------------------------


def test_exact_int_windows():
    assert F32_WINDOW == 1 << 24
    assert exact_int_window("bfloat16") == 1 << 8
    assert exact_int_window(np.float64) == 1 << 53
    assert INT32_WINDOW == 2**31 - 1
    assert exact_int_window(np.uint8) == 255
    assert exact_int_window(np.int8) == 127
    assert exact_int_window("not-a-dtype") is None


def test_flush_entry_increment_and_headroom():
    assert flush_entry_increment(1024, 1) == 1024
    assert flush_entry_increment(1024, 3) == 9216
    assert exactness_headroom_sites(np.float32, 1) == F32_WINDOW
    assert exactness_headroom_sites(np.int32, 2) == INT32_WINDOW // 4
    assert exactness_headroom_sites("not-a-dtype", 1) == 0


def test_gramian_exact_limit_is_shared():
    # The accumulator conversion threshold and the contract registry are
    # ONE constant — the GR005 story depends on it.
    from spark_examples_tpu.ops.contracts import EXACT_F32_LIMIT
    from spark_examples_tpu.ops.gramian import (
        EXACT_F32_LIMIT as GRAMIAN_LIMIT,
    )

    assert GRAMIAN_LIMIT is EXACT_F32_LIMIT
    assert GRAMIAN_LIMIT == F32_WINDOW


def test_ranges_rules_registered():
    from spark_examples_tpu.check.rules import ALL_RULES

    for rule_id in ("GR000", "GR001", "GR002", "GR003", "GR004", "GR005"):
        assert rule_id in RANGES_RULES
        assert rule_id in ALL_RULES


# --------------------------------------------------------------------------
# The interval lattice on small traced programs.
# --------------------------------------------------------------------------


def _interp(fn, in_vals, *avals, axis_sizes=None):
    import jax

    closed = jax.make_jaxpr(fn)(*avals)
    return Interpreter(axis_sizes or {}).run(closed, list(in_vals))


def test_interpreter_arithmetic():
    import jax
    import jax.numpy as jnp

    x = jax.ShapeDtypeStruct((4,), jnp.int32)
    (out,) = _interp(
        lambda a, b: a * b + 3,
        [AbsVal(0, 2, True), AbsVal(0, 5, True)],
        x,
        x,
    )
    assert (out.lo, out.hi, out.integer) == (3.0, 13.0, True)


def test_interpreter_dot_contraction():
    import jax
    import jax.numpy as jnp

    a = jax.ShapeDtypeStruct((4, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    interp = Interpreter({})
    closed = jax.make_jaxpr(lambda x, y: x @ y)(a, b)
    (out,) = interp.run(closed, [AbsVal(0, 1, True), AbsVal(0, 2, True)])
    # 16 products each in [0, 2].
    assert (out.lo, out.hi) == (0.0, 32.0)
    assert len(interp.dots) == 1
    assert interp.dots[0].contraction == 16


def test_interpreter_scan_widening():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def f(x):
        return lax.fori_loop(0, 10, lambda i, c: c + x, jnp.float32(0))

    (out,) = _interp(
        f, [AbsVal(0, 3, True)], jax.ShapeDtypeStruct((), jnp.float32)
    )
    # Outward widening: 10 trips of growth <= 3.
    assert out.lo == 0.0
    assert out.hi == 30.0


def test_interpreter_unpack_tightens_packed_bytes():
    import jax
    import jax.numpy as jnp

    from spark_examples_tpu.ops.gramian import _unpack_bits

    x = jax.ShapeDtypeStruct((4, 2), jnp.uint8)
    (out,) = _interp(
        lambda p: _unpack_bits(p, 16), [AbsVal(0, 255, True)], x
    )
    # The shift-and-mask unpack provably yields membership bits.
    assert (out.lo, out.hi, out.integer) == (0.0, 1.0, True)


# --------------------------------------------------------------------------
# Golden audits: the shipped kernels PROVE clean across the matrix.
# --------------------------------------------------------------------------


def test_shipped_matrix_proves_clean():
    report = run_audit()
    assert report.ok, "\n".join(f.format() for f in report.findings)
    # 2x(dense+counts) + 2 stacked fused-group sizes + 3 meshes x
    # (2 pack x 2 dtype + 1 counts-ring + 1 devicegen-ring)
    assert len(report.audits) == 24
    for audit in report.audits:
        assert audit.facts["entry_increment"] is not None
        assert (
            audit.facts["flush_projection"]
            >= audit.facts["entry_increment"]
        )
        assert audit.facts["exactness_headroom_sites"]["int32"] > 0
    doc = json.loads(report.to_json())
    assert doc["tool"] == "graftcheck-ranges"
    assert doc["ok"] is True


def test_ring_disjoint_slice_refinement_engages():
    # The proof that matters: the ring's per-dispatch entry increment is
    # ONE dot partial (B x max_count²), not samples x that — the
    # dynamic_update_slice disjointness was PROVEN, not assumed.
    audit = audit_range_kernel(ring_range_spec(1, 4, 64, 8, True, False))
    assert audit.ok, [f.format() for f in audit.findings]
    assert audit.facts["entry_increment"] == 8.0
    assert audit.facts["entry_increment_conservative"] == 32.0
    assert audit.facts["dot_partial_bound"] == 8.0


@pytest.mark.parametrize("samples", [2, 3, 4, 8])
def test_half_ring_tiles_each_take_one_partial_per_pass(samples):
    # The device-generation half ring adds one dot partial per pass into
    # each of its ⌊D/2⌋+1 step tiles, which hold disjoint entries: the
    # per-dispatch entry increment is K x B, the largest tile's, and the
    # tiles' stacking for the ring entry point keeps their deltas.
    from spark_examples_tpu.check.ranges import devicegen_range_spec

    audit = audit_range_kernel(devicegen_range_spec(1, samples, 64, 8, 2))
    assert audit.ok, [f.format() for f in audit.findings]
    assert audit.facts["entry_increment"] == 2 * 8
    assert audit.facts["entry_increment_conservative"] == 2 * 8
    assert "unhandled_primitives" not in audit.facts


def test_counts_ring_kernel_audited_under_join_contract():
    # Same-set-join flushes ride the UNPACKED ring kernel regardless of
    # --ring-pack-bits; the count contract must be proven on that path.
    audit = audit_range_kernel(
        ring_range_spec(1, 4, 64, 8, True, False, counts=True)
    )
    assert audit.ok, [f.format() for f in audit.findings]
    assert audit.facts["input_contracts"] == [None, COUNT_ROW.name]
    assert audit.facts["entry_increment"] == 8 * COUNT_ROW.hi**2


def test_ring_passes_multiply_refined_increment():
    # The disjointness proof bounds one update per entry per RING PASS;
    # an enclosing scan of length T runs T passes, so the refined
    # increment must scale by T (the unsound-direction regression the
    # review caught). Wrap the ring update in an outer fori_loop.
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import AbstractMesh

    from spark_examples_tpu.check.ranges import RangeKernelSpec
    from spark_examples_tpu.ops.gramian import build_sharded_update
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS

    T = 3

    def build():
        mesh = AbstractMesh((1, 4), (DATA_AXIS, SAMPLES_AXIS))
        update = build_sharded_update(mesh, np.float32, True)

        def repeated(G, X):
            return lax.fori_loop(0, T, lambda _, g: update(g, X), G)

        G = jax.ShapeDtypeStruct((1, 64, 64), jnp.float32)
        X = jax.ShapeDtypeStruct((1, 8, 8), jnp.uint8)
        return repeated, (G, X)

    spec = RangeKernelSpec(
        name="fixture:ring-x3",
        build=build,
        input_contracts=(None, PACKED_BYTE),
        axis_sizes={DATA_AXIS: 1, SAMPLES_AXIS: 4},
        rows_per_flush=T * 8,
        max_count=1,
    )
    audit = audit_range_kernel(spec)
    assert audit.ok, [f.format() for f in audit.findings]
    # T passes x one dot partial (8) per entry per pass.
    assert audit.facts["entry_increment"] == T * 8


def test_counts_kernel_uses_join_ceiling():
    audit = audit_range_kernel(counts_range_spec(1, 64, 8))
    assert audit.ok
    # B x COUNT_ROW.hi² per dispatch.
    assert audit.facts["entry_increment"] == 8 * COUNT_ROW.hi**2
    assert (
        audit.facts["exactness_headroom_sites"]["float32"]
        == F32_WINDOW // COUNT_ROW.hi**2
    )


def test_zero_live_arrays_after_audit():
    import jax

    before = len(jax.live_arrays())
    run_audit(default_specs(num_samples=64, block_size=8, meshes=((1, 2),)))
    # Pure tracing: no device buffer outlives the audit.
    assert len(jax.live_arrays()) == before


# --------------------------------------------------------------------------
# Broken-kernel fixtures: one per GR rule.
# --------------------------------------------------------------------------


def test_gr000_trace_failure():
    def build():
        raise RuntimeError("deliberately broken builder")

    audit = audit_range_kernel(
        RangeKernelSpec(
            name="fixture:trace-failure",
            build=build,
            input_contracts=(),
            acc_invar=None,
        )
    )
    assert _rule_ids(audit) == ["GR000"]


def test_gr001_declared_geometry_overflow():
    spec = dataclasses.replace(
        ring_range_spec(1, 2, 64, 8, True, exact_int=True),
        declared_rows=3_000_000_000,
    )
    audit = audit_range_kernel(spec)
    assert "GR001" in _rule_ids(audit)
    assert audit.facts["gramian_entry_bound"] == 3_000_000_000


def test_gr001_per_dispatch_int32_partial():
    # A single dispatch whose int32 partial can pass 2^31: giant block.
    audit = audit_range_kernel(
        ring_range_spec(1, 2, 64, 3_000_000_000, True, exact_int=True)
    )
    assert "GR001" in _rule_ids(audit)


def test_gr002_f32_partial_past_window():
    # B x max_count² > 2^24 on the f32 path: exactness lost before the
    # conversion point could ever fire.
    audit = audit_range_kernel(dense_range_spec(1, 64, (1 << 24) + 8))
    assert "GR002" in _rule_ids(audit)


def test_gr003_lossy_cast():
    def build():
        import jax
        import jax.numpy as jnp

        x = jax.ShapeDtypeStruct((8,), jnp.int32)
        return (lambda v: v.astype(jnp.bfloat16), (x,))

    wide = RangeContract("fixture_wide", 0, 1 << 20, "fixture", True)
    audit = audit_range_kernel(
        RangeKernelSpec(
            name="fixture:lossy-cast",
            build=build,
            input_contracts=(wide,),
            acc_invar=None,
        )
    )
    assert _rule_ids(audit) == ["GR003"]
    assert "bfloat16" in audit.findings[0].detail


def test_gr004_uncontracted_dot_input():
    spec = dataclasses.replace(
        dense_range_spec(1, 64, 8), input_contracts=(None, None)
    )
    audit = audit_range_kernel(spec)
    assert "GR004" in _rule_ids(audit)


def test_gr005_broken_projection():
    # A projection that forgets max_count² under-projects the counts
    # kernel's proven per-dispatch increment: the conversion would fire
    # late.
    spec = dataclasses.replace(
        counts_range_spec(1, 64, 8),
        projection=lambda rows, max_count: rows,
    )
    audit = audit_range_kernel(spec)
    assert _rule_ids(audit) == ["GR005"]
    assert "fire late" in audit.findings[0].detail


def test_cli_exit_codes(capsys):
    from spark_examples_tpu.check import cli

    assert cli.main(["ranges", "--mesh", "1,2"]) == 0
    capsys.readouterr()
    assert cli.main(["ranges", "--json", "--mesh", "1,2"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["tool"] == "graftcheck-ranges"
    assert doc["ok"] is True
    assert cli.main(["ranges", "--mesh", "bogus"]) == 2


# --------------------------------------------------------------------------
# graftcheck plan: exactness facts + accept/reject matrix.
# --------------------------------------------------------------------------


def _plan(args, devices=1):
    conf = PcaConf.parse(args)
    return validate_plan(conf, plan_devices=devices)


def test_plan_reports_exactness_facts():
    report = _plan(["--num-samples", "64", "--references", "1:0:50000"])
    assert report.ok
    assert report.geometry["exactness_headroom_sites"] == {
        "float32": F32_WINDOW,
        "int32": INT32_WINDOW,
    }
    # 50000 bases / spacing 100 + 1 candidate sites.
    assert report.geometry["gramian_entry_bound"] == 501
    assert any("range audit" in line for line in report.shape_checks)


def test_plan_headroom_shrinks_with_duplicate_sets():
    report = _plan(
        [
            "--num-samples", "64", "--references", "1:0:50000;1:0:50000",
            "--variant-set-id", "a,a",
        ]
    )
    assert report.geometry["exactness_headroom_sites"]["float32"] == (
        F32_WINDOW // 4
    )


def test_plan_sharded_duplicate_ids_audits_counts_ring():
    # A sharded same-set-join config must prove the UNPACKED count-valued
    # ring path too (the kernel its flushes actually ride), not just the
    # packed-[0,1] ring.
    report = _plan(
        [
            "--num-samples", "64", "--references", "1:0:50000;1:0:50000",
            "--variant-set-id", "a,a", "--mesh-shape", "1,4",
            "--similarity-strategy", "sharded",
        ],
        devices=4,
    )
    assert report.ok, [i.format() for i in report.issues]
    assert any(
        "range audit (2 kernel(s))" in line for line in report.shape_checks
    )


def test_plan_exactness_boundary_geometry():
    # sites = span // 100 + 1; the int32 window is the exact boundary.
    at_window = (INT32_WINDOW - 1) * 100
    accept = _plan(
        [
            "--num-samples", "64",
            "--references", f"1:0:{at_window}",
            "--bases-per-partition", "1000000000000",
        ]
    )
    assert accept.ok, [i.format() for i in accept.issues]
    assert accept.geometry["gramian_entry_bound"] == INT32_WINDOW

    reject = _plan(
        [
            "--num-samples", "64",
            "--references", f"1:0:{at_window + 100}",
            "--bases-per-partition", "1000000000000",
        ]
    )
    assert not reject.ok
    assert any(i.code == "exactness-window" for i in reject.issues)


def test_plan_rejects_partial_past_f32_window():
    report = _plan(
        [
            "--num-samples", "8", "--references", "1:0:50000",
            "--block-size", str((1 << 24) + 8),
        ]
    )
    assert not report.ok
    assert any(i.code == "ranges-GR002" for i in report.issues)


def test_plan_file_source_has_no_static_entry_bound():
    report = _plan(
        [
            "--source", "file", "--input-files", "cohort.vcf",
            "--references", "1:0:50000",
        ]
    )
    assert report.ok
    assert report.geometry["gramian_entry_bound"] is None
    # Headroom facts exist regardless: they are dtype arithmetic.
    assert report.geometry["exactness_headroom_sites"]["int32"] > 0


def test_plan_exactness_cli_exit_2():
    from spark_examples_tpu.check import cli

    rc = cli.main(
        [
            "plan", "--num-samples", "64",
            "--references", f"1:0:{INT32_WINDOW * 100}",
            "--bases-per-partition", "1000000000000",
        ]
    )
    assert rc == 2


# --------------------------------------------------------------------------
# GC011: narrowing casts need a range justification.
# --------------------------------------------------------------------------


def _lint(src, relpath="ops/fixture.py"):
    return [
        (f.rule_id, f.line)
        for f in lint_source(textwrap.dedent(src), relpath)
        if f.rule_id == "GC011"
    ]


def test_gc011_registered():
    assert "GC011" in RULES
    assert RULES["GC011"].applies_to("ops/gramian.py")
    assert not RULES["GC011"].applies_to("sources/files.py")


def test_gc011_flags_unjustified_narrowing_cast():
    assert _lint(
        """
        import jax.numpy as jnp
        def f(x):
            return x.astype(jnp.int8)
        """
    ) == [("GC011", 4)]


def test_gc011_range_comment_and_contract_reference_justify():
    assert _lint(
        """
        import jax.numpy as jnp
        def f(x):
            # range: x is a {0,1} membership bit
            return x.astype(jnp.uint8)
        def g(x):
            # values declared in ops/contracts.py:HAS_VARIATION
            return x.astype(jnp.uint8)
        """
    ) == []


def test_gc011_convert_element_type_spelling():
    assert _lint(
        """
        import jax.numpy as jnp
        from jax import lax
        def f(x):
            return lax.convert_element_type(x, jnp.int16)
        """
    ) == [("GC011", 5)]


def test_gc011_skips_dynamic_and_wide_targets():
    assert _lint(
        """
        import jax.numpy as jnp
        def f(x, operand_dtype):
            a = x.astype(operand_dtype)
            b = x.astype(jnp.float64)
            return a, b
        """
    ) == []


def test_gc011_scope_and_escape_hatch():
    src = """
    import jax.numpy as jnp
    def f(x):
        return x.astype(jnp.int8)
    """
    assert _lint(src, relpath="sources/fixture.py") == []
    hatched = """
    import jax.numpy as jnp
    def f(x):
        return x.astype(jnp.int8)  # graftcheck: disable=GC011 -- fixture
    """
    assert _lint(hatched) == []


def test_shipped_tree_lints_clean():
    from spark_examples_tpu.check.cli import _default_lint_root
    from spark_examples_tpu.check.linter import lint_paths

    findings, checked = lint_paths([_default_lint_root()])
    assert checked > 40
    assert findings == [], "\n".join(f.format() for f in findings)


# --------------------------------------------------------------------------
# --check-ranges runtime sampling + manifest block.
# --------------------------------------------------------------------------


def test_check_ranges_sampling_measured_within_bound():
    from spark_examples_tpu.obs.metrics import (
        GRAMIAN_ENTRY_MAX,
        GRAMIAN_STATIC_ENTRY_BOUND,
        MetricsRegistry,
    )
    from spark_examples_tpu.ops.gramian import GramianAccumulator

    registry = MetricsRegistry()
    acc = GramianAccumulator(
        8, block_size=4, check_ranges=True, registry=registry
    )
    rng = np.random.RandomState(0)
    acc.add_rows((rng.rand(32, 8) > 0.5).astype(np.uint8))
    acc.finalize()
    measured = registry.value(GRAMIAN_ENTRY_MAX)
    bound = registry.value(GRAMIAN_STATIC_ENTRY_BOUND)
    assert measured is not None and measured > 0
    assert bound == acc._entry_bound
    assert measured <= bound
    assert acc.telemetry.entry_max_seen == measured


def test_check_ranges_off_registers_nothing():
    from spark_examples_tpu.obs.metrics import (
        GRAMIAN_ENTRY_MAX,
        MetricsRegistry,
    )
    from spark_examples_tpu.ops.gramian import GramianAccumulator

    registry = MetricsRegistry()
    acc = GramianAccumulator(8, block_size=4, registry=registry)
    acc.add_rows(np.ones((8, 8), dtype=np.uint8))
    acc.finalize()
    assert registry.value(GRAMIAN_ENTRY_MAX) is None


def test_manifest_gramian_exactness_block_and_validation():
    from spark_examples_tpu.obs.manifest import (
        build_manifest,
        build_run_manifest,
        validate_manifest,
    )
    from spark_examples_tpu.obs.metrics import (
        GRAMIAN_ENTRY_MAX,
        GRAMIAN_STATIC_ENTRY_BOUND,
        MetricsRegistry,
        well_known_gauge,
    )

    # Absent without sampling (v2-additive: existing manifests unchanged).
    doc = build_manifest()
    assert doc["gramian_exactness"] is None
    assert validate_manifest(doc) == []

    registry = MetricsRegistry()
    well_known_gauge(registry, GRAMIAN_ENTRY_MAX).set(142)
    well_known_gauge(registry, GRAMIAN_STATIC_ENTRY_BOUND).set(335)
    doc = build_run_manifest(registry=registry)
    assert doc["gramian_exactness"] == {
        "entry_max": 142,
        "static_entry_bound": 335,
    }
    assert validate_manifest(doc) == []

    bad = build_manifest(gramian_exactness={"entry_max": -1})
    errors = validate_manifest(bad)
    assert any("entry_max" in e for e in errors)
    assert any("static_entry_bound" in e for e in errors)


def test_check_ranges_e2e_driver_run():
    """The runtime half end to end: a packed-ingest driver run with
    --check-ranges records measured <= proven in its own registry — the
    pair the obs smoke asserts from the manifest."""
    from spark_examples_tpu.obs.manifest import (
        build_run_manifest,
        validate_manifest,
    )
    from spark_examples_tpu.pipeline import pca_driver

    conf = PcaConf(
        num_samples=8,
        block_size=8,
        references="1:0:30000",
        check_ranges=True,
        ingest="packed",
    )
    driver = pca_driver.VariantsPcaDriver(conf)
    similarity = pca_driver._similarity_stage(
        conf, driver, use_device=False, use_packed=True
    )
    driver.compute_pca(similarity)
    doc = build_run_manifest(conf=conf, registry=driver.registry)
    assert validate_manifest(doc) == []
    ge = doc["gramian_exactness"]
    assert ge is not None
    assert 0 < ge["entry_max"] <= ge["static_entry_bound"]


# --------------------------------------------------------------------------
# The bounded packed block stream (hostmem inventory shrink): identical
# stats and output, one fewer declared_unbounded site.
# --------------------------------------------------------------------------


def test_packed_stream_stats_and_inventory():
    from spark_examples_tpu.check.hostmem import (
        audit_paths,
        default_hostmem_paths,
    )
    from spark_examples_tpu.obs.metrics import INGEST_PARTITIONS_DONE
    from spark_examples_tpu.pipeline import pca_driver

    report = audit_paths(default_hostmem_paths())
    assert report.ok
    # The per-window list(genotype_blocks) site is GONE from the declared
    # inventory: the packed path now iterates blocks boundedly.
    assert "pipeline/pca_driver.py" not in {d.path for d in report.declared}

    conf = PcaConf(num_samples=8, block_size=8, references="1:0:30000")
    driver = pca_driver.VariantsPcaDriver(conf)
    pca_driver._similarity_stage(conf, driver, use_device=False, use_packed=True)
    stats = driver.io_stats.as_dict()
    assert stats["partitions"] > 0
    assert stats["variants"] > 0
    assert stats["requests"] > 0
    # The bounded stream now reports live window progress.
    done = driver.registry.value(INGEST_PARTITIONS_DONE)
    planned = driver.registry.value("ingest_partitions_planned")
    assert done == planned > 0
