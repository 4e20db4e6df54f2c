"""The graftcheck IR auditor (``check/ir.py``) and lock-order analysis
(``check/lockgraph.py``): golden jaxpr audits of the shipped kernels across
mesh shapes (aligned + ragged cohorts), deliberately-broken kernel fixtures
that each GI rule must flag, the lock-graph's clean-tree gate, broken lock
fixtures per GL rule, DOT artifact emission, and CLI exit codes.

Broken ring kernels are built inline with the same shard_map/AbstractMesh
machinery as the real ``ops/gramian.py:build_sharded_update``, each with
exactly one contract defect, so the audit's discrimination (not just its
acceptance) is pinned.
"""

import os
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from spark_examples_tpu.check.ir import (
    DonationSite,
    KernelSpec,
    audit_kernel,
    counts_kernel_spec,
    default_specs,
    dense_kernel_spec,
    devicegen_ring_spec,
    gc005_justified_functions,
    peak_live_bytes,
    ring_kernel_spec,
    run_audit,
)
from spark_examples_tpu.check.lockgraph import (
    build_lock_graph,
    default_lock_paths,
)
from spark_examples_tpu.ops.gramian import _unpack_bits
from spark_examples_tpu.parallel.mesh import (
    DATA_AXIS,
    SAMPLES_AXIS,
    padded_cohort,
    ring_traffic_bytes,
)
from jax import shard_map

_PACKAGE_DIR = os.path.dirname(
    os.path.abspath(__import__("spark_examples_tpu").__file__)
)


def _rule_ids(audit):
    return sorted({f.rule_id for f in audit.findings})


# --------------------------------------------------------------------------
# Golden audits: the shipped kernels must prove every contract.
# --------------------------------------------------------------------------


@pytest.mark.parametrize("data,samples", [(1, 2), (1, 4), (2, 2)])
@pytest.mark.parametrize("num_samples", [64, 100])  # aligned + ragged
@pytest.mark.parametrize("pack", [True, False])
def test_ring_kernel_audits_clean(data, samples, num_samples, pack):
    audit = audit_kernel(
        ring_kernel_spec(data, samples, num_samples, 8, pack)
    )
    assert audit.ok, "\n".join(f.format() for f in audit.findings)
    assert audit.facts["permute_executions"] == samples - 1
    assert audit.facts["ring_overlap_independent"]
    assert not audit.facts["accumulator_donated"]
    assert audit.facts["gc005_disable_present"]
    assert audit.facts["f64_free"]
    # The jaxpr-derived traffic equals the ONE audited formula exactly.
    padded = padded_cohort(num_samples, samples, pack=pack)
    assert audit.facts["ring_bytes_jaxpr"] == ring_traffic_bytes(
        data * 8, samples, padded // samples, pack
    )
    assert audit.facts["peak_live_bytes"] > 0
    assert audit.facts["liveness_scope"] == "per-device"


@pytest.mark.parametrize("data", [1, 2])
def test_dense_kernels_audit_clean(data):
    for spec in (
        dense_kernel_spec(data, 64, 8),
        counts_kernel_spec(data, 64, 8),
    ):
        audit = audit_kernel(spec)
        assert audit.ok, "\n".join(f.format() for f in audit.findings)
        assert not audit.facts["accumulator_donated"]
        assert audit.facts["gc005_disable_present"]


@pytest.mark.parametrize("data,samples", [(1, 2), (1, 4), (2, 2)])
def test_devicegen_ring_audits_clean(data, samples):
    K, B = 2, 8
    audit = audit_kernel(devicegen_ring_spec(data, samples, 64, B, K))
    assert audit.ok, "\n".join(f.format() for f in audit.findings)
    # K half-ring passes per dispatch: K x S//2 permutes, and the traced
    # bytes equal the accumulator's own per-dispatch accounting
    # (DeviceGenRingGramianAccumulator.ring_bytes_total's formula).
    assert audit.facts["permute_executions"] == K * (samples // 2)
    assert audit.facts["ring_overlap_independent"]
    padded = padded_cohort(64, samples, pack=True)
    assert audit.facts["ring_bytes_jaxpr"] == ring_traffic_bytes(
        data * K * B, samples, padded // samples, True, samples // 2
    )
    # G is not donated (the dispatch loop bounds its queued copies), and
    # the ring program's GC005 disable says so.
    assert not audit.facts["accumulator_donated"]
    assert audit.facts["gc005_disable_present"]


def test_devicegen_ring_donated_under_its_disable_flags_gi002(monkeypatch):
    """The ring program jitted with G donated while ``_ring_update`` still
    carries its GC005 non-donation disable: the AST and IR layers have
    drifted, and GI002 says so."""
    from spark_examples_tpu.ops import devicegen

    built = devicegen._ring_update.__wrapped__

    def donated(*args, **kwargs):
        return jax.jit(built(*args, **kwargs).__wrapped__, donate_argnums=(0,))

    monkeypatch.setattr(devicegen._ring_update, "__wrapped__", donated)
    audit = audit_kernel(devicegen_ring_spec(1, 4, 64, 8, 2))
    assert audit.facts["accumulator_donated"]
    assert _rule_ids(audit) == ["GI002"]
    assert "drifted" in audit.findings[0].detail


def test_default_matrix_clean_and_device_free():
    before = len(jax.live_arrays())
    report = run_audit(default_specs(num_samples=32, ragged_samples=52,
                                     block_size=8, meshes=((1, 2), (2, 2))))
    assert report.ok, report.format()
    assert len(report.audits) >= 8
    # Pure tracing: no device buffer outlives the audit.
    assert len(jax.live_arrays()) == before


def test_report_json_schema():
    import json

    report = run_audit([ring_kernel_spec(1, 2, 32, 4, True)])
    doc = json.loads(report.to_json())
    assert doc["tool"] == "graftcheck-ir"
    assert doc["ok"] is True
    assert doc["kernel_count"] == 1
    [kernel] = doc["kernels"]
    assert kernel["facts"]["ring_bytes_jaxpr"] == kernel["facts"][
        "ring_bytes_formula"
    ]


def test_gc005_cross_check_reads_the_real_disables():
    names = gc005_justified_functions(
        os.path.join(_PACKAGE_DIR, "ops", "gramian.py")
    )
    assert {"_dense_update", "_dense_update_counts", "update"} <= names
    names_dg = gc005_justified_functions(
        os.path.join(_PACKAGE_DIR, "ops", "devicegen.py")
    )
    assert "_ring_update" in names_dg


def test_peak_live_bytes_is_deterministic_and_bounded_below():
    def f(a, b):
        c = a @ b
        return c + 1.0

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
    )
    peak = peak_live_bytes(closed.jaxpr)
    # At least the two inputs plus one output buffer must coexist.
    assert peak >= 3 * 64 * 64 * 4
    assert peak == peak_live_bytes(closed.jaxpr)


# --------------------------------------------------------------------------
# Broken-kernel fixtures: one defect each, the right GI rule must fire.
# --------------------------------------------------------------------------


def _fixture_update(kernel_body, packed_width):
    """A jitted shard_map update over an abstract 1x4 mesh whose per-slice
    body is ``kernel_body(G_local, X_local)`` — the same harness the real
    builder uses, with the defect injected in the body."""
    from jax.sharding import AbstractMesh, PartitionSpec as P

    mesh = AbstractMesh((1, 4), (DATA_AXIS, SAMPLES_AXIS))
    g_spec = P(DATA_AXIS, SAMPLES_AXIS, None)
    x_spec = P(DATA_AXIS, None, SAMPLES_AXIS)

    @jax.jit
    def update(G, X):
        def per_slice(G_local, X_local):
            return kernel_body(G_local[0], X_local[0])[None]

        return shard_map(
            per_slice, mesh=mesh, in_specs=(g_spec, x_spec), out_specs=g_spec
        )(G, X)

    G = jax.ShapeDtypeStruct((1, 64, 64), jnp.float32)
    X = jax.ShapeDtypeStruct((1, 8, packed_width), jnp.uint8)
    return update, (G, X)


def _fixture_spec(name, kernel_body, packed_width=8, tmp_module=None,
                  **overrides):
    spec_kwargs = dict(
        name=name,
        build=lambda: _fixture_update(kernel_body, packed_width),
        samples_axis=4,
        total_devices=4,
        packed=True,
        ring=True,
        ring_passes=1,
        rows_per_call=8,
        n_local=16,
        acc_invar=0,
        donation=tmp_module,
    )
    spec_kwargs.update(overrides)
    return KernelSpec(**spec_kwargs)


def _justified_module(tmp_path):
    """A fixture module whose `update` carries the GC005 justification, so
    broken-kernel specs isolate their own defect from GI002."""
    mod = tmp_path / "fixture_kernels.py"
    mod.write_text(
        "def update(G, X):  # graftcheck: disable=GC005 -- fixture\n"
        "    return G\n"
    )
    return DonationSite(str(mod), "update", "fixture_kernels.py")


def _dot_into(G, tile, k, i, D, n_local, operand=jnp.float32):
    j = (i + k) % D
    x_mine = _unpack_bits_t(tile)
    col = (j * n_local).astype(jnp.int32)
    zero = jnp.int32(0)
    t = jnp.matmul(
        x_mine.T, x_mine, preferred_element_type=G.dtype
    )
    return lax.dynamic_update_slice(
        G,
        lax.dynamic_slice(G, (zero, col), (n_local, n_local)) + t,
        (zero, col),
    )


def _unpack_bits_t(tile):
    return _unpack_bits(tile, tile.shape[-1] * 8).astype(jnp.float32)


def test_serialized_ring_flags_gi001(tmp_path):
    """The old pattern — permute first, dot on the permuted tile — has the
    dot waiting on the transfer every step."""

    def body_serialized(G_local, X_cols):
        D = 4
        i = lax.axis_index(SAMPLES_AXIS)
        n_local = X_cols.shape[1] * 8
        perm = [((p + 1) % D, p) for p in range(D)]

        def body(k, carry):
            G, cur = carry
            nxt = lax.ppermute(cur, SAMPLES_AXIS, perm)
            return _dot_into(G, nxt, k + 1, i, D, n_local), nxt

        G_local = _dot_into(G_local, X_cols, 0, i, D, n_local)
        G_local, _ = lax.fori_loop(0, D - 1, body, (G_local, X_cols))
        return G_local

    audit = audit_kernel(
        _fixture_spec(
            "fixture-serialized", body_serialized,
            tmp_module=_justified_module(tmp_path),
        )
    )
    assert "GI001" in _rule_ids(audit)
    assert not audit.facts["ring_overlap_independent"]


def test_extra_permute_flags_gi006(tmp_path):
    """A correct double-buffered loop run for D steps instead of D-1 pays
    one wasted tile circulation per block."""

    def body_extra(G_local, X_cols):
        D = 4
        i = lax.axis_index(SAMPLES_AXIS)
        n_local = X_cols.shape[1] * 8
        perm = [((p + 1) % D, p) for p in range(D)]

        def body(k, carry):
            G, cur = carry
            nxt = lax.ppermute(cur, SAMPLES_AXIS, perm)
            return _dot_into(G, cur, k, i, D, n_local), nxt

        G_local, _ = lax.fori_loop(0, D, body, (G_local, X_cols))
        return G_local

    audit = audit_kernel(
        _fixture_spec(
            "fixture-extra-permute", body_extra,
            tmp_module=_justified_module(tmp_path),
        )
    )
    assert "GI006" in _rule_ids(audit)
    assert audit.facts["permute_executions"] == 4


def test_unpacked_wire_flags_gi003(tmp_path):
    """Unpacking BEFORE the ring circulates f32 tiles — 32x the ICI bytes
    the packed wire format promises."""

    def body_unpacked_wire(G_local, X_cols):
        D = 4
        i = lax.axis_index(SAMPLES_AXIS)
        n_local = X_cols.shape[1] * 8
        perm = [((p + 1) % D, p) for p in range(D)]
        wire = _unpack_bits_t(X_cols)  # f32 (B, n_local) on the wire

        def dot_wide(G, tile, k):
            j = (i + k) % D
            col = (j * n_local).astype(jnp.int32)
            zero = jnp.int32(0)
            t = jnp.matmul(tile.T, tile, preferred_element_type=G.dtype)
            return lax.dynamic_update_slice(
                G,
                lax.dynamic_slice(G, (zero, col), (n_local, n_local)) + t,
                (zero, col),
            )

        def body(k, carry):
            G, cur = carry
            nxt = lax.ppermute(cur, SAMPLES_AXIS, perm)
            return dot_wide(G, cur, k), nxt

        G_local, last = lax.fori_loop(0, D - 1, body, (G_local, wire))
        return dot_wide(G_local, last, D - 1)

    audit = audit_kernel(
        _fixture_spec(
            "fixture-unpacked-wire", body_unpacked_wire,
            tmp_module=_justified_module(tmp_path),
        )
    )
    assert "GI003" in _rule_ids(audit)


def test_chatty_ring_flags_gi005(tmp_path):
    """Circulating a double-width tile moves 2x the formula's bytes while
    keeping dtype, count, and overlap intact — only GI005 may fire."""

    def body_chatty(G_local, X_cols):
        D = 4
        i = lax.axis_index(SAMPLES_AXIS)
        n_local = X_cols.shape[1] * 8
        perm = [((p + 1) % D, p) for p in range(D)]
        fat = jnp.concatenate([X_cols, X_cols], axis=1)

        def body(k, carry):
            G, cur = carry
            nxt = lax.ppermute(cur, SAMPLES_AXIS, perm)
            tile = cur[:, : cur.shape[1] // 2]
            return _dot_into(G, tile, k, i, D, n_local), nxt

        G_local, last = lax.fori_loop(0, D - 1, body, (G_local, fat))
        return _dot_into(
            G_local, last[:, : last.shape[1] // 2], D - 1, i, D, n_local
        )

    audit = audit_kernel(
        _fixture_spec(
            "fixture-chatty", body_chatty,
            tmp_module=_justified_module(tmp_path),
        )
    )
    ids = _rule_ids(audit)
    assert "GI005" in ids
    assert "GI001" not in ids and "GI006" not in ids
    assert (
        audit.facts["ring_bytes_jaxpr"]
        == 2 * audit.facts["ring_bytes_formula"]
    )


def test_f64_promotion_flags_gi004(tmp_path):
    """A float64 intermediate inside the kernel body (the silent x64/weak
    promotion class)."""

    def body_f64(G_local, X_cols):
        x = _unpack_bits_t(X_cols)
        scale = jnp.sum(x.astype(jnp.float64)) * np.float64(1.0)
        return G_local + scale.astype(G_local.dtype)

    audit = audit_kernel(
        _fixture_spec(
            "fixture-f64", body_f64, ring=False, packed=False,
            tmp_module=_justified_module(tmp_path),
        )
    )
    assert "GI004" in _rule_ids(audit)
    assert not audit.facts["f64_free"]


def test_undonated_unjustified_flags_gi002(tmp_path):
    mod = tmp_path / "plain_kernels.py"
    mod.write_text("def plain_update(G, X):\n    return G\n")

    def build():
        fn = jax.jit(lambda G, X: G + X.astype(G.dtype).sum())
        return fn, (
            jax.ShapeDtypeStruct((8, 8), jnp.float32),
            jax.ShapeDtypeStruct((4, 8), jnp.uint8),
        )

    audit = audit_kernel(
        KernelSpec(
            name="fixture-undonated",
            build=build,
            acc_invar=0,
            donation=DonationSite(str(mod), "plain_update", "plain_kernels.py"),
        )
    )
    assert _rule_ids(audit) == ["GI002"]
    assert "NOT donated" in audit.findings[0].detail


def test_stale_disable_flags_gi002_drift(tmp_path):
    mod = tmp_path / "stale_kernels.py"
    mod.write_text(
        "def donated_update(G, X):"
        "  # graftcheck: disable=GC005 -- stale justification\n"
        "    return G\n"
    )

    def build():
        fn = jax.jit(
            lambda G, X: G + X.astype(G.dtype).sum(), donate_argnums=(0,)
        )
        return fn, (
            jax.ShapeDtypeStruct((8, 8), jnp.float32),
            jax.ShapeDtypeStruct((4, 8), jnp.float32),
        )

    audit = audit_kernel(
        KernelSpec(
            name="fixture-stale-disable",
            build=build,
            acc_invar=0,
            donation=DonationSite(
                str(mod), "donated_update", "stale_kernels.py"
            ),
        )
    )
    assert _rule_ids(audit) == ["GI002"]
    assert "drifted" in audit.findings[0].detail


def test_trace_failure_flags_gi000():
    def build():
        raise ValueError("fixture cannot build")

    audit = audit_kernel(KernelSpec(name="fixture-boom", build=build))
    assert _rule_ids(audit) == ["GI000"]


# --------------------------------------------------------------------------
# Lock-order analysis.
# --------------------------------------------------------------------------


def test_tree_lock_graph_is_acyclic_and_clean():
    graph = build_lock_graph(default_lock_paths())
    assert graph.ok, "\n".join(f.format() for f in graph.findings)
    assert graph.cycles() == []
    keys = set(graph.nodes)
    # The known ingest/telemetry locks are all discovered.
    assert "sources/files.py::FileGenomicsSource._lock" in keys
    assert "obs/metrics.py::MetricsRegistry._lock" in keys
    assert "obs/metrics.py::_Family._lock" in keys
    assert "obs/metrics.py::_Child._lock" in keys
    assert "obs/spans.py::SpanRecorder._lock" in keys
    # The one real ordering edge: registry lock held while a new family's
    # constructor takes the family lock.
    assert (
        "obs/metrics.py::MetricsRegistry._lock",
        "obs/metrics.py::_Family._lock",
    ) in graph.edges


def test_lock_graph_dot_artifact():
    graph = build_lock_graph(default_lock_paths())
    dot = graph.to_dot()
    assert dot.startswith("digraph lock_order {")
    assert '"obs/metrics.py::MetricsRegistry._lock"' in dot
    assert "->" in dot


_BROKEN_LOCKS = textwrap.dedent(
    """
    import threading
    import queue
    import jax

    work_queue = queue.Queue()

    class Broken:
        def __init__(self):
            self._lock = threading.Lock()
            self._other_lock = threading.Lock()

        def forward(self):
            with self._lock:
                with self._other_lock:
                    pass

        def backward(self):
            with self._other_lock:
                with self._lock:
                    pass

        def sync_under_lock(self, x):
            with self._lock:
                jax.block_until_ready(x)

        def put_under_lock(self, item):
            with self._lock:
                work_queue.put(item)

        def reacquire(self):
            with self._lock:
                self.helper()

        def helper(self):
            with self._lock:
                pass
    """
)


def test_broken_lock_fixture_flags_every_gl_rule(tmp_path):
    mod = tmp_path / "broken_locks.py"
    mod.write_text(_BROKEN_LOCKS)
    graph = build_lock_graph([str(mod)])
    ids = {f.rule_id for f in graph.findings}
    assert ids == {"GL001", "GL002", "GL003", "GL004"}
    assert len(graph.cycles()) == 1
    by_rule = {f.rule_id: f for f in graph.findings}
    assert by_rule["GL002"].line == 25  # the block_until_ready line
    assert by_rule["GL003"].line == 29  # the work_queue.put line
    # Cycle names both member locks.
    assert "Broken._lock" in by_rule["GL001"].detail
    assert "Broken._other_lock" in by_rule["GL001"].detail


def test_lockgraph_escape_hatch(tmp_path):
    src = textwrap.dedent(
        """
        import threading
        import jax

        class Holder:
            def __init__(self):
                self._lock = threading.Lock()

            def sync(self, x):
                with self._lock:
                    jax.block_until_ready(x)  # graftcheck: disable=GL002 -- startup-only path, measured
        """
    )
    mod = tmp_path / "justified_locks.py"
    mod.write_text(src)
    graph = build_lock_graph([str(mod)])
    assert graph.ok, "\n".join(f.format() for f in graph.findings)


def test_annotated_and_class_level_locks_are_visible(tmp_path):
    """`x: Lock = threading.Lock()` (the strict-typing idiom) and
    class-body lock attributes must register exactly like the plain form —
    an invisible lock silently disables every GL rule for it."""
    src = textwrap.dedent(
        """
        import threading
        import jax

        class Annotated:
            _shared_lock = threading.Lock()

            def __init__(self):
                self._lock: threading.Lock = threading.Lock()

            def sync(self, x):
                with self._lock:
                    jax.block_until_ready(x)

            def shared_sync(self, x):
                with self._shared_lock:
                    jax.block_until_ready(x)
        """
    )
    mod = tmp_path / "annotated_locks.py"
    mod.write_text(src)
    graph = build_lock_graph([str(mod)])
    assert "annotated_locks.py::Annotated._lock" in graph.nodes
    assert "annotated_locks.py::Annotated._shared_lock" in graph.nodes
    assert [f.rule_id for f in graph.findings] == ["GL002", "GL002"]


def test_closure_calls_resolve_in_the_lock_graph(tmp_path):
    """Locks acquired inside a nested def must flow to a caller holding
    another lock — the closures-handed-to-pools case the scanner registers
    nested functions for."""
    src = textwrap.dedent(
        """
        import threading

        class Pool:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def drive(self):
                def flush():
                    with self._b_lock:
                        pass

                with self._a_lock:
                    flush()
        """
    )
    mod = tmp_path / "closure_locks.py"
    mod.write_text(src)
    graph = build_lock_graph([str(mod)])
    assert (
        "closure_locks.py::Pool._a_lock",
        "closure_locks.py::Pool._b_lock",
    ) in graph.edges


def test_module_level_lock_resolves_through_attr_reference(tmp_path):
    """`with holder.shared_lock:` against a module-level lock in another
    analyzed module must resolve (the '::' in the key must not defeat the
    attribute-name match)."""
    pkg = tmp_path / "lockpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "shared.py").write_text(
        "import threading\n\nshared_lock = threading.Lock()\n"
    )
    (pkg / "user.py").write_text(
        textwrap.dedent(
            """
            import threading
            from lockpkg import shared

            class User:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self):
                    with self._lock:
                        with shared.shared_lock:
                            pass
            """
        )
    )
    graph = build_lock_graph([str(pkg)])
    assert "shared.py::shared_lock" in graph.nodes
    assert (
        "user.py::User._lock",
        "shared.py::shared_lock",
    ) in graph.edges


def test_lockgraph_cli_rejects_unwritable_dot(tmp_path):
    from spark_examples_tpu.check.cli import main

    assert (
        main(["lockgraph", "--dot", str(tmp_path / "no_dir" / "g.dot")]) == 2
    )


def test_acquire_without_with_still_orders(tmp_path):
    src = textwrap.dedent(
        """
        import threading

        a_lock = threading.Lock()

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                a_lock.acquire()
                with self._lock:
                    pass
                a_lock.release()
        """
    )
    mod = tmp_path / "acquired.py"
    mod.write_text(src)
    graph = build_lock_graph([str(mod)])
    assert (
        "acquired.py::a_lock",
        "acquired.py::C._lock",
    ) in graph.edges


# --------------------------------------------------------------------------
# CLI exit codes.
# --------------------------------------------------------------------------


def test_cli_ir_and_lockgraph(tmp_path):
    from spark_examples_tpu.check.cli import main

    assert (
        main(["ir", "--mesh", "1,2", "--num-samples", "16",
              "--block-size", "4"])
        == 0
    )
    assert main(["ir", "--mesh", "bogus"]) == 2
    dot = tmp_path / "lockorder.dot"
    assert main(["lockgraph", "--dot", str(dot)]) == 0
    assert dot.read_text().startswith("digraph lock_order {")
    assert main(["lockgraph", str(tmp_path / "missing")]) == 2
    broken = tmp_path / "broken_locks.py"
    broken.write_text(_BROKEN_LOCKS)
    assert main(["lockgraph", str(broken)]) == 1
