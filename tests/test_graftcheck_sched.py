"""graftcheck sched: the device-free collective-schedule prover, the
hierarchical two-level ring, and their satellites.

Covers: the closed-form traffic properties (monotonicity, exact pack
ratio, hier-DCN-below-flat for every multi-host topology), topology
grammar, schedule extraction/simulation over the shipped matrix, every GS
rule via a broken or mis-selected subject, the hierarchical kernel's
runtime parity against the flat ring (byte-identical on 8 virtual
devices), the two-radix ranges refinement, the plan validator's
``--topology``/``--sched-budget-seconds`` accept/reject matrix, the
manifest ``schedule`` block, the zero-live-arrays contract, and the
retired checkpoint-compute streaming path.
"""

import json

import numpy as np
import pytest

import jax

from spark_examples_tpu.parallel.mesh import (
    DEFAULT_DCN_BYTES_PER_S,
    DEFAULT_ICI_BYTES_PER_S,
    Topology,
    flat_traffic_split,
    hierarchical_mesh,
    hierarchical_traffic_bytes,
    make_mesh,
    parse_topology,
    resolve_hier_hosts,
    resolve_reduce_schedule,
    ring_traffic_bytes,
)
from spark_examples_tpu.check.sched import (
    DEFAULT_TOPOLOGIES,
    audit_schedule,
    extract_schedule,
    run_audit,
    schedule_kernel_spec,
)


# --------------------------------------------------------------------------
# Closed-form traffic properties (the formula layer the GS rules enforce).
# --------------------------------------------------------------------------


class TestTrafficFormulas:
    def test_hier_total_equals_flat_total(self):
        # The hierarchical schedule moves the SAME bytes as the flat ring
        # — it proves their placement, it does not shrink them.
        for hosts, per_host in DEFAULT_TOPOLOGIES:
            s = hosts * per_host
            for packed in (True, False):
                level = hierarchical_traffic_bytes(64, hosts, per_host, 16, packed)
                assert level.total == ring_traffic_bytes(64, s, 16, packed)

    def test_hier_dcn_strictly_below_flat_for_multihost(self):
        # The acceptance property: on EVERY hosts>1 topology of the
        # matrix, hier puts strictly fewer bytes on the slow link than
        # the flat ring's provable bound.
        for hosts, per_host in DEFAULT_TOPOLOGIES:
            if hosts == 1:
                continue
            topo = Topology(hosts, per_host)
            for packed in (True, False):
                hier = hierarchical_traffic_bytes(
                    64, hosts, per_host, 16, packed
                )
                flat = flat_traffic_split(64, topo, 16, packed)
                assert hier.dcn_bytes < flat.dcn_bytes, (hosts, per_host)
                assert flat.ici_bytes == 0  # nothing provably intra-host

    def test_single_host_rides_ici_only(self):
        topo = Topology(1, 4)
        flat = flat_traffic_split(64, topo, 16, True)
        hier = hierarchical_traffic_bytes(64, 1, 4, 16, True)
        assert flat.dcn_bytes == 0 and hier.dcn_bytes == 0
        assert flat.ici_bytes == hier.ici_bytes > 0

    def test_monotone_in_sites_and_devices(self):
        base = hierarchical_traffic_bytes(64, 4, 8, 16, True)
        assert (
            hierarchical_traffic_bytes(128, 4, 8, 16, True).total
            > base.total
        )
        assert (
            hierarchical_traffic_bytes(64, 8, 8, 16, True).total
            > base.total
        )
        assert (
            hierarchical_traffic_bytes(64, 4, 16, 16, True).total
            > base.total
        )
        assert ring_traffic_bytes(128, 8, 16, True) > ring_traffic_bytes(
            64, 8, 16, True
        )
        assert ring_traffic_bytes(64, 16, 16, True) > ring_traffic_bytes(
            64, 8, 16, True
        )

    def test_exact_pack_ratio(self):
        # n_local a multiple of 8 -> the packed wire moves EXACTLY 1/8.
        assert ring_traffic_bytes(64, 8, 16, False) == 8 * ring_traffic_bytes(
            64, 8, 16, True
        )
        packed = hierarchical_traffic_bytes(64, 4, 8, 16, True)
        unpacked = hierarchical_traffic_bytes(64, 4, 8, 16, False)
        assert unpacked.ici_bytes == 8 * packed.ici_bytes
        assert unpacked.dcn_bytes == 8 * packed.dcn_bytes

    def test_topology_grammar(self):
        topo = parse_topology("32,8")
        assert (topo.hosts, topo.devices_per_host, topo.devices) == (32, 8, 256)
        assert topo.ici_bytes_per_s == DEFAULT_ICI_BYTES_PER_S
        assert topo.dcn_bytes_per_s == DEFAULT_DCN_BYTES_PER_S
        for bad in ("32", "a,b", "1,2,3", ""):
            with pytest.raises(ValueError):
                parse_topology(bad)
        with pytest.raises(ValueError):
            Topology(0, 4)
        with pytest.raises(ValueError):
            Topology(2, 2, ici_bytes_per_s=0)

    def test_resolve_reduce_schedule(self):
        assert resolve_reduce_schedule("auto", 1) == "flat"
        assert resolve_reduce_schedule("auto", 4) == "hier"
        assert resolve_reduce_schedule("flat", 4) == "flat"
        assert resolve_reduce_schedule("hier", 1) == "hier"
        with pytest.raises(ValueError):
            resolve_reduce_schedule("ring", 2)

    def test_resolve_hier_hosts(self, monkeypatch):
        assert resolve_hier_hosts(8, 2) == 2
        with pytest.raises(ValueError):
            resolve_hier_hosts(8, 3)  # must divide
        monkeypatch.setenv("SPARK_EXAMPLES_TPU_HIER_HOSTS", "4")
        assert resolve_hier_hosts(8) == 4


# --------------------------------------------------------------------------
# Schedule extraction + simulation over the shipped matrix.
# --------------------------------------------------------------------------


class TestSchedMatrix:
    def test_default_matrix_proves_clean(self):
        report = run_audit()
        assert report.ok, "\n".join(f.format() for f in report.findings)
        # Every multi-host topology carries its hier-vs-flat comparison
        # for BOTH ring kernels (the host-fed Gramian ring and the fused
        # generation ring), hier strictly below on the slow link.
        multihost = [t for t in DEFAULT_TOPOLOGIES if t[0] > 1]
        assert len(report.comparisons) == 2 * len(multihost)
        kernels = {comp.get("kernel") for comp in report.comparisons}
        assert kernels == {"gramian", "devicegen"}
        for comp in report.comparisons:
            assert comp["hier_strictly_below"], comp
            assert comp["dcn_reduction"] > 1.0

    def test_flat_simulation_matches_formula_exactly(self):
        # GS002's clean side, asserted directly: the simulated flat
        # schedule reproduces ring_traffic_bytes byte for byte — the full
        # ring's S-1 permutes for the host-fed kernel, the half ring's S//2
        # for the device-generation kernel (2 ring passes per call).
        for hosts, per_host in DEFAULT_TOPOLOGIES:
            topo = Topology(hosts, per_host)
            for kernel, permutes, passes in (
                ("gramian", topo.devices - 1, 1),
                ("devicegen", topo.devices // 2, 2),
            ):
                audit = audit_schedule(
                    topo, "flat", selected=False, kernel=kernel
                )
                assert audit.ok, [f.format() for f in audit.findings]
                total = audit.facts["ici_bytes"] + audit.facts["dcn_bytes"]
                assert total == ring_traffic_bytes(
                    audit.facts["rows_per_call"],
                    topo.devices,
                    schedule_kernel_spec(
                        topo, "flat", 64, 8, kernel=kernel
                    ).n_local,
                    True,
                    permutes,
                )
                steps = audit.facts["ici_steps"] + audit.facts["dcn_steps"]
                assert steps == passes * permutes

    def test_hier_per_level_bytes_and_steps(self):
        topo = Topology(4, 8)
        audit = audit_schedule(topo, "hier")
        assert audit.ok
        level = hierarchical_traffic_bytes(
            audit.facts["rows_per_call"], 4, 8,
            schedule_kernel_spec(topo, "hier", 64, 8).n_local, True,
        )
        assert audit.facts["ici_bytes"] == level.ici_bytes
        assert audit.facts["dcn_bytes"] == level.dcn_bytes
        # Per-device step counts: H*(D-1) inner + (H-1) outer = S-1.
        assert audit.facts["ici_steps"] == 4 * 7
        assert audit.facts["dcn_steps"] == 3

    def test_critical_path_scales_linearly_with_rows(self):
        topo = Topology(4, 8)
        spec = schedule_kernel_spec(topo, "hier", 64, 8)
        from spark_examples_tpu.check.ir import trace_kernel

        sched = extract_schedule(trace_kernel(spec), spec, topo, "hier")
        one = sched.critical_path_seconds()
        assert sched.critical_path_seconds(sched.rows_per_call * 10) == (
            pytest.approx(one * 10)
        )
        # Overlap proven on both levels -> critical path is the slower
        # level, not the sum.
        seconds = sched.link_seconds()
        assert sched.critical_path_seconds() == max(seconds.values())

    def test_zero_live_arrays_after_audit(self):
        before = len(jax.live_arrays())
        run_audit(topologies=((2, 2), (1, 2)))
        assert len(jax.live_arrays()) == before


# --------------------------------------------------------------------------
# The GS rules, one broken/mis-selected subject each.
# --------------------------------------------------------------------------


def _serialized_hier_trace(hosts, per_host, num_samples, block_size):
    """A two-level ring whose dots CONSUME the just-permuted tile (the
    serialized anti-pattern): same geometry as the real kernel, so it can
    stand in as ``traced`` for GS003/GI001 fixtures."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from jax import shard_map
    from spark_examples_tpu.parallel.mesh import (
        DATA_AXIS,
        HOST_AXIS,
        SAMPLES_AXIS,
        RING_PACK_MULTIPLE,
        padded_cohort,
    )

    samples = hosts * per_host
    padded = padded_cohort(num_samples, samples, pack=True)
    n_local = padded // samples
    mesh = AbstractMesh(
        (1, hosts, per_host), (DATA_AXIS, HOST_AXIS, SAMPLES_AXIS)
    )

    from spark_examples_tpu.ops.gramian import _unpack_bits

    def per_slice(G_local, X_local):
        G, X = G_local[0], X_local[0]
        D = per_host
        H = hosts
        x_mine = _unpack_bits(X, n_local).astype(jnp.float32).T
        perm_d = [((p + 1) % D, p) for p in range(D)]
        perm_h = [((p + 1) % H, p) for p in range(H)]

        def inner(j, carry):
            G, cur = carry
            cur = lax.ppermute(cur, SAMPLES_AXIS, perm_d)  # then consumed!
            t = jnp.matmul(
                x_mine, _unpack_bits(cur, n_local).astype(jnp.float32),
                preferred_element_type=G.dtype,
            )
            return G + jnp.pad(
                t, ((0, 0), (0, padded - n_local))
            ), cur

        def outer(k, carry):
            G, cur = carry
            cur = lax.ppermute(cur, HOST_AXIS, perm_h)  # then consumed!
            G, cur = lax.fori_loop(0, D - 1, inner, (G, cur))
            return G, cur

        G, _ = lax.fori_loop(0, H - 1, outer, (G, X))
        return G[None]

    @jax.jit
    def update(G, X):
        return shard_map(
            per_slice,
            mesh=mesh,
            in_specs=(
                P(DATA_AXIS, (HOST_AXIS, SAMPLES_AXIS), None),
                P(DATA_AXIS, None, (HOST_AXIS, SAMPLES_AXIS)),
            ),
            out_specs=P(DATA_AXIS, (HOST_AXIS, SAMPLES_AXIS), None),
        )(G, X)

    with jax.enable_x64(True):
        G = jax.ShapeDtypeStruct((1, padded, padded), jnp.float32)
        X = jax.ShapeDtypeStruct(
            (1, block_size, padded // RING_PACK_MULTIPLE), jnp.uint8
        )
        return jax.make_jaxpr(update)(G, X)


class TestSchedRules:
    def test_gs001_flat_selected_on_multihost(self):
        audit = audit_schedule(Topology(2, 4), "flat", selected=True)
        assert [f.rule_id for f in audit.findings] == ["GS001"]
        assert "inter-host" in audit.findings[0].detail

    def test_gs001_not_on_single_host_or_unselected(self):
        assert audit_schedule(Topology(1, 4), "flat", selected=True).ok
        assert audit_schedule(Topology(2, 4), "flat", selected=False).ok

    def test_gs001_silent_when_one_device_per_host(self):
        # hosts x 1: the flat ring IS the host ring — hier buys nothing,
        # the bounds are equal, and flat stays a legitimate selection.
        audit = audit_schedule(Topology(4, 1), "flat", selected=True)
        assert audit.ok, [f.format() for f in audit.findings]

    def test_gs003_serialized_schedule(self):
        traced = _serialized_hier_trace(2, 2, 64, 8)
        audit = audit_schedule(
            Topology(2, 2), "hier", selected=False, traced=traced
        )
        rules = {f.rule_id for f in audit.findings}
        assert "GS003" in rules  # every link step is an overlap hole
        assert "GI001" in rules  # and the IR layer agrees
        # With holes, the levels serialize: critical path is the sum.
        spec = schedule_kernel_spec(Topology(2, 2), "hier", 64, 8)
        sched = extract_schedule(traced, spec, Topology(2, 2), "hier")
        seconds = sched.link_seconds()
        assert sched.critical_path_seconds() == pytest.approx(
            seconds["ici"] + seconds["dcn"]
        )

    def test_gs004_liveness_budget(self):
        audit = audit_schedule(
            Topology(2, 2), "hier", hbm_budget_bytes=1024
        )
        assert [f.rule_id for f in audit.findings] == ["GS004"]

    def test_gs005_budget(self):
        topo = Topology(32, 8)
        tight = audit_schedule(
            topo, "hier", rows=40_000_000, budget_seconds=1e-6
        )
        assert [f.rule_id for f in tight.findings] == ["GS005"]
        roomy = audit_schedule(
            topo, "hier", rows=40_000_000, budget_seconds=3600.0
        )
        assert roomy.ok, [f.format() for f in roomy.findings]

    def test_gs002_schedule_formula_mismatch(self):
        # A DOUBLE-WIDTH hierarchical trace (unpacked wire) against the
        # packed spec: the simulated bytes can no longer match the packed
        # formulas.
        from spark_examples_tpu.check.ir import hier_kernel_spec, trace_kernel

        unpacked = trace_kernel(hier_kernel_spec(1, 2, 2, 64, 8, False))
        audit = audit_schedule(
            Topology(2, 2), "hier", selected=False, traced=unpacked
        )
        assert "GS002" in {f.rule_id for f in audit.findings}


# --------------------------------------------------------------------------
# The hierarchical kernel at runtime: parity + schedule block.
# --------------------------------------------------------------------------


class TestHierRuntime:
    @pytest.fixture()
    def mesh(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        return make_mesh({"data": 1, "samples": 4})

    def test_hier_parity_flat_and_oracle(self, mesh):
        from spark_examples_tpu.ops.gramian import (
            ShardedGramianAccumulator,
            gramian_reference,
        )

        rng = np.random.default_rng(11)
        rows = (rng.random((90, 52)) < 0.35).astype(np.uint8)
        oracle = gramian_reference(rows)
        results = {}
        for sched, hosts in (("flat", None), ("hier", 2), ("hier", 4)):
            acc = ShardedGramianAccumulator(
                52, mesh, block_size=16,
                reduce_schedule=sched, hier_hosts=hosts,
            )
            acc.add_rows(rows)
            results[(sched, hosts)] = acc.finalize()
        for key, G in results.items():
            assert np.array_equal(G, oracle), key
        # Byte-identical across schedules, not merely oracle-equal.
        flat = results[("flat", None)]
        assert flat.tobytes() == results[("hier", 2)].tobytes()
        assert flat.tobytes() == results[("hier", 4)].tobytes()

    def test_hier_parity_unpacked_and_counts_fallback(self, mesh):
        from spark_examples_tpu.ops.gramian import (
            ShardedGramianAccumulator,
            gramian_reference,
        )

        rng = np.random.default_rng(3)
        rows = rng.integers(0, 3, (40, 48)).astype(np.uint8)  # count-valued
        expect = rows.astype(np.int64).T @ rows
        for pack_bits in ("on", "off"):
            acc = ShardedGramianAccumulator(
                48, mesh, block_size=8, pack_bits=pack_bits,
                reduce_schedule="hier", hier_hosts=2,
            )
            acc.add_rows(rows)
            assert np.array_equal(acc.finalize(), expect), pack_bits

    def test_hier_requires_dividing_host_factor(self, mesh):
        from spark_examples_tpu.ops.gramian import ShardedGramianAccumulator

        with pytest.raises(ValueError, match="divide"):
            ShardedGramianAccumulator(
                48, mesh, reduce_schedule="hier", hier_hosts=3
            )
        # auto with a non-dividing factor degrades to flat, loudly typed.
        acc = ShardedGramianAccumulator(
            48, mesh, reduce_schedule="auto", hier_hosts=3
        )
        assert acc.reduce_schedule == "flat"

    def test_ring_bytes_survive_checkpoint_round_trip(self, mesh):
        # A resumed run's schedule block must keep predicted == measured:
        # ring accounting rides the snapshot (absent in old artifacts -> 0).
        from spark_examples_tpu.ops.gramian import ShardedGramianAccumulator

        acc = ShardedGramianAccumulator(
            48, mesh, block_size=8, reduce_schedule="hier", hier_hosts=2
        )
        rows = (np.arange(16 * 48).reshape(16, 48) % 3 == 0).astype(np.uint8)
        acc.add_rows(rows)
        state = acc.snapshot_state()
        assert state["ring_bytes_total"] == acc.ring_bytes_total > 0
        fresh = ShardedGramianAccumulator(
            48, mesh, block_size=8, reduce_schedule="hier", hier_hosts=2
        )
        fresh.restore_state({"meta": state, "G": state["G"]})
        assert fresh.ring_bytes_total == acc.ring_bytes_total
        block = fresh.schedule_block()
        assert block["predicted_ring_bytes"] == block["measured_ring_bytes"]
        # Old artifacts without the field resume with 0 (no crash).
        legacy = {k: v for k, v in state.items() if k != "ring_bytes_total"}
        fresh2 = ShardedGramianAccumulator(
            48, mesh, block_size=8, reduce_schedule="hier", hier_hosts=2
        )
        fresh2.restore_state({"meta": legacy, "G": state["G"]})
        assert fresh2.ring_bytes_total == 0

    def test_schedule_block_shape(self, mesh):
        from spark_examples_tpu.obs.manifest import (
            build_manifest,
            validate_manifest,
        )
        from spark_examples_tpu.ops.gramian import ShardedGramianAccumulator
        from spark_examples_tpu.parallel.mesh import (
            hierarchical_traffic_bytes,
        )

        acc = ShardedGramianAccumulator(
            48, mesh, block_size=8, reduce_schedule="hier", hier_hosts=2
        )
        rows = (np.arange(16 * 48).reshape(16, 48) % 3 == 0).astype(np.uint8)
        acc.add_rows(rows)
        acc.finalize()
        block = acc.schedule_block()
        assert block["kind"] == "hier"
        assert (block["hosts"], block["devices_per_host"]) == (2, 2)
        assert block["predicted_ring_bytes"] == block["measured_ring_bytes"]
        # Per-flush projection x flush count (capacity rows per flush).
        level = hierarchical_traffic_bytes(
            acc.block_size, 2, 2, acc.n_local, acc.pack
        )
        flushes = acc._flushes
        assert flushes == 2
        assert block["predicted_ici_bytes"] == level.ici_bytes * flushes
        assert block["predicted_dcn_bytes"] == level.dcn_bytes * flushes
        doc = build_manifest(schedule=block)
        assert validate_manifest(doc) == []
        bad = dict(block, kind="ring")
        assert validate_manifest(build_manifest(schedule=bad))

    def test_device_ingest_runs_explicit_hier(self, mesh, monkeypatch):
        # The generation ring speaks the two-level schedule: an explicit
        # hier request (host factor from the rehearsal override) runs the
        # hierarchical kernel and lands byte-identical to the flat run.
        from spark_examples_tpu.config import PcaConf
        from spark_examples_tpu.parallel.mesh import HIER_HOSTS_ENV
        from spark_examples_tpu.pipeline.pca_driver import VariantsPcaDriver

        argv = ["--num-samples", "16", "--references", "1:0:50000",
                "--mesh-shape", "1,4", "--similarity-strategy", "sharded",
                "--ingest", "device"]
        monkeypatch.setenv(HIER_HOSTS_ENV, "2")
        conf = PcaConf.parse(argv + ["--reduce-schedule", "hier"])
        driver = VariantsPcaDriver(conf)
        hier_res = np.asarray(
            driver.get_similarity_device_gen(
                conf.get_contigs(driver.source, conf.variant_set_id)
            )
        )
        block = driver._sched_block
        driver.stop()
        assert block["kind"] == "hier"
        assert (block["hosts"], block["devices_per_host"]) == (2, 2)
        assert block["predicted_dcn_bytes"] > 0
        monkeypatch.delenv(HIER_HOSTS_ENV)
        conf2 = PcaConf.parse(argv + ["--reduce-schedule", "flat"])
        driver2 = VariantsPcaDriver(conf2)
        flat_res = np.asarray(
            driver2.get_similarity_device_gen(
                conf2.get_contigs(driver2.source, conf2.variant_set_id)
            )
        )
        flat_block = driver2._sched_block
        driver2.stop()
        assert flat_block["kind"] == "flat"
        assert hier_res.tobytes() == flat_res.tobytes()

    def test_hierarchical_mesh_factorization(self, mesh):
        m3 = hierarchical_mesh(mesh, 2)
        assert m3.shape == {"data": 1, "hosts": 2, "samples": 2}
        # Host-major: the inner axis groups consecutive samples-axis slots.
        assert list(np.asarray(m3.devices).flat) == list(
            np.asarray(mesh.devices).flat
        )
        with pytest.raises(ValueError, match="divide"):
            hierarchical_mesh(mesh, 3)


# --------------------------------------------------------------------------
# The two-radix ranges refinement for the hierarchical kernel.
# --------------------------------------------------------------------------


class TestHierRanges:
    def test_two_radix_refinement_engages(self):
        from spark_examples_tpu.check.ranges import (
            audit_range_kernel,
            hier_range_spec,
        )

        for hosts, per_host in ((2, 2), (2, 4), (4, 2)):
            audit = audit_range_kernel(
                hier_range_spec(hosts, per_host, 64, 8, True, False)
            )
            assert audit.ok, [f.format() for f in audit.findings]
            # Refined to ONE dot partial per pass (8 = block rows), not
            # the conservative trips-multiplied bound.
            assert audit.facts["entry_increment"] == 8.0
            assert audit.facts["entry_increment_conservative"] > 8.0

    def test_flat_matrix_unchanged_by_multiplier_generalization(self):
        from spark_examples_tpu.check.ranges import run_audit as ranges_audit

        report = ranges_audit()
        assert report.ok, "\n".join(f.format() for f in report.findings)


# --------------------------------------------------------------------------
# CLI surfaces: sched subcommand + the unified --topology spelling.
# --------------------------------------------------------------------------


class TestCli:
    def test_sched_clean_and_json(self, capsys):
        from spark_examples_tpu.check import cli

        assert cli.main(["sched", "--topology", "2,2", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["tool"] == "graftcheck-sched"
        assert doc["ok"] is True
        kinds = {s["facts"]["schedule"] for s in doc["subjects"]}
        assert kinds == {"hier", "flat"}
        assert doc["comparisons"][0]["hier_strictly_below"] is True

    def test_sched_flat_forced_flags_gs001(self, capsys):
        from spark_examples_tpu.check import cli

        assert cli.main(
            ["sched", "--topology", "2,2", "--reduce-schedule", "flat"]
        ) == 1
        assert "GS001" in capsys.readouterr().out

    def test_sched_budget_flag(self, capsys):
        from spark_examples_tpu.check import cli

        assert cli.main(
            ["sched", "--topology", "2,2",
             "--sched-budget-seconds", "1e-15"]
        ) == 1
        assert "GS005" in capsys.readouterr().out

    def test_topology_grammar_error_exit_2(self, capsys):
        from spark_examples_tpu.check import cli

        assert cli.main(["sched", "--topology", "nope"]) == 2
        assert cli.main(["ir", "--topology", "1"]) == 2
        assert cli.main(["ranges", "--topology", "2,2,2"]) == 2

    def test_sched_rejects_mesh_flag(self, capsys):
        # --mesh belongs to ir/ranges; silently ignoring it on sched
        # would fake a constrained matrix.
        from spark_examples_tpu.check import cli

        assert cli.main(["sched", "--mesh", "2,2"]) == 2
        assert "--topology" in capsys.readouterr().err

    def test_sched_rejects_nonpositive_budget(self, capsys):
        # Same positivity contract as graftcheck plan: a usage error
        # (exit 2), not a GS005 finding on every topology.
        from spark_examples_tpu.check import cli

        assert cli.main(["sched", "--sched-budget-seconds", "-1"]) == 2
        assert "positive" in capsys.readouterr().err

    def test_ir_topology_appends_hier_kernels(self, capsys):
        from spark_examples_tpu.check import cli

        assert cli.main(
            ["ir", "--mesh", "1,2", "--topology", "2,2", "--json"]
        ) == 0
        doc = json.loads(capsys.readouterr().out)
        names = [k["kernel"] for k in doc["kernels"]]
        assert any(n.startswith("hier[") for n in names)

    def test_ranges_topology_appends_hier_kernels(self, capsys):
        from spark_examples_tpu.check import cli

        assert cli.main(
            ["ranges", "--mesh", "1,2", "--topology", "2,2", "--json"]
        ) == 0
        doc = json.loads(capsys.readouterr().out)
        names = [k["kernel"] for k in doc["kernels"]]
        assert any("hier[" in n for n in names)


# --------------------------------------------------------------------------
# graftcheck plan: --topology / --sched-budget-seconds matrix.
# --------------------------------------------------------------------------


class TestPlanTopology:
    def _plan(self, argv):
        from spark_examples_tpu.check.plan import (
            parse_plan_args,
            validate_plan,
        )

        conf, devices, _json, budget, analysis, topology, sched_budget = (
            parse_plan_args(argv)
        )
        return validate_plan(
            conf, devices, host_mem_budget=budget, analysis=analysis,
            topology=topology, sched_budget_seconds=sched_budget,
        )

    BASE = ["--num-samples", "64", "--references", "1:0:400000"]

    def test_accepts_pod_topology(self):
        report = self._plan(self.BASE + ["--topology", "32,8"])
        assert report.ok, [i.message for i in report.issues]
        assert report.geometry["sched_schedule"] == "hier"
        assert report.geometry["sched_dcn_bytes"] > 0
        assert report.geometry["sched_critical_path_seconds"] > 0
        assert report.geometry["sched_rows"] == 4001

    def test_rejects_flat_on_pod(self):
        report = self._plan(
            self.BASE + ["--topology", "2,4", "--reduce-schedule", "flat"]
        )
        assert not report.ok
        assert any(i.code == "sched-GS001" for i in report.issues)

    def test_rejects_unprovable_budget(self):
        report = self._plan(
            ["--num-samples", "64", "--all-references",
             "--topology", "2,4", "--sched-budget-seconds", "10"]
        )
        assert any(
            i.code == "sched-budget-unprovable" for i in report.issues
        )

    def test_rejects_budget_past_critical_path(self):
        report = self._plan(
            self.BASE + ["--topology", "32,8",
                         "--sched-budget-seconds", "1e-12"]
        )
        assert any(i.code == "sched-GS005" for i in report.issues)

    def test_accepts_provable_budget(self):
        report = self._plan(
            self.BASE + ["--topology", "32,8",
                         "--sched-budget-seconds", "60"]
        )
        assert report.ok, [i.message for i in report.issues]

    def test_budget_without_topology_rejected(self):
        report = self._plan(self.BASE + ["--sched-budget-seconds", "60"])
        assert any(
            i.code == "sched-budget-seconds" for i in report.issues
        )

    def test_budget_on_host_backend_rejected_not_ignored(self):
        # A declared budget the config cannot prove must reject, never
        # silently pass: the host backend dispatches no ring schedule.
        report = self._plan(
            self.BASE + ["--pca-backend", "host", "--topology", "2,4",
                         "--sched-budget-seconds", "0.001"]
        )
        assert any(
            i.code == "sched-budget-unprovable" for i in report.issues
        )

    def test_topology_on_host_backend_warns(self):
        report = self._plan(
            self.BASE + ["--pca-backend", "host", "--topology", "2,4"]
        )
        assert report.ok
        assert any(
            i.code == "sched-not-applicable" and i.severity == "warning"
            for i in report.issues
        )

    def test_budget_on_ld_analysis_rejected(self):
        report = self._plan(
            ["--analysis", "ld", *self.BASE, "--topology", "2,4",
             "--sched-budget-seconds", "1"]
        )
        assert any(
            i.code == "sched-budget-unprovable" for i in report.issues
        )

    def test_explicit_dense_strategy_not_falsely_proven(self):
        # An EXPLICIT dense pin dispatches no ring even on the pod: the
        # topology must not produce a false schedule proof — budget
        # rejects, topology alone warns.
        report = self._plan(
            self.BASE + ["--similarity-strategy", "dense",
                         "--topology", "32,8"]
        )
        assert report.ok
        assert "sched_schedule" not in report.geometry
        assert any(i.code == "sched-not-applicable" for i in report.issues)
        report = self._plan(
            self.BASE + ["--similarity-strategy", "dense",
                         "--topology", "32,8",
                         "--sched-budget-seconds", "60"]
        )
        assert any(
            i.code == "sched-budget-unprovable" for i in report.issues
        )

    def test_data_only_mesh_rejected_against_topology(self):
        # An explicit samples=1 mesh pins a run with no ring at all; the
        # schedule proof must not admit it.
        report = self._plan(
            self.BASE + ["--topology", "2,2", "--mesh-shape", "4,1",
                         "--plan-devices", "4"]
        )
        assert any(
            i.code == "topology-mesh-mismatch" for i in report.issues
        )

    def test_hier_on_device_ingest_accepted(self):
        # The generation ring speaks the two-level schedule now
        # (ops/devicegen.py:_ring_update + _hier_ring_tiles): an explicit
        # hier request on device ingest validates instead of rejecting,
        # and the topology proof traces the DEVICEGEN kernel.
        report = self._plan(
            self.BASE + ["--ingest", "device", "--reduce-schedule", "hier"]
        )
        assert report.ok, [i.message for i in report.issues]
        report = self._plan(
            self.BASE + ["--ingest", "device", "--reduce-schedule", "hier",
                         "--topology", "2,4"]
        )
        assert report.ok, [i.message for i in report.issues]
        assert report.geometry["sched_schedule"] == "hier"
        assert report.geometry["sched_kernel"] == "devicegen"
        assert report.geometry["sched_dcn_bytes"] > 0

    def test_hier_host_factor_must_divide_samples_axis(self):
        # The factorization invariant IS the static validation that
        # replaced the blanket device-ingest rejection: a declared
        # topology whose host count does not divide the declared samples
        # axis cannot build the host-major mesh.
        report = self._plan(
            self.BASE + ["--reduce-schedule", "hier",
                         "--mesh-shape", "1,9", "--plan-devices", "9",
                         "--similarity-strategy", "sharded",
                         "--topology", "2,4"]
        )
        assert any(
            i.code == "hier-hosts-samples-axis" for i in report.issues
        )

    def test_hier_env_override_validated_offline(self, monkeypatch):
        from spark_examples_tpu.parallel.mesh import HIER_HOSTS_ENV

        monkeypatch.setenv(HIER_HOSTS_ENV, "3")
        report = self._plan(
            self.BASE + ["--reduce-schedule", "hier",
                         "--mesh-shape", "1,8", "--plan-devices", "8",
                         "--similarity-strategy", "sharded"]
        )
        assert any(
            i.code == "hier-hosts-samples-axis" for i in report.issues
        )
        monkeypatch.setenv(HIER_HOSTS_ENV, "4")
        report = self._plan(
            self.BASE + ["--reduce-schedule", "hier",
                         "--mesh-shape", "1,8", "--plan-devices", "8",
                         "--similarity-strategy", "sharded"]
        )
        assert report.ok, [i.message for i in report.issues]

    def test_plan_devices_topology_mismatch(self):
        report = self._plan(
            self.BASE + ["--topology", "32,8", "--plan-devices", "8"]
        )
        assert any(
            i.code == "topology-devices-mismatch" for i in report.issues
        )
        # Agreement passes.
        report = self._plan(
            self.BASE + ["--topology", "2,4", "--plan-devices", "8"]
        )
        assert report.ok, [i.message for i in report.issues]

    def test_mesh_topology_mismatch(self):
        report = self._plan(
            self.BASE + ["--topology", "2,4", "--mesh-shape", "1,2",
                         "--plan-devices", "8",
                         "--similarity-strategy", "sharded"]
        )
        assert any(
            i.code == "topology-mesh-mismatch" for i in report.issues
        )

    def test_mesh_matching_topology_accepted(self):
        report = self._plan(
            self.BASE + ["--topology", "2,2", "--mesh-shape", "1,4",
                         "--plan-devices", "4",
                         "--similarity-strategy", "sharded"]
        )
        assert report.ok, [i.message for i in report.issues]

    def test_topology_grammar_rejection(self):
        from spark_examples_tpu.check.plan import parse_plan_args

        with pytest.raises(ValueError):
            parse_plan_args(self.BASE + ["--topology", "pod"])

    def test_reduce_schedule_spelling_validated(self):
        from spark_examples_tpu.check.plan import validate_plan
        from spark_examples_tpu.config import PcaConf

        conf = PcaConf(num_samples=8)
        conf.reduce_schedule = "ring"
        report = validate_plan(conf)
        assert any(i.code == "reduce-schedule" for i in report.issues)

    def test_plan_cli_exit_codes(self):
        from spark_examples_tpu.check import cli

        assert cli.main(["plan", *self.BASE, "--topology", "2,4"]) == 0
        assert cli.main(
            ["plan", *self.BASE, "--topology", "2,4",
             "--reduce-schedule", "flat"]
        ) == 2
        assert cli.main(["plan", *self.BASE, "--topology", "bad"]) == 2


# --------------------------------------------------------------------------
# Satellite: the retired checkpoint-compute O(part) list.
# --------------------------------------------------------------------------


class TestCheckpointComputeStreams:
    def test_compute_streams_and_round_trips(self, tmp_path):
        from typing import Iterator

        from spark_examples_tpu.models.variant import (
            VariantKey,
            VariantsBuilder,
        )
        from spark_examples_tpu.pipeline import checkpoint as cp

        records = []
        for i in range(40):
            wire = {
                "referenceName": "1",
                "variantSetId": "s",
                "id": f"v{i}",
                "start": 100 + i,
                "end": 101 + i,
                "referenceBases": "A",
                "alternateBases": ["C"],
                "calls": [
                    {
                        "callSetId": "s-0",
                        "callSetName": "S0",
                        "genotype": [0, 1],
                    }
                ],
            }
            built = VariantsBuilder.build(wire)
            assert built is not None
            records.append((VariantKey("1", 100 + i), built[1]))
        path = tmp_path / "ckpt"
        cp.save_variants(str(path), [records[:25], records[25:]])
        loaded = cp.load_variants(str(path))
        first = loaded.partitions()[0]
        stream = loaded.compute(first)
        # A generator, not an O(part) list — the retired hostmem site.
        assert isinstance(stream, Iterator)
        got = list(stream)
        assert [k for k, _ in got] == [k for k, _ in records[:25]]
        assert [v.to_json() for _, v in got] == [
            v.to_json() for _, v in records[:25]
        ]
