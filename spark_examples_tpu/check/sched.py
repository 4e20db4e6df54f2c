"""Device-free collective-schedule proving (``graftcheck sched``).

``graftcheck ir`` proves per-kernel IR contracts (overlap, donation, wire
dtype, total traffic) and ``graftcheck ranges`` proves exactness — both
blind to WHERE the bytes ride. At pod scale that is the whole question:
a v5e-256 has two link classes (ICI within a host, DCN between hosts,
~4x slower and shared per host), and a flat packed ring's ``S - 1``
lockstep steps are each gated on the slowest edge of that step's
permutation. This module is the schedule-level layer on top:

- **topology** — :class:`~spark_examples_tpu.parallel.mesh.Topology`
  declares a pod (``hosts x devices_per_host`` + per-link bandwidths)
  that need not exist: like ``--plan-devices``, it is validated against,
  never queried.
- **schedule extraction** — the communication schedule (every ``ppermute``
  site with its operand bytes, scan trip counts, mesh axis, and
  overlap-with-compute flag) is read off the REAL kernel jaxprs via
  ``check/ir.py``'s trace builders — ``ops/gramian.py:
  build_sharded_update`` (flat) and ``build_hierarchical_update`` (the
  two-level ring), never re-implementations.
- **per-level simulation** — each extracted step is attributed to a link
  class. The hierarchical schedule's split is PROVEN by construction (its
  inner axis is intra-host under the host-major mesh factorization); a
  flat ``ppermute`` over one mesh axis carries no host-boundary structure,
  so on a multi-host topology no byte of it is provably intra-host and the
  sound bound attributes the whole circulation to DCN
  (``parallel/mesh.py:flat_traffic_split``). The simulator then closes
  per-level traffic, step counts, per-device peak liveness, and the
  critical path (overlapped levels run concurrently; an overlap hole
  serializes them).

Rules (``check/rules.py:SCHED_RULES``): GS001 a flat ring SELECTED on a
multi-host topology (its DCN bytes exceed the hierarchical bound); GS002
simulated traffic diverging from the closed-form formulas
(``ring_traffic_bytes`` / ``hierarchical_traffic_bytes``); GS003 a
link-bound step with no concurrent compute; GS004 per-device peak
liveness past the HBM fraction; GS005 a predicted critical path past a
declared ``--sched-budget-seconds``. The full ``graftcheck ir`` audit
(GI001-GI006) runs over the same trace, so the flat-ring contracts hold
under both schedules.

Everything is device-free: the whole topology matrix — including the
32x8 pod — is proven on a laptop with zero live device arrays
(test-asserted), which is the point: the hierarchical reduction was
developed and machine-proven before the pod it targets exists.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from spark_examples_tpu.check.ir import (
    KernelSpec,
    _aval_nbytes,
    _ring_bodies,
    _walk_eqns,
    audit_kernel,
    devicegen_hier_spec,
    devicegen_ring_spec,
    hier_kernel_spec,
    permute_overlap,
    ring_kernel_spec,
    trace_kernel,
)
from spark_examples_tpu.check.rules import Finding
from spark_examples_tpu.parallel.mesh import (
    HOST_AXIS,
    Topology,
    flat_traffic_split,
    hierarchical_traffic_bytes,
    resolve_reduce_schedule,
    ring_permutes,
)

#: The shipped topology matrix: single-host shapes (where flat is the
#: right schedule), small multi-host shapes (CI-traceable in seconds), and
#: the v5e-256-class pod (32 hosts x 8 chips) the hierarchical reduction
#: targets — proven on every build, no pod required.
DEFAULT_TOPOLOGIES: Tuple[Tuple[int, int], ...] = (
    (1, 2),
    (1, 4),
    (2, 4),
    (4, 8),
    (32, 8),
)


@dataclass(frozen=True)
class ScheduleStep:
    """One ``ppermute`` site of the extracted schedule: which link class
    its bytes ride, how many times it executes per kernel call (scan trip
    counts multiplied through), the per-device payload, and whether the
    jaxpr proves a concurrent compute dependency-free of it."""

    level: str  # "ici" | "dcn"
    axis: str
    bytes_per_execution: int
    executions: int
    overlapped: bool


@dataclass
class CollectiveSchedule:
    """The communication schedule of one kernel x topology: extracted
    steps plus the geometry needed to scale and price them."""

    schedule: str  # "flat" | "hier"
    topology: Topology
    steps: List[ScheduleStep]
    rows_per_call: int
    n_local: int
    packed: bool
    total_devices: int

    def per_device_bytes(self) -> Dict[str, int]:
        out = {"ici": 0, "dcn": 0}
        for step in self.steps:
            out[step.level] += step.bytes_per_execution * step.executions
        return out

    def mesh_bytes(self) -> Dict[str, int]:
        return {
            level: per_device * self.total_devices
            for level, per_device in self.per_device_bytes().items()
        }

    def step_counts(self) -> Dict[str, int]:
        out = {"ici": 0, "dcn": 0}
        for step in self.steps:
            out[step.level] += step.executions
        return out

    def overlap_holes(self) -> List[ScheduleStep]:
        return [s for s in self.steps if not s.overlapped]

    def link_seconds(self, rows: Optional[int] = None) -> Dict[str, float]:
        """Per-link-class serialized transfer time for ``rows`` variant
        rows (default: one kernel call). ICI is per chip; the DCN NIC is
        shared by the host's chips, so its level serializes the host's
        ``devices_per_host`` tile streams through one link."""
        scale = (
            float(rows) / self.rows_per_call
            if rows is not None and self.rows_per_call
            else 1.0
        )
        per_device = self.per_device_bytes()
        topo = self.topology
        return {
            "ici": per_device["ici"] * scale / topo.ici_bytes_per_s,
            "dcn": (
                per_device["dcn"] * topo.devices_per_host * scale
                / topo.dcn_bytes_per_s
            ),
        }

    def critical_path_seconds(self, rows: Optional[int] = None) -> float:
        """Predicted schedule-limited time: with every link step proven
        overlap-independent of compute (GS003 clean), the two link classes
        also overlap each other (the outer DCN hop hides behind a whole
        inner ring), so the critical path is the slower level; an overlap
        hole serializes the levels instead."""
        seconds = self.link_seconds(rows)
        if self.overlap_holes():
            return seconds["ici"] + seconds["dcn"]
        return max(seconds.values())


def _overlapped_permutes(jaxpr: Any) -> Dict[int, bool]:
    """``id(ppermute eqn) -> proven to wait for no dot and to run beside
    one dot of its ring body`` — the per-site form of the GI001 analysis
    (``check/ir.py:permute_overlap``)."""
    flags: Dict[int, bool] = {}
    for body in _ring_bodies(jaxpr):
        for p, (waits, overlapped) in permute_overlap(body).items():
            flags[id(body.eqns[p])] = overlapped and not waits
    return flags


def _axis_of(eqn: Any) -> str:
    axis = eqn.params.get("axis_name")
    if isinstance(axis, (tuple, list)):
        return str(axis[0]) if len(axis) == 1 else str(tuple(axis))
    return str(axis)


def extract_schedule(
    traced: Any,
    spec: KernelSpec,
    topology: Topology,
    schedule: str,
) -> CollectiveSchedule:
    """Read the communication schedule off one traced kernel.

    Link attribution is the schedule's PROVABLE placement: the
    hierarchical kernel's ``hosts``-axis permutes are DCN and its
    ``samples``-axis permutes are ICI by the host-major mesh
    factorization; a flat kernel's single samples axis spans the whole
    topology, so its permutes are ICI only when the topology has one host
    — on a pod, nothing pins any hop intra-host and every byte is
    attributed to the slow link (the GS001 premise)."""
    jaxpr = traced.jaxpr
    overlap = _overlapped_permutes(jaxpr)
    steps: List[ScheduleStep] = []
    for eqn, mult, _ in _walk_eqns(jaxpr):
        if eqn.primitive.name != "ppermute":
            continue
        axis = _axis_of(eqn)
        if schedule == "hier":
            level = "dcn" if axis == HOST_AXIS else "ici"
        else:
            level = "ici" if topology.hosts == 1 else "dcn"
        steps.append(
            ScheduleStep(
                level=level,
                axis=axis,
                bytes_per_execution=_aval_nbytes(eqn.invars[0].aval),
                executions=mult,
                overlapped=overlap.get(id(eqn), False),
            )
        )
    return CollectiveSchedule(
        schedule=schedule,
        topology=topology,
        steps=steps,
        rows_per_call=spec.rows_per_call,
        n_local=spec.n_local,
        packed=spec.packed,
        total_devices=spec.total_devices,
    )


@dataclass
class ScheduleAudit:
    """One schedule x topology audit: findings + machine-readable facts."""

    name: str
    findings: List[Finding] = field(default_factory=list)
    facts: Dict[str, object] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> Dict[str, object]:
        return {
            "subject": self.name,
            "ok": self.ok,
            "facts": self.facts,
            "findings": [f.to_json() for f in self.findings],
        }


def _emit(audit: ScheduleAudit, rule_id: str, detail: str) -> None:
    audit.findings.append(Finding(rule_id, audit.name, 0, 0, detail))


def schedule_kernel_spec(
    topology: Topology,
    schedule: str,
    num_samples: int,
    block_size: int,
    data: int = 1,
    pack: bool = True,
    exact_int: bool = False,
    kernel: str = "gramian",
    blocks_per_dispatch: int = 2,
) -> KernelSpec:
    """The IR kernel spec for one schedule on one topology — the flat ring
    over a ``data x S`` abstract mesh, or the two-level ring over the
    host-major ``data x hosts x samples`` factorization. ``kernel``
    selects the subject: the host-fed gramian update
    (``ops/gramian.py``) or the fused device-generation ring
    (``ops/devicegen.py:_ring_update``, ``blocks_per_dispatch`` ring
    passes per call). All four are the runtime's own constructors."""
    if kernel == "devicegen":
        if schedule == "hier":
            return devicegen_hier_spec(
                data,
                topology.hosts,
                topology.devices_per_host,
                num_samples,
                block_size,
                blocks_per_dispatch,
                pack,
            )
        return devicegen_ring_spec(
            data,
            topology.devices,
            num_samples,
            block_size,
            blocks_per_dispatch,
            pack,
        )
    if kernel != "gramian":
        raise ValueError(
            f"kernel must be 'gramian' or 'devicegen', got {kernel!r}"
        )
    if schedule == "hier":
        return hier_kernel_spec(
            data,
            topology.hosts,
            topology.devices_per_host,
            num_samples,
            block_size,
            pack,
            exact_int=exact_int,
        )
    return ring_kernel_spec(
        data, topology.devices, num_samples, block_size, pack,
        exact_int=exact_int,
    )


def audit_schedule(
    topology: Topology,
    schedule: str,
    num_samples: int = 64,
    block_size: int = 8,
    data: int = 1,
    pack: bool = True,
    exact_int: bool = False,
    rows: Optional[int] = None,
    budget_seconds: Optional[float] = None,
    selected: bool = True,
    traced: Optional[Any] = None,
    hbm_budget_bytes: Optional[int] = None,
    kernel: str = "gramian",
) -> ScheduleAudit:
    """Trace (or reuse ``traced``), IR-audit, extract, and simulate one
    schedule on one topology; enforce the GS rules.

    ``selected`` marks the schedule the run would actually build (the
    ``--reduce-schedule``/auto resolution): GS001 is a SELECTION rule —
    a flat ring is a fine reference subject on any topology, but choosing
    it for a multi-host run puts the whole circulation on the slow link.
    ``rows`` scales the critical-path prediction (default: one flush);
    ``budget_seconds`` arms GS005."""
    from spark_examples_tpu.ops.gramian import (
        _DEFAULT_DEVICE_BYTES,
        DENSE_HBM_FRACTION,
    )

    spec = schedule_kernel_spec(
        topology, schedule, num_samples, block_size, data, pack, exact_int,
        kernel=kernel,
    )
    audit = ScheduleAudit(
        f"sched[{topology.describe()},{schedule},{spec.name}]"
    )
    audit.facts["topology"] = topology.describe()
    audit.facts["schedule"] = schedule
    audit.facts["kernel"] = kernel
    audit.facts["selected"] = bool(selected)
    if traced is None:
        try:
            traced = trace_kernel(spec)
        except Exception as e:  # noqa: BLE001 — the trace failure is the finding
            _emit(
                audit,
                "GS002",
                f"kernel failed to trace on topology "
                f"{topology.describe()}: {type(e).__name__}: {e} — no "
                "schedule can be extracted, so no traffic/overlap claim "
                "holds",
            )
            return audit

    # The full IR audit over the same trace: the flat-ring contracts
    # (overlap, donation, wire dtype, GI005/GI006 traffic/step counts)
    # hold under BOTH schedules — any GI finding is a sched finding too.
    ir_audit = audit_kernel(spec, traced=traced)
    audit.findings.extend(ir_audit.findings)
    peak_live = int(ir_audit.facts.get("peak_live_bytes", 0))
    audit.facts["peak_live_bytes_per_device"] = peak_live

    sched = extract_schedule(traced, spec, topology, schedule)
    mesh_bytes = sched.mesh_bytes()
    counts = sched.step_counts()
    audit.facts["ici_bytes"] = mesh_bytes["ici"]
    audit.facts["dcn_bytes"] = mesh_bytes["dcn"]
    audit.facts["ici_steps"] = counts["ici"]
    audit.facts["dcn_steps"] = counts["dcn"]
    audit.facts["rows_per_call"] = sched.rows_per_call

    # ---- GS002: simulated schedule vs the closed-form formulas --------
    if schedule == "hier":
        formula = hierarchical_traffic_bytes(
            sched.rows_per_call,
            topology.hosts,
            topology.devices_per_host,
            spec.n_local,
            spec.packed,
        )
        expect = {"ici": formula.ici_bytes, "dcn": formula.dcn_bytes}
    else:
        # The device-generation kernel's flat ring is the half ring.
        split = flat_traffic_split(
            sched.rows_per_call, topology, spec.n_local, spec.packed,
            ring_permutes(topology.devices, half=spec.half_ring),
        )
        expect = {"ici": split.ici_bytes, "dcn": split.dcn_bytes}
    audit.facts["formula_ici_bytes"] = expect["ici"]
    audit.facts["formula_dcn_bytes"] = expect["dcn"]
    for level in ("ici", "dcn"):
        if mesh_bytes[level] != expect[level]:
            _emit(
                audit,
                "GS002",
                f"simulated {level.upper()} traffic is "
                f"{mesh_bytes[level]} bytes/call but the audited formula "
                f"({'hierarchical_traffic_bytes' if schedule == 'hier' else 'ring_traffic_bytes'}) "
                f"says {expect[level]} — the schedule the kernel executes "
                "no longer matches the one telemetry and the plan "
                "validator describe",
            )

    # ---- GS003: overlap holes -----------------------------------------
    for hole in sched.overlap_holes():
        _emit(
            audit,
            "GS003",
            f"a {hole.level.upper()} step over axis {hole.axis!r} "
            f"({hole.bytes_per_execution} B x {hole.executions} "
            "execution(s)) has no concurrent compute proven "
            "dependency-free of it — the link time adds to the critical "
            "path instead of hiding behind the MXU",
        )

    # ---- GS004: per-device liveness -----------------------------------
    hbm_budget = (
        hbm_budget_bytes
        if hbm_budget_bytes is not None
        else int(DENSE_HBM_FRACTION * _DEFAULT_DEVICE_BYTES)
    )
    audit.facts["hbm_budget_bytes"] = hbm_budget
    if peak_live > hbm_budget:
        _emit(
            audit,
            "GS004",
            f"static per-device peak liveness {peak_live} B exceeds the "
            f"HBM budget {hbm_budget} B "
            f"({DENSE_HBM_FRACTION:.0%} of the default device memory) — "
            "the schedule cannot run at this geometry; widen the samples "
            "axis or shrink the block",
        )

    # ---- GS001: flat ring selected on a multi-host topology -----------
    if selected and schedule == "flat" and topology.hosts > 1:
        hier_bound = hierarchical_traffic_bytes(
            sched.rows_per_call,
            topology.hosts,
            topology.devices_per_host,
            spec.n_local,
            spec.packed,
        ).dcn_bytes
        audit.facts["hier_dcn_bound_bytes"] = hier_bound
        if mesh_bytes["dcn"] > hier_bound:
            _emit(
                audit,
                "GS001",
                f"the flat ring on {topology.describe()} puts "
                f"{mesh_bytes['dcn']} bytes/call on the inter-host link "
                f"(no hop is provably intra-host), "
                f"{mesh_bytes['dcn'] / max(1, hier_bound):.1f}x the "
                f"hierarchical schedule's proven {hier_bound} B DCN bound "
                "— use --reduce-schedule hier (or auto) for multi-host "
                "topologies",
            )

    # ---- GS005: declared critical-path budget -------------------------
    sim_rows = rows if rows is not None else sched.rows_per_call
    seconds = sched.link_seconds(sim_rows)
    critical = sched.critical_path_seconds(sim_rows)
    audit.facts["sim_rows"] = int(sim_rows)
    audit.facts["ici_seconds"] = seconds["ici"]
    audit.facts["dcn_seconds"] = seconds["dcn"]
    audit.facts["critical_path_seconds"] = critical
    if budget_seconds is not None and critical > budget_seconds:
        _emit(
            audit,
            "GS005",
            f"predicted schedule-limited critical path "
            f"{critical:.3f} s for {sim_rows} rows on "
            f"{topology.describe()} (ICI {seconds['ici']:.3f} s, DCN "
            f"{seconds['dcn']:.3f} s) exceeds the declared "
            f"--sched-budget-seconds {budget_seconds:g} — the schedule "
            "cannot be proven to fit the budget on this topology",
        )
    return audit


@dataclass
class SchedReport:
    """Every schedule audit of one ``graftcheck sched`` run, grouped per
    topology, with the flat-vs-hier DCN comparison the hierarchical
    schedule exists for."""

    audits: List[ScheduleAudit] = field(default_factory=list)
    comparisons: List[Dict[str, object]] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(a.ok for a in self.audits)

    @property
    def findings(self) -> List[Finding]:
        return [f for a in self.audits for f in a.findings]

    def to_json(self) -> str:
        return json.dumps(
            {
                "tool": "graftcheck-sched",
                "ok": self.ok,
                "subject_count": len(self.audits),
                "finding_count": len(self.findings),
                "subjects": [a.to_json() for a in self.audits],
                "comparisons": self.comparisons,
            },
            indent=2,
        )

    def format(self) -> str:
        lines = []
        for a in self.audits:
            if a.ok:
                bits = [
                    f"ici {a.facts.get('ici_bytes', 0)} B/"
                    f"{a.facts.get('ici_steps', 0)} steps",
                    f"dcn {a.facts.get('dcn_bytes', 0)} B/"
                    f"{a.facts.get('dcn_steps', 0)} steps",
                    "== formula",
                    f"critical path {a.facts.get('critical_path_seconds', 0):.2e} s",
                    f"peak live {a.facts.get('peak_live_bytes_per_device', 0)} B",
                ]
                if a.facts.get("selected"):
                    bits.append("selected")
                lines.append(f"  proved: {a.name}: " + ", ".join(bits))
            else:
                for f in a.findings:
                    lines.append(f"  {f.format()}")
        for comp in self.comparisons:
            lines.append(
                f"  compared: {comp['topology']} "
                f"{comp.get('kernel', 'gramian')}: hier DCN "
                f"{comp['hier_dcn_bytes']} B < flat DCN "
                f"{comp['flat_dcn_bytes']} B "
                f"({comp['dcn_reduction']:.1f}x less on the slow link)"
            )
        verdict = "clean" if self.ok else f"{len(self.findings)} finding(s)"
        lines.append(
            f"graftcheck sched: {len(self.audits)} schedule(s), {verdict}"
        )
        return "\n".join(lines)


def run_audit(
    topologies: Optional[Sequence[Tuple[int, int]]] = None,
    num_samples: int = 64,
    block_size: int = 8,
    reduce_schedule: str = "auto",
    budget_seconds: Optional[float] = None,
) -> SchedReport:
    """Prove the schedule matrix: for every topology and BOTH ring
    kernels (the host-fed gramian update and the fused device-generation
    ring — ``ops/devicegen.py`` runs the same two-level schedule since the
    devicegen/hier seam closed), audit the schedule the
    ``--reduce-schedule`` resolution would build (GS001 armed) AND, on
    multi-host topologies, the flat ring as the reference subject
    (facts + GS002/GS003 — its contracts must hold even where it is the
    wrong choice), then record the flat-vs-hier DCN comparison. Pure
    tracing — zero device buffers survive the call (test-asserted)."""
    report = SchedReport()
    pairs = tuple(topologies) if topologies is not None else DEFAULT_TOPOLOGIES
    for hosts, per_host in pairs:
        topo = Topology(hosts, per_host)
        if topo.devices < 2:
            continue
        chosen = resolve_reduce_schedule(reduce_schedule, topo.hosts)
        for kernel in ("gramian", "devicegen"):
            chosen_audit = audit_schedule(
                topo,
                chosen,
                num_samples=num_samples,
                block_size=block_size,
                budget_seconds=budget_seconds,
                selected=True,
                kernel=kernel,
            )
            report.audits.append(chosen_audit)
            if topo.hosts > 1 and chosen == "hier":
                flat_audit = audit_schedule(
                    topo,
                    "flat",
                    num_samples=num_samples,
                    block_size=block_size,
                    selected=False,
                    kernel=kernel,
                )
                report.audits.append(flat_audit)
                flat_dcn = int(flat_audit.facts.get("dcn_bytes", 0))
                hier_dcn = int(chosen_audit.facts.get("dcn_bytes", 0))
                report.comparisons.append(
                    {
                        "topology": topo.describe(),
                        "kernel": kernel,
                        "flat_dcn_bytes": flat_dcn,
                        "hier_dcn_bytes": hier_dcn,
                        "dcn_reduction": (
                            flat_dcn / hier_dcn if hier_dcn else float("inf")
                        ),
                        "hier_strictly_below": hier_dcn < flat_dcn,
                    }
                )
    return report


__all__ = [
    "DEFAULT_TOPOLOGIES",
    "CollectiveSchedule",
    "ScheduleAudit",
    "ScheduleStep",
    "SchedReport",
    "audit_schedule",
    "extract_schedule",
    "run_audit",
    "schedule_kernel_spec",
]
