"""jaxpr-level kernel auditing (``graftcheck ir``).

The AST linter (``linter.py``) and the plan validator (``plan.py``) stop at
source text and ``eval_shape`` signatures. The properties the packed ring
Gramian actually rests on live one layer down, in the *traced IR*:

- **overlap schedule** — the ring loop issues step k+1's ``ppermute``
  before step k's ``dot_general`` consumes its tile; the two must share NO
  data dependency or XLA serializes ICI against the MXU and the
  communication/compute overlap silently vanishes (GI001). A full ring
  pass must execute exactly ``samples_axis - 1`` permutes — the old
  serialized loop paid one extra, returning each tile to its owner — and
  the device-generation half ring ``samples_axis // 2``
  (``parallel/mesh.py:ring_permutes``; GI006).
- **donation/aliasing** — the accumulator's donation contract is read off
  the traced ``jit`` eqn's ``donated_invars`` and cross-checked against
  the AST layer's justified ``# graftcheck: disable=GC005`` escape
  hatches, so the two layers cannot drift (GI002): a non-donated update
  needs the justification, a justified disable needs the non-donation.
- **dtype flow** — bit-packed wire tiles must stay ``uint8`` from staging
  (or on-device pack) through every ``ppermute`` until the designated
  unpack (the shift-and-mask), and no ``float64`` may appear anywhere in a
  kernel (GI003/GI004). Kernels are traced under ``enable_x64`` so silent
  weak-type promotions are visible instead of masked by canonicalization.
- **static traffic/liveness** — the ICI bytes the jaxpr moves (ppermute
  operand bytes x scan trip counts x devices) must equal the one audited
  formula ``parallel/mesh.py:ring_traffic_bytes`` that telemetry and the
  plan validator report (GI005), and a static buffer-lifetime walk yields
  peak live bytes per kernel, surfaced as facts here and in
  ``graftcheck plan``.

Everything runs device-free: kernels are traced with ``jax.make_jaxpr``
over ``ShapeDtypeStruct`` operands and ``AbstractMesh`` meshes — the same
staged-verification trick the plan validator uses, pushed from shapes down
to the full IR. The audited constructors are the runtime's own
(``ops/gramian.py:build_sharded_update``, ``ops/gramian.py:_dense_update``,
``ops/devicegen.py:_ring_update``), never re-implementations.
"""

from __future__ import annotations

import ast
import functools
import json
import os
from dataclasses import dataclass, field
from typing import (
    Any,
    Callable,
    Dict,
    Iterator,
    List,
    Optional,
    Sequence,
    Set,
    Tuple,
)

import numpy as np

from spark_examples_tpu.check.rules import Finding, parse_disables

# --------------------------------------------------------------------------
# jaxpr plumbing
# --------------------------------------------------------------------------


def _is_var(v: Any) -> bool:
    return not hasattr(v, "val")  # Literal carries .val; Var does not


def _sub_jaxprs(eqn: Any) -> List[Any]:
    """The inner Jaxpr objects of one eqn's params (jit/scan/shard_map
    jaxpr=, cond branches=, while cond/body_jaxpr=...)."""
    out: List[Any] = []

    def add(v: Any) -> None:
        if hasattr(v, "jaxpr") and hasattr(v, "consts"):  # ClosedJaxpr
            out.append(v.jaxpr)
        elif hasattr(v, "eqns") and hasattr(v, "invars"):  # bare Jaxpr
            out.append(v)

    for value in eqn.params.values():
        if isinstance(value, (tuple, list)):
            for item in value:
                add(item)
        else:
            add(value)
    return out


def _aval_nbytes(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64) or 1) * np.dtype(dtype).itemsize


def _walk_eqns(jaxpr: Any, mult: int = 1) -> Iterator[Tuple[Any, int, Any]]:
    """Yield ``(eqn, trip_multiplier, enclosing_jaxpr)`` over every eqn at
    every nesting depth. ``trip_multiplier`` is the product of the lengths
    of enclosing ``scan``s — how many times the eqn executes per call
    (``while`` bodies keep multiplier 1: their trip counts are dynamic, and
    no audited kernel loops with one)."""
    for eqn in jaxpr.eqns:
        yield eqn, mult, jaxpr
        sub_mult = mult
        if eqn.primitive.name == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub, sub_mult)


def _contains_primitive(jaxpr: Any, name: str) -> bool:
    return any(eqn.primitive.name == name for eqn, _, _ in _walk_eqns(jaxpr))


# --------------------------------------------------------------------------
# Intra-body dependency analysis (the GI001 overlap proof).
# --------------------------------------------------------------------------


def _producer_map(jaxpr: Any) -> Dict[Any, int]:
    prod: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            prod[v] = i
    return prod


def _upstream_eqns(jaxpr: Any, start: int, prod: Dict[Any, int]) -> Set[int]:
    """Indices of eqns transitively feeding eqn ``start`` (exclusive)."""
    seen: Set[int] = set()
    frontier = [start]
    while frontier:
        i = frontier.pop()
        for v in jaxpr.eqns[i].invars:
            if not _is_var(v):
                continue
            j = prod.get(v)
            if j is not None and j not in seen:
                seen.add(j)
                frontier.append(j)
    return seen


def _is_dot_eqn(eqn: Any) -> bool:
    if eqn.primitive.name == "dot_general":
        return True
    return any(_contains_primitive(sub, "dot_general") for sub in _sub_jaxprs(eqn))


def _ring_bodies(jaxpr: Any) -> List[Any]:
    """Bodies of scans that contain a ``ppermute`` at their own top level —
    the ring loops (a scan whose permutes are only in NESTED scans is an
    enclosing block loop, not a ring). The half ring unrolls its steps, so
    its ring body is the block loop itself."""
    bodies = []
    for eqn, _, _ in _walk_eqns(jaxpr):
        if eqn.primitive.name != "scan":
            continue
        for sub in _sub_jaxprs(eqn):
            if any(e.primitive.name == "ppermute" for e in sub.eqns):
                bodies.append(sub)
    return bodies


def permute_overlap(body: Any) -> Dict[int, Tuple[bool, bool]]:
    """``{ppermute eqn index: (waits_on_dot, overlapped)}`` in one ring
    body — the GI001 analysis. ``waits_on_dot``: a dot_general feeds the
    permute, so the transfer waits for a matmul. ``overlapped``: some dot
    of the body shares no dependency with the permute either way, so the
    transfer can run behind it. In a loop ring that is the step's own dot;
    in an unrolled ring the dot of step k+1 rightly consumes step k's
    permute, and the dot that overlaps it is step k's."""
    prod = _producer_map(body)
    dots = [i for i, e in enumerate(body.eqns) if _is_dot_eqn(e)]
    ups = {d: _upstream_eqns(body, d, prod) for d in dots}
    out: Dict[int, Tuple[bool, bool]] = {}
    for p, eqn in enumerate(body.eqns):
        if eqn.primitive.name != "ppermute":
            continue
        p_up = _upstream_eqns(body, p, prod)
        waits = any(d in p_up for d in dots)
        overlapped = any(d not in p_up and p not in ups[d] for d in dots)
        out[p] = (waits, overlapped)
    return out


# --------------------------------------------------------------------------
# Packed-wire dtype flow (GI003).
# --------------------------------------------------------------------------

#: Ops a packed uint8 tile may pass through unchanged (layout/movement).
_PACKED_TRANSPARENT = {
    "broadcast_in_dim",
    "reshape",
    "slice",
    "squeeze",
    "transpose",
    "dynamic_slice",
    "copy",
    "concatenate",
    "expand_dims",
    "rev",
    "ppermute",
    "optimization_barrier",
    "pbroadcast",
}

#: The designated unpack: big-endian shift-and-mask (ops/gramian.py:
#: _unpack_bits). Its output is bit planes, no longer the wire format.
_PACKED_UNPACK = {"shift_right_logical"}

#: Consuming a packed tile with these is a contract violation: the byte
#: lanes would be treated as genotype values (wrong math) or widened
#: before the wire (8x traffic).
_PACKED_VIOLATION = {
    "convert_element_type",
    "dot_general",
    "add",
    "sub",
    "mul",
    "div",
    "reduce_sum",
    "reduce_max",
}


def _map_into_sub(eqn: Any, sub: Any, packed_in: Set[Any]) -> Set[Any]:
    """Positionally map packed eqn operands onto a sub-jaxpr's invars
    (jit/shard_map/scan all bind operands to inner invars in order)."""
    seeds: Set[Any] = set()
    for outer, inner in zip(eqn.invars, sub.invars):
        if _is_var(outer) and outer in packed_in:
            seeds.add(inner)
    return seeds


def _packed_flow(
    jaxpr: Any,
    seeds: Set[Any],
    emit: Callable[[str], None],
) -> Set[Any]:
    """Forward-propagate wire-format packedness from ``seeds``; returns the
    packed members of ``jaxpr.outvars``. Emits one violation message per
    offending eqn."""
    packed: Set[Any] = set(seeds)
    for eqn in jaxpr.eqns:
        touched = [
            v for v in eqn.invars if _is_var(v) and v in packed
        ]
        subs = _sub_jaxprs(eqn)
        if subs:
            # Map into every sub-jaxpr; packed sub-outvars flow back to the
            # eqn outvars positionally (scan: final carry + ys align).
            for sub in subs:
                inner_seeds = _map_into_sub(eqn, sub, packed)
                if not inner_seeds:
                    continue
                inner_packed_out = _packed_flow(sub, inner_seeds, emit)
                for outer, inner in zip(eqn.outvars, sub.outvars):
                    if _is_var(inner) and inner in inner_packed_out:
                        packed.add(outer)
            continue
        if not touched:
            continue
        name = eqn.primitive.name
        if name in _PACKED_UNPACK:
            continue  # designated unpack — wire format ends here, by design
        if name in _PACKED_TRANSPARENT:
            for ov in eqn.outvars:
                out_dtype = getattr(ov.aval, "dtype", None)
                if out_dtype is not None and np.dtype(out_dtype) != np.uint8:
                    emit(
                        f"packed wire tile widened by {name} to "
                        f"{np.dtype(out_dtype).name} before the designated "
                        "unpack"
                    )
                else:
                    packed.add(ov)
            continue
        if name in _PACKED_VIOLATION:
            detail = name
            if name == "convert_element_type":
                target = np.dtype(eqn.outvars[0].aval.dtype).name
                if target == "uint8":
                    for ov in eqn.outvars:
                        packed.add(ov)
                    continue
                detail = f"convert_element_type to {target}"
            emit(
                f"packed wire tile consumed by {detail} before the "
                "designated unpack (shift-and-mask)"
            )
    return packed


def _ring_wire_seeds(body: Any) -> Set[Any]:
    """The tiles the ring body's ``ppermute``s put on the wire — the
    carried tile of a loop ring, wherever the builder put it in the carry,
    or the freshly packed tile of a ring that generates its columns in the
    same body."""
    return {
        eqn.invars[0]
        for eqn in body.eqns
        if eqn.primitive.name == "ppermute" and _is_var(eqn.invars[0])
    }


# --------------------------------------------------------------------------
# Static liveness (peak live bytes from buffer lifetimes).
# --------------------------------------------------------------------------


def peak_live_bytes(jaxpr: Any, count_inputs: bool = True) -> int:
    """Static peak of simultaneously-live buffer bytes for one jaxpr.

    A buffer is live from its defining eqn (or entry, for inputs) to its
    last use (program exit for outputs); sub-jaxpr temporaries add their
    own peak at the enclosing eqn, with the sub-jaxpr's inputs excluded
    (they alias the operands already counted outside). Deterministic
    arithmetic over avals — an upper-bound estimate (XLA may fuse
    intermediates away), comparable across kernels and stable across runs,
    which is what a static fact needs.
    """
    n = len(jaxpr.eqns)
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
    for v in jaxpr.outvars:
        if _is_var(v):
            last_use[v] = n
    live = 0
    if count_inputs:
        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            if v in last_use:
                live += _aval_nbytes(v.aval)
    peak = live
    for i, eqn in enumerate(jaxpr.eqns):
        sub_peak = max(
            (peak_live_bytes(s, count_inputs=False) for s in _sub_jaxprs(eqn)),
            default=0,
        )
        out_bytes = sum(
            _aval_nbytes(v.aval)
            for v in eqn.outvars
            if last_use.get(v, -1) >= i
        )
        peak = max(peak, live + out_bytes + sub_peak)
        live += out_bytes
        for v in {v for v in eqn.invars if _is_var(v)}:
            if last_use.get(v) == i:
                live -= _aval_nbytes(v.aval)
    return peak


# --------------------------------------------------------------------------
# AST cross-check: which functions carry a justified GC005 disable.
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def gc005_justified_functions(module_file: str) -> Set[str]:
    """Names of functions in ``module_file`` whose span contains a
    ``# graftcheck: disable=GC005`` escape hatch — the AST layer's
    justified non-donation sites, which GI002 holds the traced
    ``donated_invars`` against. A whole-file disable returns ``{"*"}``."""
    with open(module_file, "r", encoding="utf-8") as f:
        source = f.read()
    per_line, whole_file = parse_disables(source)
    if "GC005" in whole_file or "all" in whole_file:
        return {"*"}
    lines = {
        ln
        for ln, ids in per_line.items()
        if "GC005" in ids or "all" in ids
    }
    if not lines:
        return set()
    spans: List[Tuple[int, int, str]] = []
    tree = ast.parse(source)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            start = min(
                [node.lineno]
                + [d.lineno for d in node.decorator_list]
            )
            spans.append((start, node.end_lineno or node.lineno, node.name))
    out: Set[str] = set()
    for ln in lines:
        containing = [s for s in spans if s[0] <= ln <= s[1]]
        if containing:
            # Innermost = smallest span.
            containing.sort(key=lambda s: s[1] - s[0])
            out.add(containing[0][2])
    return out


# --------------------------------------------------------------------------
# Kernel specs and the audit itself.
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class DonationSite:
    """Where the GC005 justification for a non-donated kernel must live."""

    module_file: str
    function: str
    relpath: str


@dataclass
class KernelSpec:
    """One kernel x geometry to trace and audit.

    ``build`` returns ``(callable, abstract_args)``; it runs inside
    ``enable_x64`` so int64 operand signatures survive. Ring expectations
    (``samples_axis``, ``ring_passes``, ``rows_per_call``, ``n_local``) are
    the audit's ground truth, taken from the same geometry helpers the
    runtime uses (``parallel/mesh.py:padded_cohort``)."""

    name: str
    build: Callable[[], Tuple[Callable[..., Any], Tuple[Any, ...]]]
    samples_axis: int = 1
    total_devices: int = 1
    packed: bool = False
    ring: bool = False
    #: the half ring (``ops/gramian.py:_half_ring_tiles``): ``samples // 2``
    #: permutes per pass instead of ``samples - 1``
    #: (``parallel/mesh.py:ring_permutes``).
    half_ring: bool = False
    ring_passes: int = 1
    rows_per_call: int = 0
    n_local: int = 0
    packed_invars: Tuple[int, ...] = ()
    acc_invar: Optional[int] = 0
    donation: Optional[DonationSite] = None
    liveness_scope: str = "global"


@dataclass
class KernelAudit:
    """The audit result for one kernel: findings + machine-readable facts."""

    name: str
    findings: List[Finding] = field(default_factory=list)
    facts: Dict[str, object] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> Dict[str, object]:
        return {
            "kernel": self.name,
            "ok": self.ok,
            "facts": self.facts,
            "findings": [f.to_json() for f in self.findings],
        }


def _emit(audit: KernelAudit, rule_id: str, detail: str) -> None:
    audit.findings.append(Finding(rule_id, audit.name, 0, 0, detail))


def _find_top_jit(jaxpr: Any) -> Optional[Any]:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            return eqn
    return None


def _audit_donation(spec: KernelSpec, jaxpr: Any, audit: KernelAudit) -> None:
    if spec.acc_invar is None:
        return
    eqn = _find_top_jit(jaxpr)
    if eqn is None:
        _emit(
            audit,
            "GI002",
            "kernel has no jitted (jit) entry point; the accumulator "
            "donation contract cannot be audited",
        )
        return
    acc_var = jaxpr.invars[spec.acc_invar]
    try:
        pos = next(
            i for i, v in enumerate(eqn.invars) if v is acc_var
        )
    except StopIteration:
        _emit(
            audit,
            "GI002",
            "accumulator argument is not an operand of the jitted entry "
            "point — the update cannot be writing it",
        )
        return
    donated_invars = eqn.params.get("donated_invars")
    donated = bool(donated_invars[pos]) if donated_invars else False
    audit.facts["accumulator_donated"] = donated
    justified = False
    if spec.donation is not None:
        names = gc005_justified_functions(spec.donation.module_file)
        justified = "*" in names or spec.donation.function in names
    audit.facts["gc005_disable_present"] = justified
    if not donated and not justified:
        where = (
            f"{spec.donation.relpath}:{spec.donation.function}"
            if spec.donation
            else "the kernel"
        )
        _emit(
            audit,
            "GI002",
            f"accumulator buffer is NOT donated and {where} carries no "
            "justified `# graftcheck: disable=GC005` — donate the buffer "
            "or document the measured reason at the AST layer",
        )
    elif donated and justified:
        _emit(
            audit,
            "GI002",
            f"stale justification: {spec.donation.relpath}:"  # type: ignore[union-attr]
            f"{spec.donation.function} carries a GC005 non-donation "
            "disable but the traced kernel DOES donate the accumulator — "
            "the AST and IR layers have drifted; drop the disable",
        )


def _audit_ring(spec: KernelSpec, jaxpr: Any, audit: KernelAudit) -> None:
    from spark_examples_tpu.parallel.mesh import (
        ring_permutes,
        ring_traffic_bytes,
    )

    permute_sites = [
        (eqn, mult)
        for eqn, mult, _ in _walk_eqns(jaxpr)
        if eqn.primitive.name == "ppermute"
    ]
    executions = sum(mult for _, mult in permute_sites)
    per_pass = ring_permutes(spec.samples_axis, half=spec.half_ring)
    expected = spec.ring_passes * per_pass
    audit.facts["permute_executions"] = executions
    audit.facts["permute_executions_expected"] = expected
    if executions != expected:
        rule = "samples//2 (half ring)" if spec.half_ring else "(samples-1)"
        _emit(
            audit,
            "GI006",
            f"{executions} ppermute execution(s) per call; the "
            f"double-buffered ring contract is ring_passes x {rule} "
            f"= {spec.ring_passes} x {per_pass} = {expected}",
        )

    # Per-call ICI bytes straight from the IR vs the one audited formula.
    per_device = sum(
        _aval_nbytes(eqn.invars[0].aval) * mult for eqn, mult in permute_sites
    )
    jaxpr_bytes = per_device * spec.total_devices
    # rows_per_call already sums every ring pass's rows (D x K x B for the
    # device-generation dispatch), matching how the runtime feeds the
    # formula per flush/dispatch.
    formula_bytes = ring_traffic_bytes(
        spec.rows_per_call, spec.samples_axis, spec.n_local, spec.packed,
        per_pass,
    )
    audit.facts["ring_bytes_jaxpr"] = jaxpr_bytes
    audit.facts["ring_bytes_formula"] = formula_bytes
    if jaxpr_bytes != formula_bytes:
        _emit(
            audit,
            "GI005",
            f"traced ring traffic is {jaxpr_bytes} bytes/call but "
            f"parallel/mesh.py:ring_traffic_bytes says {formula_bytes} — "
            "telemetry and plan facts no longer describe this kernel",
        )

    # Wire dtype at every permute (the packed contract's visible edge).
    # Pack width comes from the ONE constant the runtime geometry uses
    # (parallel/mesh.py:RING_PACK_MULTIPLE), never a re-stated literal.
    if spec.packed:
        from spark_examples_tpu.parallel.mesh import RING_PACK_MULTIPLE

        for eqn, _ in permute_sites:
            aval = eqn.invars[0].aval
            if np.dtype(aval.dtype) != np.uint8:
                _emit(
                    audit,
                    "GI003",
                    f"ppermute circulates {np.dtype(aval.dtype).name} "
                    "tiles; the packed wire format is uint8 "
                    f"({RING_PACK_MULTIPLE} genotypes/byte)",
                )
            elif (
                aval.shape
                and aval.shape[-1] != spec.n_local // RING_PACK_MULTIPLE
            ):
                _emit(
                    audit,
                    "GI003",
                    f"ppermute tile trailing dim is {aval.shape[-1]} "
                    f"bytes; the pack-width invariant says "
                    f"n_local/{RING_PACK_MULTIPLE} = "
                    f"{spec.n_local // RING_PACK_MULTIPLE}",
                )

    # Overlap: each permute must wait for no dot, and some dot of its ring
    # body (its own step's) must be independent of it.
    serialized = False
    for body in _ring_bodies(jaxpr):
        has_dots = any(_is_dot_eqn(e) for e in body.eqns)
        for waits, overlapped in permute_overlap(body).values():
            if has_dots and not overlapped:
                serialized = True
                _emit(
                    audit,
                    "GI001",
                    "the ring step's dot_general depends on that "
                    "step's ppermute output — the matmul waits for the "
                    "ICI transfer every step (serialized ring; the "
                    "permute must move NEXT step's tile)",
                )
            if waits:
                serialized = True
                _emit(
                    audit,
                    "GI001",
                    "the ring step's ppermute depends on that step's "
                    "dot_general output — the ICI transfer waits for "
                    "the matmul every step (no overlap)",
                )
    audit.facts["ring_overlap_independent"] = (
        bool(permute_sites) and not serialized
    )


def _audit_dtypes(spec: KernelSpec, jaxpr: Any, audit: KernelAudit) -> None:
    f64_prims: Set[str] = set()
    for eqn, _, _ in _walk_eqns(jaxpr):
        for ov in eqn.outvars:
            dtype = getattr(ov.aval, "dtype", None)
            if dtype is not None and np.dtype(dtype) == np.float64:
                f64_prims.add(eqn.primitive.name)
    audit.facts["f64_free"] = not f64_prims
    if f64_prims:
        _emit(
            audit,
            "GI004",
            "float64 values produced by: " + ", ".join(sorted(f64_prims)),
        )

    violations: List[str] = []
    seeds = {
        jaxpr.invars[i] for i in spec.packed_invars if i < len(jaxpr.invars)
    }
    if seeds:
        _packed_flow(jaxpr, seeds, violations.append)
    for body in _ring_bodies(jaxpr):
        wire = _ring_wire_seeds(body) if spec.packed else set()
        if wire:
            _packed_flow(body, wire, violations.append)
    for message in sorted(set(violations)):
        _emit(audit, "GI003", message)


def trace_kernel(spec: KernelSpec) -> Any:
    """Trace one kernel spec to its ClosedJaxpr — shared by the IR and
    range audit layers so one geometry pays ONE trace (the plan validator
    hands the same trace to both)."""
    import jax

    with jax.enable_x64(True):
        fn, args = spec.build()
        return jax.make_jaxpr(fn)(*args)


def audit_kernel(spec: KernelSpec, traced: Optional[Any] = None) -> KernelAudit:
    """Trace one kernel spec (or reuse a caller-supplied ``traced``
    ClosedJaxpr from :func:`trace_kernel`) and run every IR audit over its
    jaxpr."""
    audit = KernelAudit(spec.name)
    if traced is not None:
        closed = traced
    else:
        try:
            closed = trace_kernel(spec)
        except Exception as e:  # noqa: BLE001 — any trace failure is the finding
            _emit(
                audit,
                "GI000",
                f"kernel failed to trace: {type(e).__name__}: {e}",
            )
            return audit
    jaxpr = closed.jaxpr
    # Output signature facts: callers (the plan validator) derive their
    # shape checks from THIS trace instead of paying a second one.
    audit.facts["out_shapes"] = [
        list(getattr(a, "shape", ())) for a in closed.out_avals
    ]
    audit.facts["out_dtypes"] = [
        str(getattr(a, "dtype", "?")) for a in closed.out_avals
    ]
    _audit_donation(spec, jaxpr, audit)
    _audit_dtypes(spec, jaxpr, audit)
    if spec.ring:
        _audit_ring(spec, jaxpr, audit)
    scope_jaxpr = jaxpr
    if spec.liveness_scope == "per-device":
        for eqn, _, _ in _walk_eqns(jaxpr):
            if eqn.primitive.name == "shard_map":
                scope_jaxpr = _sub_jaxprs(eqn)[0]
                break
    audit.facts["peak_live_bytes"] = peak_live_bytes(scope_jaxpr)
    audit.facts["liveness_scope"] = spec.liveness_scope
    if traced is None:
        # free trace-time consts before the zero-arrays contract check
        # (a caller-supplied trace is the caller's to free)
        del closed
    return audit


# --------------------------------------------------------------------------
# The shipped audit matrix: the REAL kernels across mesh shapes/flags.
# --------------------------------------------------------------------------


def _gramian_file() -> str:
    from spark_examples_tpu.ops import gramian

    return os.path.abspath(gramian.__file__)


def _devicegen_file() -> str:
    from spark_examples_tpu.ops import devicegen

    return os.path.abspath(devicegen.__file__)


def dense_kernel_spec(data: int, num_samples: int, block_size: int) -> KernelSpec:
    """The dense (replicated N x N) packed update, ``ops/gramian.py:
    _dense_update`` — host blocks arrive bit-packed."""

    def build() -> Tuple[Callable[..., Any], Tuple[Any, ...]]:
        import jax
        import jax.numpy as jnp

        from spark_examples_tpu.ops.gramian import _dense_update
        from spark_examples_tpu.parallel.mesh import RING_PACK_MULTIPLE

        G = jax.ShapeDtypeStruct((data, num_samples, num_samples), jnp.float32)
        X = jax.ShapeDtypeStruct(
            (data, block_size, -(-num_samples // RING_PACK_MULTIPLE)),
            jnp.uint8,
        )
        return (
            lambda g, x: _dense_update(g, x, np.float32, num_samples),
            (G, X),
        )

    return KernelSpec(
        name=f"dense[data={data},N={num_samples},B={block_size}]",
        build=build,
        packed=True,
        packed_invars=(1,),
        acc_invar=0,
        donation=DonationSite(_gramian_file(), "_dense_update", "ops/gramian.py"),
        liveness_scope="global",
    )


def stacked_kernel_spec(
    jobs: int, num_samples: int, block_size: int
) -> KernelSpec:
    """The fused batch executor's stacked-jobs update (``ops/batched.py``):
    the IDENTICAL ``_dense_update`` body with the jobs axis in the batch
    slot — one program accumulating K independent Gramians. A first-class
    audit subject: the serving daemon's fused dispatch runs exactly this
    jaxpr, so its donation/dtype/liveness contracts must hold at group
    geometry, not just at the serial data-axis geometry."""

    def build() -> Tuple[Callable[..., Any], Tuple[Any, ...]]:
        import jax
        import jax.numpy as jnp

        from spark_examples_tpu.ops.gramian import _dense_update
        from spark_examples_tpu.parallel.mesh import RING_PACK_MULTIPLE

        G = jax.ShapeDtypeStruct((jobs, num_samples, num_samples), jnp.float32)
        X = jax.ShapeDtypeStruct(
            (jobs, block_size, -(-num_samples // RING_PACK_MULTIPLE)),
            jnp.uint8,
        )
        return (
            lambda g, x: _dense_update(g, x, np.float32, num_samples),
            (G, X),
        )

    return KernelSpec(
        name=f"stacked[jobs={jobs},N={num_samples},B={block_size}]",
        build=build,
        packed=True,
        packed_invars=(1,),
        acc_invar=0,
        donation=DonationSite(_gramian_file(), "_dense_update", "ops/gramian.py"),
        liveness_scope="global",
    )


def counts_kernel_spec(data: int, num_samples: int, block_size: int) -> KernelSpec:
    """The count-valued (same-set-join) dense update — unpacked by
    necessity, audited for donation and dtype hygiene."""

    def build() -> Tuple[Callable[..., Any], Tuple[Any, ...]]:
        import jax
        import jax.numpy as jnp

        from spark_examples_tpu.ops.gramian import _dense_update_counts

        G = jax.ShapeDtypeStruct((data, num_samples, num_samples), jnp.float32)
        X = jax.ShapeDtypeStruct((data, block_size, num_samples), jnp.uint8)
        return (
            lambda g, x: _dense_update_counts(g, x, np.float32),
            (G, X),
        )

    return KernelSpec(
        name=f"dense-counts[data={data},N={num_samples},B={block_size}]",
        build=build,
        acc_invar=0,
        donation=DonationSite(
            _gramian_file(), "_dense_update_counts", "ops/gramian.py"
        ),
        liveness_scope="global",
    )


def ring_kernel_spec(
    data: int,
    samples: int,
    num_samples: int,
    block_size: int,
    pack: bool,
    exact_int: bool = False,
) -> KernelSpec:
    """The sharded ring-exchange update over an abstract ``data x samples``
    mesh — ``ops/gramian.py:build_sharded_update``, the runtime's own
    constructor."""
    from spark_examples_tpu.parallel.mesh import padded_cohort

    padded = padded_cohort(num_samples, samples, pack=pack)
    n_local = padded // samples

    def build() -> Tuple[Callable[..., Any], Tuple[Any, ...]]:
        import jax
        import jax.numpy as jnp
        from jax.sharding import AbstractMesh

        from spark_examples_tpu.ops.gramian import build_sharded_update
        from spark_examples_tpu.parallel.mesh import (
            DATA_AXIS,
            RING_PACK_MULTIPLE,
            SAMPLES_AXIS,
        )

        mesh = AbstractMesh((data, samples), (DATA_AXIS, SAMPLES_AXIS))
        operand = np.int8 if exact_int else np.float32
        accum = jnp.int32 if exact_int else jnp.float32
        update = build_sharded_update(mesh, operand, pack)
        G = jax.ShapeDtypeStruct((data, padded, padded), accum)
        X = jax.ShapeDtypeStruct(
            (data, block_size,
             padded // RING_PACK_MULTIPLE if pack else padded),
            jnp.uint8,
        )
        return update, (G, X)

    wire = "on" if pack else "off"
    return KernelSpec(
        name=(
            f"ring[data={data},samples={samples},N={num_samples},"
            f"B={block_size},pack={wire}]"
        ),
        build=build,
        samples_axis=samples,
        total_devices=data * samples,
        packed=pack,
        ring=True,
        ring_passes=1,
        rows_per_call=data * block_size,
        n_local=n_local,
        packed_invars=(1,) if pack else (),
        acc_invar=0,
        donation=DonationSite(_gramian_file(), "update", "ops/gramian.py"),
        liveness_scope="per-device",
    )


def hier_kernel_spec(
    data: int,
    hosts: int,
    devices_per_host: int,
    num_samples: int,
    block_size: int,
    pack: bool,
    exact_int: bool = False,
) -> KernelSpec:
    """The hierarchical two-level ring update over an abstract
    ``data x hosts x samples`` mesh — ``ops/gramian.py:
    build_hierarchical_update``, the runtime's own constructor. The ring
    contracts hold UNCHANGED with ``samples_axis = hosts x
    devices_per_host``: total permutes are ``(H-1) + H x (D-1) = S - 1``
    (GI006) and total bytes equal ``ring_traffic_bytes`` (GI005) — the
    schedule moves the same data as the flat ring, split across link
    classes (which ``check/sched.py`` proves per level)."""
    from spark_examples_tpu.parallel.mesh import padded_cohort

    samples = hosts * devices_per_host
    padded = padded_cohort(num_samples, samples, pack=pack)
    n_local = padded // samples

    def build() -> Tuple[Callable[..., Any], Tuple[Any, ...]]:
        import jax
        import jax.numpy as jnp
        from jax.sharding import AbstractMesh

        from spark_examples_tpu.ops.gramian import build_hierarchical_update
        from spark_examples_tpu.parallel.mesh import (
            DATA_AXIS,
            HOST_AXIS,
            RING_PACK_MULTIPLE,
            SAMPLES_AXIS,
        )

        mesh = AbstractMesh(
            (data, hosts, devices_per_host),
            (DATA_AXIS, HOST_AXIS, SAMPLES_AXIS),
        )
        operand = np.int8 if exact_int else np.float32
        accum = jnp.int32 if exact_int else jnp.float32
        update = build_hierarchical_update(mesh, operand, pack)
        G = jax.ShapeDtypeStruct((data, padded, padded), accum)
        X = jax.ShapeDtypeStruct(
            (data, block_size,
             padded // RING_PACK_MULTIPLE if pack else padded),
            jnp.uint8,
        )
        return update, (G, X)

    wire = "on" if pack else "off"
    return KernelSpec(
        name=(
            f"hier[data={data},hosts={hosts},devices={devices_per_host},"
            f"N={num_samples},B={block_size},pack={wire}]"
        ),
        build=build,
        samples_axis=samples,
        total_devices=data * samples,
        packed=pack,
        ring=True,
        ring_passes=1,
        rows_per_call=data * block_size,
        n_local=n_local,
        packed_invars=(1,) if pack else (),
        acc_invar=0,
        donation=DonationSite(_gramian_file(), "update", "ops/gramian.py"),
        liveness_scope="per-device",
    )


def devicegen_ring_spec(
    data: int,
    samples: int,
    num_samples: int,
    block_size: int,
    blocks_per_dispatch: int,
    pack: bool = True,
) -> KernelSpec:
    """The fused generate-and-ring-accumulate dispatch,
    ``ops/devicegen.py:_ring_update`` — traced through its unmemoized
    constructor (``__wrapped__``) so the audit neither pollutes nor pins
    the runtime's compile cache. On this flat mesh it runs the half ring:
    its state is ``half_ring_steps(samples)`` step tiles, the first of them
    the audited accumulator invar."""
    from spark_examples_tpu.parallel.mesh import half_ring_steps, padded_cohort

    padded = padded_cohort(num_samples, samples, pack=pack)
    n_local = padded // samples
    steps = half_ring_steps(samples)

    def build() -> Tuple[Callable[..., Any], Tuple[Any, ...]]:
        import jax
        import jax.numpy as jnp
        from jax.sharding import AbstractMesh

        from spark_examples_tpu.ops.devicegen import _ring_update
        from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS

        mesh = AbstractMesh((data, samples), (DATA_AXIS, SAMPLES_AXIS))
        pops = np.zeros(padded, dtype=np.int32)
        update = _ring_update.__wrapped__(
            (0x5EED,),
            pops.tobytes(),
            0xFACADE,
            100,
            0.1,
            None,
            block_size,
            blocks_per_dispatch,
            "int8",
            num_samples,
            padded,
            1,
            mesh,
            None,
            pack,
        )
        tiles = (jax.ShapeDtypeStruct((data, padded, n_local), jnp.int32),) * steps
        rows = jax.ShapeDtypeStruct((data, 1), jnp.int64)
        kept = jax.ShapeDtypeStruct((data,), jnp.int64)
        offsets = jax.ShapeDtypeStruct((data,), jnp.int64)
        valids = jax.ShapeDtypeStruct((data,), jnp.int64)
        return update, (tiles, rows, kept, offsets, valids)

    return KernelSpec(
        name=(
            f"devicegen-ring[data={data},samples={samples},N={num_samples},"
            f"B={block_size},K={blocks_per_dispatch},"
            f"pack={'on' if pack else 'off'}]"
        ),
        build=build,
        samples_axis=samples,
        total_devices=data * samples,
        packed=pack,
        ring=True,
        half_ring=True,
        ring_passes=blocks_per_dispatch,
        rows_per_call=data * blocks_per_dispatch * block_size,
        n_local=n_local,
        acc_invar=0,
        donation=DonationSite(
            _devicegen_file(), "_ring_update", "ops/devicegen.py"
        ),
        liveness_scope="per-device",
    )


def devicegen_hier_spec(
    data: int,
    hosts: int,
    devices_per_host: int,
    num_samples: int,
    block_size: int,
    blocks_per_dispatch: int,
    pack: bool = True,
) -> KernelSpec:
    """The fused generation ring under the hierarchical two-level schedule
    — ``ops/devicegen.py:_ring_update`` traced over an abstract
    ``data x hosts x samples`` mesh (the mesh in the memo key selects the
    schedule, exactly as at runtime). The ring contracts hold UNCHANGED
    with ``samples_axis = hosts x devices_per_host``: ``(H-1) + H x (D-1)
    = S - 1`` permutes per pass (GI006) and flat-equal total bytes
    (GI005), split across link classes by ``check/sched.py``."""
    from spark_examples_tpu.parallel.mesh import padded_cohort

    samples = hosts * devices_per_host
    padded = padded_cohort(num_samples, samples, pack=pack)
    n_local = padded // samples

    def build() -> Tuple[Callable[..., Any], Tuple[Any, ...]]:
        import jax
        import jax.numpy as jnp
        from jax.sharding import AbstractMesh

        from spark_examples_tpu.ops.devicegen import _ring_update
        from spark_examples_tpu.parallel.mesh import (
            DATA_AXIS,
            HOST_AXIS,
            SAMPLES_AXIS,
        )

        mesh = AbstractMesh(
            (data, hosts, devices_per_host),
            (DATA_AXIS, HOST_AXIS, SAMPLES_AXIS),
        )
        pops = np.zeros(padded, dtype=np.int32)
        update = _ring_update.__wrapped__(
            (0x5EED,),
            pops.tobytes(),
            0xFACADE,
            100,
            0.1,
            None,
            block_size,
            blocks_per_dispatch,
            "int8",
            num_samples,
            padded,
            1,
            mesh,
            None,
            pack,
        )
        G = jax.ShapeDtypeStruct((data, padded, padded), jnp.int32)
        rows = jax.ShapeDtypeStruct((data, 1), jnp.int64)
        kept = jax.ShapeDtypeStruct((data,), jnp.int64)
        offsets = jax.ShapeDtypeStruct((data,), jnp.int64)
        valids = jax.ShapeDtypeStruct((data,), jnp.int64)
        return update, (G, rows, kept, offsets, valids)

    return KernelSpec(
        name=(
            f"devicegen-hier[data={data},hosts={hosts},"
            f"devices={devices_per_host},N={num_samples},B={block_size},"
            f"K={blocks_per_dispatch},pack={'on' if pack else 'off'}]"
        ),
        build=build,
        samples_axis=samples,
        total_devices=data * samples,
        packed=pack,
        ring=True,
        ring_passes=blocks_per_dispatch,
        rows_per_call=data * blocks_per_dispatch * block_size,
        n_local=n_local,
        acc_invar=0,
        donation=DonationSite(
            _devicegen_file(), "_ring_update", "ops/devicegen.py"
        ),
        liveness_scope="per-device",
    )


#: The default mesh matrix: enough shapes that an axis-size-dependent
#: regression (a hardcoded D, a ragged-width assumption) cannot hide.
DEFAULT_MESHES: Tuple[Tuple[int, int], ...] = ((1, 2), (1, 4), (2, 2))


def default_specs(
    num_samples: int = 64,
    ragged_samples: int = 100,
    block_size: int = 8,
    meshes: Sequence[Tuple[int, int]] = DEFAULT_MESHES,
    topologies: Sequence[Tuple[int, int]] = (),
) -> List[KernelSpec]:
    """The shipped audit matrix: dense + counts kernels per data-axis size,
    the ring kernel over every mesh shape x {packed, unpacked} x
    {aligned, ragged} cohort, and the device-generation ring.
    ``topologies`` (``--topology hosts,devices_per_host`` pairs) append the
    hierarchical two-level kernel per topology, packed + unpacked — the
    same GI contracts proven on the pod-scale schedule."""
    specs: List[KernelSpec] = []
    for data in sorted({d for d, _ in meshes}):
        specs.append(dense_kernel_spec(data, num_samples, block_size))
        specs.append(counts_kernel_spec(data, num_samples, block_size))
    # The fused batch groups' stacked program, at a small and a larger
    # group size: same body as dense, jobs axis in the batch slot.
    for jobs in (2, 4):
        specs.append(stacked_kernel_spec(jobs, num_samples, block_size))
    for data, samples in meshes:
        if samples < 2:
            continue
        for pack in (True, False):
            specs.append(
                ring_kernel_spec(data, samples, num_samples, block_size, pack)
            )
        specs.append(
            ring_kernel_spec(data, samples, ragged_samples, block_size, True)
        )
    for data, samples in meshes:
        if samples < 2:
            continue
        specs.append(
            devicegen_ring_spec(data, samples, num_samples, block_size, 2)
        )
    for hosts, per_host in topologies:
        if hosts * per_host < 2:
            continue
        for pack in (True, False):
            specs.append(
                hier_kernel_spec(
                    1, hosts, per_host, num_samples, block_size, pack
                )
            )
        specs.append(
            devicegen_hier_spec(
                1, hosts, per_host, num_samples, block_size, 2
            )
        )
    return specs


@dataclass
class IrReport:
    """Every kernel audit of one ``graftcheck ir`` run."""

    audits: List[KernelAudit] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(a.ok for a in self.audits)

    @property
    def findings(self) -> List[Finding]:
        return [f for a in self.audits for f in a.findings]

    def to_json(self) -> str:
        return json.dumps(
            {
                "tool": "graftcheck-ir",
                "ok": self.ok,
                "kernel_count": len(self.audits),
                "finding_count": len(self.findings),
                "kernels": [a.to_json() for a in self.audits],
            },
            indent=2,
        )

    def format(self) -> str:
        lines = []
        for a in self.audits:
            if a.ok:
                bits = []
                if "permute_executions" in a.facts:
                    bits.append(
                        f"permutes {a.facts['permute_executions']}"
                        f"/{a.facts['permute_executions_expected']}"
                    )
                if a.facts.get("ring_overlap_independent"):
                    bits.append("overlap independent")
                if "ring_bytes_jaxpr" in a.facts:
                    bits.append(
                        f"ring bytes {a.facts['ring_bytes_jaxpr']} == formula"
                    )
                if "accumulator_donated" in a.facts:
                    bits.append(
                        "donated"
                        if a.facts["accumulator_donated"]
                        else "non-donation justified"
                    )
                bits.append(
                    f"peak live {a.facts.get('peak_live_bytes', 0)} B "
                    f"({a.facts.get('liveness_scope')})"
                )
                lines.append(f"  audited: {a.name}: " + ", ".join(bits))
            else:
                for f in a.findings:
                    lines.append(f"  {f.format()}")
        verdict = (
            "clean" if self.ok else f"{len(self.findings)} finding(s)"
        )
        lines.append(f"graftcheck ir: {len(self.audits)} kernel(s), {verdict}")
        return "\n".join(lines)


def run_audit(specs: Optional[Sequence[KernelSpec]] = None) -> IrReport:
    """Audit ``specs`` (default: the shipped matrix). Pure tracing — zero
    device buffers survive the call (test-asserted)."""
    report = IrReport()
    for spec in specs if specs is not None else default_specs():
        report.audits.append(audit_kernel(spec))
    return report


__all__ = [
    "DonationSite",
    "IrReport",
    "KernelAudit",
    "KernelSpec",
    "audit_kernel",
    "counts_kernel_spec",
    "default_specs",
    "dense_kernel_spec",
    "devicegen_hier_spec",
    "devicegen_ring_spec",
    "gc005_justified_functions",
    "hier_kernel_spec",
    "peak_live_bytes",
    "ring_kernel_spec",
    "run_audit",
    "stacked_kernel_spec",
    "trace_kernel",
]
