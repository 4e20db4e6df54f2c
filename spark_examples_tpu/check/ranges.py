"""Abstract-interpretation overflow & exactness prover (``graftcheck ranges``).

The Gramian dtype ladder's exactness claims — bf16×bf16→f32 partials exact
below 2^24 per entry, int8×int8→int32 accumulation exact below 2^31, the
lossless f32→int32 conversion point (``ops/gramian.py:
_maybe_switch_accumulator``) firing before any entry could leave the f32
exact-integer window — were hand-reasoned prose (DESIGN.md §5) that no
check could see. This module proves them per geometry, the way
``graftcheck ir`` proves the ring schedule and ``hostmem`` proves host RAM:

- kernels are traced device-free through the runtime's OWN constructors
  (``check/ir.py``'s specs over ``ShapeDtypeStruct`` + ``AbstractMesh``);
- an abstract interpreter walks the jaxpr with an **interval ×
  exact-in-dtype lattice**: every value is an interval ``[lo, hi]`` plus an
  integrality bit, seeded from the declared input contracts
  (``ops/contracts.py`` — genotypes ∈ [0,2], has-variation ∈ [0,1],
  count-valued join rows, packed wire bytes ∈ [0,255]) and pushed through
  ``dot_general`` (contraction-size multiplication), ``add``/``mul``,
  ``scan`` (outward widening × trip count), ``convert_element_type``, and
  the pack/unpack shift-and-mask ops;
- a parallel **accumulator-delta** component tracks, for values aliasing
  the accumulator operand, the per-entry increment one kernel call can add.
  The ring kernel's ``dynamic_update_slice`` accumulation is refined by a
  disjoint-slice proof: every update slice's column start is
  ``((axis_index + k) mod D) · n_local`` with ``D · n_local`` spanning the
  accumulator and the ``k`` values pairwise distinct mod D (the scan
  induction counter plus the post-loop constant), so each entry is updated
  at most once per ring pass and the per-dispatch increment is ONE dot
  partial, not D of them. Kernels that do not match the pattern keep the
  conservative trips × growth bound.

Rules (``check/rules.py:RANGES_RULES``): GR001 int32 accumulator overflow
for the declared max geometry; GR002 f32 partial past the 2^24 window
before the conversion point; GR003 lossy narrowing cast (inferred range
wider than the destination's exact window); GR004 an uncontracted input
reaching a dot; GR005 the runtime conversion trigger's projection
(``ops/contracts.py:flush_entry_increment`` — the SAME callable the
accumulators feed ``_maybe_switch_accumulator``) smaller than the proven
per-dispatch increment.

Everything is pure tracing + arithmetic: zero device buffers survive an
audit (test-asserted), and ``graftcheck plan`` reuses the same audit per
configuration to report ``gramian_entry_bound`` / ``exactness_headroom_sites``
facts and reject geometries whose accumulation could leave the exact
window.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field, replace
from typing import (
    Any,
    Callable,
    Dict,
    List,
    Optional,
    Sequence,
    Set,
    Tuple,
)

import numpy as np

from spark_examples_tpu.check.ir import _is_var
from spark_examples_tpu.check.rules import Finding
from spark_examples_tpu.ops.contracts import (
    COUNT_ROW,
    DECLARED_MAX_SITES,
    HAS_VARIATION,
    PACKED_BYTE,
    SITE_INDEX,
    RangeContract,
    exact_int_window,
    exactness_headroom_sites,
    flush_entry_increment,
)

_INF = float("inf")


# --------------------------------------------------------------------------
# The lattice: interval × integrality × optional accumulator delta.
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class AbsVal:
    """One abstract value: every concrete element lies in ``[lo, hi]``;
    ``integer`` asserts all elements are integers; ``delta`` (set only for
    values aliasing the designated accumulator) bounds the per-entry
    increment relative to the accumulator's kernel-entry contents;
    ``contracted`` is provenance — False taints everything derived from an
    input with no declared contract (GR004), even where a dtype range
    re-bounds the interval."""

    lo: float
    hi: float
    integer: bool = True
    delta: Optional[Tuple[float, float]] = None
    contracted: bool = True

    @property
    def bounded(self) -> bool:
        return math.isfinite(self.lo) and math.isfinite(self.hi)

    @property
    def magnitude(self) -> float:
        return max(abs(self.lo), abs(self.hi))

    @property
    def point(self) -> Optional[float]:
        return self.lo if self.lo == self.hi else None


TOP = AbsVal(-_INF, _INF, integer=False, contracted=False)


def _hull(a: AbsVal, b: AbsVal) -> AbsVal:
    delta = None
    if a.delta is not None and b.delta is not None:
        delta = (min(a.delta[0], b.delta[0]), max(a.delta[1], b.delta[1]))
    return AbsVal(
        min(a.lo, b.lo),
        max(a.hi, b.hi),
        a.integer and b.integer,
        delta,
    )


def _mul_bound(a: float, b: float) -> float:
    # Concrete values are finite reals, so 0 × anything is 0 even when the
    # other interval endpoint is ±inf.
    if a == 0.0 or b == 0.0:
        return 0.0
    return a * b


def _mul(a: AbsVal, b: AbsVal) -> AbsVal:
    combos = [
        _mul_bound(a.lo, b.lo),
        _mul_bound(a.lo, b.hi),
        _mul_bound(a.hi, b.lo),
        _mul_bound(a.hi, b.hi),
    ]
    return AbsVal(min(combos), max(combos), a.integer and b.integer)


def _add(a: AbsVal, b: AbsVal) -> AbsVal:
    return AbsVal(a.lo + b.lo, a.hi + b.hi, a.integer and b.integer)


def _sub(a: AbsVal, b: AbsVal) -> AbsVal:
    return AbsVal(a.lo - b.hi, a.hi - b.lo, a.integer and b.integer)


def _from_concrete(value: Any) -> AbsVal:
    """Abstract a trace-time constant (a numpy/jax array or scalar)."""
    arr = np.asarray(value)
    if arr.size == 0:
        return AbsVal(0.0, 0.0, True)
    if arr.dtype.kind in ("i", "u", "b"):
        return AbsVal(float(arr.min()), float(arr.max()), True)
    if arr.dtype.kind == "f":
        lo, hi = float(arr.min()), float(arr.max())
        integer = bool(np.all(arr == np.floor(arr)))
        return AbsVal(lo, hi, integer)
    return TOP


def contract_val(contract: Optional[RangeContract]) -> AbsVal:
    if contract is None:
        return TOP
    return AbsVal(float(contract.lo), float(contract.hi), contract.integral)


# --------------------------------------------------------------------------
# Recorded sites (checked after interpretation).
# --------------------------------------------------------------------------


@dataclass
class DotSite:
    """One ``dot_general`` execution site."""

    out: AbsVal
    out_dtype: str
    operands: Tuple[AbsVal, AbsVal]
    operand_dtypes: Tuple[str, str]
    contraction: int
    trips: int
    uncontracted: bool  # an operand interval is unbounded


@dataclass
class ConvertSite:
    src: AbsVal
    src_dtype: str
    dst_dtype: str
    trips: int


@dataclass
class AddEvent:
    """``add`` of a plain value onto an accumulator alias."""

    out_id: int
    t_lo: float
    t_hi: float
    trips: int


@dataclass
class DusEvent:
    """``dynamic_update_slice`` of ``slice(acc) + t`` back into ``acc``."""

    update_id: int
    t_lo: float
    t_hi: float
    trips: int
    #: The execution count of the enclosing RING PASS (the trips multiplier
    #: OUTSIDE the innermost ring scan): the disjointness proof bounds each
    #: entry at one update per pass, so a proven group still multiplies by
    #: this — an outer scan of length T runs T passes.
    passes: int
    #: (modulus, width, base_key, k_values) when the disjoint-slice
    #: pattern was proven; None → conservative accounting.
    pattern: Optional[Tuple[int, int, Tuple[int, ...], Tuple[int, ...]]]


# --------------------------------------------------------------------------
# The interpreter.
# --------------------------------------------------------------------------

#: Layout/movement ops: one data operand in, same values out.
_PASSTHROUGH = {
    "slice",
    "split",
    "squeeze",
    "reshape",
    "broadcast_in_dim",
    "transpose",
    "expand_dims",
    "rev",
    "copy",
    "optimization_barrier",
    "pbroadcast",
    "pvary",
    "ppermute",
    "dynamic_slice",
    "stop_gradient",
    "reduce_precision",
}

_CMP = {"eq", "ne", "lt", "le", "gt", "ge"}


class _Frame:
    """Per-jaxpr interpretation scope: environment, producer map, and the
    binding of this jaxpr's invars to the enclosing frame's vars (for the
    cross-scope peeling the disjointness proof needs)."""

    def __init__(
        self,
        jaxpr: Any,
        parent: Optional["_Frame"],
        binding: Dict[Any, Any],
    ):
        self.jaxpr = jaxpr
        self.parent = parent
        self.binding = binding  # inner invar -> outer var (or None)
        self.env: Dict[Any, AbsVal] = {}
        self.producers: Dict[Any, Any] = {}  # var -> producing eqn
        #: body invars that are scan induction counters: var -> (init, length)
        self.induction: Dict[Any, Tuple[int, int]] = {}

    def read(self, atom: Any) -> AbsVal:
        if not _is_var(atom):  # Literal
            return _from_concrete(atom.val)
        return self.env.get(atom, TOP)

    def write(self, var: Any, val: AbsVal) -> None:
        self.env[var] = val


class Interpreter:
    """Walks a closed jaxpr once, computing an :class:`AbsVal` per var and
    recording the dot/convert/accumulate sites the GR rules inspect."""

    def __init__(self, axis_sizes: Dict[str, int]):
        self.axis_sizes = dict(axis_sizes)
        self.dots: List[DotSite] = []
        self.converts: List[ConvertSite] = []
        self.adds: List[AddEvent] = []
        self.dus: List[DusEvent] = []
        self.unknown_prims: Set[str] = set()
        #: Trips at entry of the innermost enclosing scan (1 at top level)
        #: — the ring-pass count the disjoint-slice group multiplies by.
        self._passes: int = 1

    # ------------------------------------------------------------- plumbing

    def run(self, closed: Any, in_vals: Sequence[AbsVal]) -> List[AbsVal]:
        return self._eval_jaxpr(
            closed.jaxpr,
            [_from_concrete(c) for c in closed.consts],
            list(in_vals),
            parent=None,
            binding={},
            trips=1,
            collect=True,
        )

    def _eval_jaxpr(
        self,
        jaxpr: Any,
        const_vals: Sequence[AbsVal],
        in_vals: Sequence[AbsVal],
        parent: Optional[_Frame],
        binding: Dict[Any, Any],
        trips: int,
        collect: bool,
    ) -> List[AbsVal]:
        frame = _Frame(jaxpr, parent, binding)
        for var, val in zip(jaxpr.constvars, const_vals):
            frame.write(var, val)
        for var, val in zip(jaxpr.invars, in_vals):
            frame.write(var, val)
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                frame.producers[ov] = eqn
            self._eval_eqn(frame, eqn, trips, collect)
        return [frame.read(v) for v in jaxpr.outvars]

    # ------------------------------------------------------------ equations

    def _eval_eqn(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        # Taint provenance: anything derived from an uncontracted input
        # stays uncontracted, re-applied after every handler so dtype-range
        # fallbacks cannot launder the missing-contract fact (GR004).
        tainted = any(
            _is_var(v) and not frame.read(v).contracted for v in eqn.invars
        )
        self._dispatch_eqn(frame, eqn, trips, collect)
        if tainted:
            for ov in eqn.outvars:
                if ov in frame.env:
                    frame.write(ov, replace(frame.env[ov], contracted=False))

    def _dispatch_eqn(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        name = eqn.primitive.name
        handler = getattr(self, f"_prim_{name}", None)
        if handler is not None:
            handler(frame, eqn, trips, collect)
            return
        if name in _PASSTHROUGH:
            val = frame.read(eqn.invars[0])
            for ov in eqn.outvars:
                frame.write(ov, val)
            return
        if name in _CMP:
            a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
            frame.write(eqn.outvars[0], self._compare(name, a, b))
            return
        if name in ("jit", "closed_call", "custom_jvp_call", "custom_vjp_call",
                    "remat", "checkpoint"):
            self._descend(frame, eqn, trips, collect)
            return
        if name == "shard_map":
            self._descend(frame, eqn, trips, collect)
            return
        self.unknown_prims.add(name)
        for ov in eqn.outvars:
            frame.write(ov, TOP)

    def _descend(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        sub = eqn.params.get("jaxpr")
        if sub is None:
            for ov in eqn.outvars:
                frame.write(ov, TOP)
            return
        if hasattr(sub, "jaxpr"):  # ClosedJaxpr
            inner, consts = sub.jaxpr, [_from_concrete(c) for c in sub.consts]
        else:
            inner, consts = sub, []
        in_vals = [frame.read(v) for v in eqn.invars]
        binding = {
            iv: (ov if _is_var(ov) else None)
            for iv, ov in zip(inner.invars, eqn.invars)
        }
        outs = self._eval_jaxpr(
            inner, consts, in_vals, frame, binding, trips, collect
        )
        for ov, val in zip(eqn.outvars, outs):
            frame.write(ov, val)

    # ------------------------------------------------------ leaf primitives

    def _prim_add(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        out = _add(a, b)
        # Accumulator delta: acc + plain → delta grows by the plain interval.
        acc, plain = None, None
        if a.delta is not None and b.delta is None:
            acc, plain = a, b
        elif b.delta is not None and a.delta is None:
            acc, plain = b, a
        if acc is not None and plain is not None:
            out = replace(
                out,
                delta=(acc.delta[0] + plain.lo, acc.delta[1] + plain.hi),
            )
            if collect:
                self.adds.append(
                    AddEvent(
                        id(eqn.outvars[0]),
                        min(plain.lo, 0.0),
                        max(plain.hi, 0.0),
                        trips,
                    )
                )
        elif a.delta is not None and b.delta is not None:
            out = replace(out, delta=None)  # acc + acc: no per-entry claim
        frame.write(eqn.outvars[0], out)

    def _prim_sub(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        frame.write(eqn.outvars[0], _sub(a, b))

    def _prim_mul(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        frame.write(eqn.outvars[0], _mul(a, b))

    def _prim_neg(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a = frame.read(eqn.invars[0])
        frame.write(eqn.outvars[0], AbsVal(-a.hi, -a.lo, a.integer))

    def _prim_abs(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a = frame.read(eqn.invars[0])
        lo = 0.0 if a.lo <= 0.0 <= a.hi else min(abs(a.lo), abs(a.hi))
        frame.write(eqn.outvars[0], AbsVal(lo, a.magnitude, a.integer))

    def _prim_max(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        frame.write(
            eqn.outvars[0],
            AbsVal(max(a.lo, b.lo), max(a.hi, b.hi), a.integer and b.integer),
        )

    def _prim_min(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        frame.write(
            eqn.outvars[0],
            AbsVal(min(a.lo, b.lo), min(a.hi, b.hi), a.integer and b.integer),
        )

    def _prim_rem(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        if b.bounded and b.lo > 0:
            m = b.hi - 1
            lo = 0.0 if a.lo >= 0 else -m
            frame.write(eqn.outvars[0], AbsVal(lo, m, a.integer and b.integer))
        else:
            frame.write(eqn.outvars[0], TOP)

    def _prim_div(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        if b.bounded and (b.lo > 0 or b.hi < 0):
            combos = [a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi]
            frame.write(
                eqn.outvars[0], AbsVal(min(combos), max(combos), False)
            )
        else:
            frame.write(eqn.outvars[0], TOP)

    def _prim_and(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        if a.lo >= 0 and b.lo >= 0:
            frame.write(
                eqn.outvars[0], AbsVal(0.0, min(a.hi, b.hi), True)
            )
        else:
            frame.write(eqn.outvars[0], self._dtype_range(eqn.outvars[0]))

    def _bits_upper(self, hi: float) -> float:
        if not math.isfinite(hi) or hi < 0:
            return _INF
        bits = int(hi).bit_length()
        return float((1 << bits) - 1)

    def _prim_or(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        if a.lo >= 0 and b.lo >= 0:
            hi = self._bits_upper(max(a.hi, b.hi))
            frame.write(eqn.outvars[0], AbsVal(0.0, hi, True))
        else:
            frame.write(eqn.outvars[0], self._dtype_range(eqn.outvars[0]))

    _prim_xor = _prim_or

    def _prim_not(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        frame.write(eqn.outvars[0], self._dtype_range(eqn.outvars[0]))

    def _prim_shift_right_logical(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        a = frame.read(eqn.invars[0])
        if a.lo >= 0:
            frame.write(eqn.outvars[0], AbsVal(0.0, a.hi, True))
        else:
            frame.write(eqn.outvars[0], self._dtype_range(eqn.outvars[0]))

    _prim_shift_right_arithmetic = _prim_shift_right_logical

    def _prim_shift_left(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        a, s = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        if a.lo >= 0 and s.bounded and s.lo >= 0:
            hi = a.hi * (2.0 ** s.hi)
            out = AbsVal(0.0, hi, True)
            frame.write(eqn.outvars[0], self._clamp_int(out, eqn.outvars[0]))
        else:
            frame.write(eqn.outvars[0], self._dtype_range(eqn.outvars[0]))

    def _prim_select_n(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        pred = frame.read(eqn.invars[0])
        cases = [frame.read(v) for v in eqn.invars[1:]]
        pt = pred.point
        if pt is not None and 0 <= int(pt) < len(cases):
            out = cases[int(pt)]
        else:
            out = cases[0]
            for c in cases[1:]:
                out = _hull(out, c)
        frame.write(eqn.outvars[0], out)

    def _prim_iota(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        shape = eqn.outvars[0].aval.shape
        dim = eqn.params.get("dimension", 0)
        n = shape[dim] if shape else 1
        frame.write(eqn.outvars[0], AbsVal(0.0, float(max(n - 1, 0)), True))

    def _prim_axis_index(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        axis = eqn.params.get("axis_name")
        if isinstance(axis, (tuple, list)):
            size = 1
            for a in axis:
                size *= self.axis_sizes.get(a, 0)
        else:
            size = self.axis_sizes.get(axis, 0)
        if size > 0:
            frame.write(eqn.outvars[0], AbsVal(0.0, float(size - 1), True))
        else:
            frame.write(eqn.outvars[0], TOP)

    def _prim_gather(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        # Every gathered element IS an element of the operand, so the
        # operand's interval carries over verbatim; under FILL mode the
        # out-of-bounds fill value joins the hull (the declared fill when
        # present, the dtype range otherwise). The index operand cannot
        # influence VALUES — only which ones — so it contributes nothing
        # to the interval (taint provenance still flows via _eval_eqn).
        a = frame.read(eqn.invars[0])
        out = AbsVal(a.lo, a.hi, a.integer)
        mode = eqn.params.get("mode")
        if mode is not None and "FILL" in str(mode).upper():
            fill = eqn.params.get("fill_value")
            if fill is not None:
                f = float(fill)
                out = _hull(out, AbsVal(f, f, float(f).is_integer()))
            else:
                out = _hull(out, self._dtype_range(eqn.outvars[0]))
        frame.write(eqn.outvars[0], out)

    def _prim_psum(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        # A psum over named axes is a sum of ``size`` per-device terms,
        # each inside the operand's interval: [size·lo, size·hi].
        axes = eqn.params.get("axes", ())
        size = 1
        for ax in axes:
            if isinstance(ax, int):
                shape = eqn.invars[0].aval.shape
                size *= int(shape[ax]) if ax < len(shape) else 0
            else:
                size *= self.axis_sizes.get(ax, 0)
        for iv, ov in zip(eqn.invars, eqn.outvars):
            a = frame.read(iv)
            if size > 0:
                frame.write(
                    ov,
                    AbsVal(
                        _mul_bound(float(size), a.lo),
                        _mul_bound(float(size), a.hi),
                        a.integer,
                    ),
                )
            else:
                frame.write(ov, TOP)

    def _prim_convert_element_type(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        a = frame.read(eqn.invars[0])
        src_dtype = str(getattr(eqn.invars[0].aval, "dtype", "?")) if _is_var(
            eqn.invars[0]
        ) else str(np.asarray(eqn.invars[0].val).dtype)
        dst_dtype = str(eqn.outvars[0].aval.dtype)
        if collect:
            self.converts.append(ConvertSite(a, src_dtype, dst_dtype, trips))
        out = AbsVal(a.lo, a.hi, a.integer or _is_int_dtype(dst_dtype), a.delta)
        frame.write(eqn.outvars[0], self._clamp_int(out, eqn.outvars[0]))

    def _prim_dot_general(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        a, b = frame.read(eqn.invars[0]), frame.read(eqn.invars[1])
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs_shape = eqn.invars[0].aval.shape
        k = 1
        for d in lhs_contract:
            k *= int(lhs_shape[d])
        prod = _mul(a, b)
        # Sum of k products each in [prod.lo, prod.hi]:
        out = AbsVal(
            _mul_bound(float(k), prod.lo),
            _mul_bound(float(k), prod.hi),
            prod.integer,
        )
        if collect:
            self.dots.append(
                DotSite(
                    out,
                    str(eqn.outvars[0].aval.dtype),
                    (a, b),
                    (
                        str(eqn.invars[0].aval.dtype)
                        if _is_var(eqn.invars[0])
                        else "literal",
                        str(eqn.invars[1].aval.dtype)
                        if _is_var(eqn.invars[1])
                        else "literal",
                    ),
                    k,
                    trips,
                    uncontracted=not (
                        a.bounded and b.bounded and a.contracted and b.contracted
                    ),
                )
            )
        frame.write(eqn.outvars[0], out)

    def _prim_reduce_sum(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        a = frame.read(eqn.invars[0])
        shape = eqn.invars[0].aval.shape
        n = 1
        for ax in eqn.params.get("axes", ()):
            n *= int(shape[ax])
        out = AbsVal(_mul_bound(float(n), a.lo), _mul_bound(float(n), a.hi), a.integer)
        frame.write(eqn.outvars[0], self._clamp_int(out, eqn.outvars[0]))

    def _prim_reduce_max(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        frame.write(eqn.outvars[0], frame.read(eqn.invars[0]))

    _prim_reduce_min = _prim_reduce_max
    _prim_reduce_and = _prim_reduce_max
    _prim_reduce_or = _prim_reduce_max

    def _prim_concatenate(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        out = frame.read(eqn.invars[0])
        for v in eqn.invars[1:]:
            out = _hull(out, frame.read(v))
        frame.write(eqn.outvars[0], out)

    def _prim_dynamic_update_slice(
        self, frame: _Frame, eqn: Any, trips: int, collect: bool
    ) -> None:
        operand = frame.read(eqn.invars[0])
        update = frame.read(eqn.invars[1])
        out = _hull(operand, update)
        if operand.delta is not None and update.delta is not None:
            out = replace(
                out,
                delta=(
                    min(operand.delta[0], update.delta[0]),
                    max(operand.delta[1], update.delta[1]),
                ),
            )
            if collect:
                t_lo = min(0.0, update.delta[0] - operand.delta[0])
                t_hi = max(0.0, update.delta[1] - operand.delta[1])
                pattern = self._dus_pattern(frame, eqn)
                self.dus.append(
                    DusEvent(
                        id(eqn.invars[1]),
                        t_lo,
                        t_hi,
                        trips,
                        self._passes,
                        pattern,
                    )
                )
        frame.write(eqn.outvars[0], out)

    def _prim_scan(self, frame: _Frame, eqn: Any, trips: int, collect: bool) -> None:
        closed = eqn.params["jaxpr"]
        body = closed.jaxpr if hasattr(closed, "jaxpr") else closed
        consts_vals = (
            [_from_concrete(c) for c in closed.consts]
            if hasattr(closed, "consts")
            else []
        )
        nc = int(eqn.params.get("num_consts", 0))
        nk = int(eqn.params.get("num_carry", 0))
        length = int(eqn.params.get("length", 1))
        in_vals = [frame.read(v) for v in eqn.invars]
        consts, carry, xs = in_vals[:nc], in_vals[nc : nc + nk], in_vals[nc + nk :]
        binding = {
            iv: (ov if _is_var(ov) else None)
            for iv, ov in zip(body.invars, eqn.invars)
        }

        def run_body(carry_vals: List[AbsVal], do_collect: bool, mult: int):
            # Inside this scan's body, one "pass" = one execution of the
            # scan itself — the trips THIS eqn was evaluated with.
            saved_passes, self._passes = self._passes, trips
            try:
                return self._eval_scan_body(
                    body,
                    consts_vals,
                    consts + carry_vals + xs,
                    frame,
                    binding,
                    mult,
                    do_collect,
                    nc,
                    nk,
                    carry,
                    length,
                )
            finally:
                self._passes = saved_passes

        out1 = run_body(list(carry), False, trips)
        new_carry = out1[:nk]
        widened: List[AbsVal] = []
        for init, out in zip(carry, new_carry):
            g_hi = max(0.0, out.hi - init.hi)
            g_lo = min(0.0, out.lo - init.lo)
            d = init.delta
            if d is not None and out.delta is not None:
                d = (
                    d[0] + length * min(0.0, out.delta[0] - d[0]),
                    d[1] + length * max(0.0, out.delta[1] - d[1]),
                )
            elif out.delta is None:
                d = None
            widened.append(
                AbsVal(
                    init.lo + length * g_lo,
                    init.hi + length * g_hi,
                    init.integer and out.integer,
                    d,
                    contracted=init.contracted and out.contracted,
                )
            )
        # Soundness check: one more step from the widened carry must not
        # outgrow the linear-widening assumption; if it does, give up on
        # that carry (TOP) rather than under-approximate.
        out2 = run_body(list(widened), False, trips)
        for i, (w, o) in enumerate(zip(widened, out2[:nk])):
            g_hi = max(0.0, out1[i].hi - carry[i].hi)
            g_lo = min(0.0, out1[i].lo - carry[i].lo)
            if o.hi > w.hi + g_hi + 1e-9 or o.lo < w.lo + g_lo - 1e-9:
                widened[i] = TOP
        # Final, collecting pass: the carry the body sees spans every trip.
        final = run_body(list(widened), collect, trips * length)
        outs = list(widened) + final[nk:]
        for ov, val in zip(eqn.outvars, outs):
            frame.write(ov, val)

    def _eval_scan_body(
        self,
        body: Any,
        consts_vals: Sequence[AbsVal],
        in_vals: Sequence[AbsVal],
        parent: _Frame,
        binding: Dict[Any, Any],
        trips: int,
        collect: bool,
        nc: int,
        nk: int,
        carry_init: Sequence[AbsVal],
        length: int,
    ) -> List[AbsVal]:
        sub = _Frame(body, parent, binding)
        for var, val in zip(body.constvars, consts_vals):
            sub.write(var, val)
        for var, val in zip(body.invars, in_vals):
            sub.write(var, val)
        # Induction counters: a carry whose body output is carry + 1 and
        # whose initial value is a known point — the k of the ring
        # disjointness proof.
        for i in range(nk):
            iv = body.invars[nc + i]
            ov = body.outvars[i]
            init_pt = carry_init[i].point if i < len(carry_init) else None
            if init_pt is None or not _is_var(ov):
                continue
            for eq in body.eqns:
                if ov in eq.outvars and eq.primitive.name == "add":
                    args = eq.invars
                    if len(args) == 2 and (
                        (args[0] is iv and _lit_value(args[1]) == 1)
                        or (args[1] is iv and _lit_value(args[0]) == 1)
                    ):
                        sub.induction[iv] = (int(init_pt), length)
        for eqn in body.eqns:
            for ov in eqn.outvars:
                sub.producers[ov] = eqn
            self._eval_eqn(sub, eqn, trips, collect)
        return [sub.read(v) for v in body.outvars]

    # -------------------------------------------- disjoint-slice peeling

    def _dus_pattern(
        self, frame: _Frame, eqn: Any
    ) -> Optional[Tuple[int, int, Tuple[int, ...], Tuple[int, ...]]]:
        """Prove the accumulate-into-disjoint-slices idiom for one
        ``dynamic_update_slice``: every start index is either a known point
        or ``((base + k) mod D) · width`` with ``D · width`` spanning that
        accumulator dimension; returns ``(modulus, width, base_key,
        k_values)`` or None. ``k`` must be a scan induction counter or a
        constant — the caller checks distinctness across the event group."""
        operand_shape = eqn.invars[0].aval.shape
        update_shape = eqn.invars[1].aval.shape
        starts = eqn.invars[2:]
        mod_info = None
        for dim, start in enumerate(starts):
            val = frame.read(start) if _is_var(start) else _from_concrete(start.val)
            if val.point is not None:
                continue  # fixed offset in this dim
            peeled = self._peel_mod_mul(frame, start)
            if peeled is None:
                return None
            modulus, width, base_key, k_values = peeled
            if width != int(update_shape[dim]):
                return None
            if modulus * width != int(operand_shape[dim]):
                return None
            if mod_info is not None:
                return None  # more than one varying dim: out of scope
            mod_info = (modulus, width, base_key, k_values)
        return mod_info

    def _peel(self, frame: _Frame, var: Any) -> Tuple[_Frame, Any]:
        """Follow transparent producers (pbroadcast/convert/copy/
        optimization_barrier, interval-decided select_n) and cross-frame
        invar bindings to the semantically-defining (frame, var)."""
        seen = 0
        while seen < 64:
            seen += 1
            if not _is_var(var):
                return frame, var
            if var in frame.binding and var not in frame.producers:
                outer = frame.binding[var]
                if outer is None or frame.parent is None:
                    return frame, var
                frame, var = frame.parent, outer
                continue
            eqn = frame.producers.get(var)
            if eqn is None:
                return frame, var
            name = eqn.primitive.name
            if name in ("pbroadcast", "pvary", "convert_element_type", "copy",
                        "optimization_barrier", "broadcast_in_dim", "squeeze"):
                var = eqn.invars[0]
                continue
            if name == "select_n":
                pred = frame.read(eqn.invars[0]) if _is_var(eqn.invars[0]) else _from_concrete(eqn.invars[0].val)
                pt = pred.point
                if pt is not None and 0 <= int(pt) < len(eqn.invars) - 1:
                    var = eqn.invars[1 + int(pt)]
                    continue
                return frame, var
            return frame, var
        return frame, var

    def _peel_mod_mul(
        self, frame: _Frame, var: Any
    ) -> Optional[Tuple[int, int, Tuple[int, ...], Tuple[int, ...]]]:
        frame, var = self._peel(frame, var)
        eqn = frame.producers.get(var) if _is_var(var) else None
        if eqn is None or eqn.primitive.name != "mul":
            return None
        width = None
        mod_var = None
        for a, b in ((eqn.invars[0], eqn.invars[1]), (eqn.invars[1], eqn.invars[0])):
            bv = frame.read(b) if _is_var(b) else _from_concrete(b.val)
            if bv.point is not None:
                width = int(bv.point)
                mod_var = a
                break
        if width is None or width <= 0 or mod_var is None:
            return None
        mframe, mvar = self._peel(frame, mod_var)
        meqn = mframe.producers.get(mvar) if _is_var(mvar) else None
        if meqn is None:
            return None
        if meqn.primitive.name == "add":
            # The hierarchical ring's TWO-RADIX owner index:
            # ((h + k) mod H) * D + ((d + j) mod D), multiplied by the tile
            # width outside. Same disjointness structure, two loop levels.
            two_radix = self._peel_two_radix(mframe, meqn)
            if two_radix is None:
                return None
            modulus, base_key, k_values = two_radix
            return modulus, width, base_key, k_values
        single = self._peel_rem(mframe, mvar)
        if single is None:
            return None
        modulus, base_ids, k_values = single
        return modulus, width, tuple(sorted(base_ids)), tuple(sorted(k_values))

    def _peel_rem(
        self, frame: _Frame, var: Any
    ) -> Optional[Tuple[int, Set[int], Set[int]]]:
        """Peel one ``(base + k) mod M`` radix: returns ``(modulus,
        base ids, k values)`` or None. The shared recognizer of the
        single-radix (flat ring) and two-radix (hierarchical ring)
        disjointness patterns."""
        f, v = self._peel(frame, var)
        eqn = f.producers.get(v) if _is_var(v) else None
        if eqn is None:
            return None
        modulus = None
        dividend = None
        if eqn.primitive.name == "rem":
            div = (
                f.read(eqn.invars[1])
                if _is_var(eqn.invars[1])
                else _from_concrete(eqn.invars[1].val)
            )
            if div.point is not None:
                modulus, dividend = int(div.point), eqn.invars[0]
        elif eqn.primitive.name == "jit" and eqn.params.get("name") in (
            "remainder",
            "mod",
            "floormod",
        ):
            div = (
                f.read(eqn.invars[1])
                if _is_var(eqn.invars[1])
                else _from_concrete(eqn.invars[1].val)
            )
            if div.point is not None:
                modulus, dividend = int(div.point), eqn.invars[0]
        if modulus is None or modulus <= 0 or dividend is None:
            return None
        terms = self._peel_add_terms(f, dividend)
        if terms is None or terms[1] is None:
            return None
        return modulus, set(terms[0]), set(terms[1])

    def _peel_two_radix(
        self, frame: _Frame, add_eqn: Any
    ) -> Optional[Tuple[int, Tuple[int, ...], Tuple[int, ...]]]:
        """Prove the hierarchical owner index ``((h + k) mod H) * D +
        ((d + j) mod D)``: a two-level scan's flat owner, pairwise
        distinct over the (k, j) double loop exactly when the per-level
        residues are. Returns ``(H * D, base_key, flat k values)`` with
        the flat values ``(k mod H) * D + (j mod D)`` — distinct iff the
        (k, j) pairs are, so the group check in ``_refined_increment``
        applies unchanged. A collision WITHIN the site (fewer flat values
        than k x j combinations) means one entry is updated twice per
        pass: the proof fails rather than under-counts."""
        for a, b in (
            (add_eqn.invars[0], add_eqn.invars[1]),
            (add_eqn.invars[1], add_eqn.invars[0]),
        ):
            fa, va = self._peel(frame, a)
            ea = fa.producers.get(va) if _is_var(va) else None
            if ea is None or ea.primitive.name != "mul":
                continue
            low_radix = None
            rem_var = None
            for x, y in (
                (ea.invars[0], ea.invars[1]),
                (ea.invars[1], ea.invars[0]),
            ):
                yv = fa.read(y) if _is_var(y) else _from_concrete(y.val)
                if yv.point is not None:
                    low_radix = int(yv.point)
                    rem_var = x
                    break
            if low_radix is None or low_radix <= 0 or rem_var is None:
                continue
            high = self._peel_rem(fa, rem_var)
            low = self._peel_rem(frame, b)
            if high is None or low is None:
                continue
            h_mod, h_base, h_ks = high
            l_mod, l_base, l_ks = low
            if l_mod != low_radix:
                continue
            flat = {
                (kh % h_mod) * l_mod + (kl % l_mod)
                for kh in h_ks
                for kl in l_ks
            }
            if len(flat) != len(h_ks) * len(l_ks):
                return None
            return (
                h_mod * l_mod,
                tuple(sorted(h_base | l_base)),
                tuple(sorted(flat)),
            )
        return None

    def _peel_add_terms(
        self, frame: _Frame, var: Any
    ) -> Optional[Tuple[Set[int], Optional[Set[int]]]]:
        """Decompose an add chain into (base atoms, k values). Exactly one
        varying term (induction counter or literal) is allowed; every other
        term must be loop-invariant (it becomes part of the base key)."""
        base: Set[int] = set()
        k_values: Optional[Set[int]] = None
        stack = [(frame, var)]
        steps = 0
        while stack:
            steps += 1
            if steps > 64:
                return None
            f, v = stack.pop()
            f, v = self._peel(f, v)
            if not _is_var(v):
                val = _from_concrete(v.val)
                if val.point is None:
                    return None
                if k_values is not None:
                    return None
                k_values = {int(val.point)}
                continue
            if v in f.induction:
                init, length = f.induction[v]
                if k_values is not None:
                    return None
                k_values = set(range(init, init + length))
                continue
            eqn = f.producers.get(v)
            if eqn is not None and eqn.primitive.name == "add":
                stack.append((f, eqn.invars[0]))
                stack.append((f, eqn.invars[1]))
                continue
            val = f.read(v)
            if val.point is not None:
                if k_values is not None:
                    # Two constant terms: fold into one k.
                    k_values = {k + int(val.point) for k in k_values}
                else:
                    k_values = {int(val.point)}
                continue
            base.add(id(v))
        return base, k_values

    # ------------------------------------------------------------- helpers

    def _compare(self, name: str, a: AbsVal, b: AbsVal) -> AbsVal:
        ops = {
            "lt": (lambda: a.hi < b.lo, lambda: a.lo >= b.hi),
            "le": (lambda: a.hi <= b.lo, lambda: a.lo > b.hi),
            "gt": (lambda: a.lo > b.hi, lambda: a.hi <= b.lo),
            "ge": (lambda: a.lo >= b.hi, lambda: a.hi < b.lo),
            "eq": (
                lambda: a.point is not None and a.point == b.point,
                lambda: a.hi < b.lo or a.lo > b.hi,
            ),
            "ne": (
                lambda: a.hi < b.lo or a.lo > b.hi,
                lambda: a.point is not None and a.point == b.point,
            ),
        }
        always, never = ops[name]
        if a.bounded and b.bounded:
            if always():
                return AbsVal(1.0, 1.0, True)
            if never():
                return AbsVal(0.0, 0.0, True)
        return AbsVal(0.0, 1.0, True)

    def _dtype_range(self, var: Any) -> AbsVal:
        dtype = getattr(getattr(var, "aval", None), "dtype", None)
        if dtype is None:
            return TOP
        window = exact_int_window(dtype)
        if window is None:
            return TOP
        np_dtype = np.dtype(str(dtype)) if not isinstance(dtype, np.dtype) else dtype
        try:
            if np_dtype.kind == "u" or np_dtype.kind == "b":
                return AbsVal(0.0, float(window), True)
            if np_dtype.kind == "i":
                return AbsVal(float(np.iinfo(np_dtype).min), float(window), True)
        except Exception:
            pass
        return TOP

    def _clamp_int(self, val: AbsVal, var: Any) -> AbsVal:
        """Integer results that could exceed their dtype's range wrap; the
        sound abstraction is the full dtype range (the packed-wire byte sum
        relies on exactly this — 8 disjoint-bit terms wrap-free in uint8 is
        a VALUE property the interval cannot see, so the range widens to
        the dtype and the downstream unpack's `& 1` re-tightens it)."""
        dtype = getattr(getattr(var, "aval", None), "dtype", None)
        if dtype is None or not val.bounded:
            return val
        np_dtype = np.dtype(str(dtype))
        if np_dtype.kind not in ("i", "u"):
            return val
        info = np.iinfo(np_dtype)
        if val.lo < info.min or val.hi > info.max:
            return AbsVal(float(info.min), float(info.max), True, val.delta)
        return val


def _lit_value(atom: Any) -> Optional[int]:
    if _is_var(atom):
        return None
    try:
        val = np.asarray(atom.val)
        if val.size == 1:
            return int(val)
    except Exception:
        return None
    return None


def _is_int_dtype(name: str) -> bool:
    try:
        return np.dtype(name).kind in ("i", "u", "b")
    except TypeError:
        return False


# --------------------------------------------------------------------------
# Kernel specs, the audit, and the report.
# --------------------------------------------------------------------------


@dataclass
class RangeKernelSpec:
    """One kernel × geometry × contract assignment to prove.

    ``build`` returns ``(callable, abstract_args)`` (the same builders the
    IR auditor uses — the runtime's own constructors). ``input_contracts``
    assigns one declared contract per top-level invar (None = uncontracted:
    any dot it reaches is GR004). ``rows_per_flush``/``max_count`` mirror
    what the runtime's ``_flush`` feeds the projection formula;
    ``declared_rows`` is the max geometry (total variant rows) the GR001
    overflow proof covers."""

    name: str
    build: Callable[[], Tuple[Callable[..., Any], Tuple[Any, ...]]]
    input_contracts: Tuple[Optional[RangeContract], ...]
    axis_sizes: Dict[str, int] = field(default_factory=dict)
    #: Which invar is the accumulator (None = the kernel has none: delta
    #: tracking and the GR005 trigger check are skipped).
    acc_invar: Optional[int] = 0
    #: How many consecutive invars from ``acc_invar`` (and outputs) hold
    #: the accumulator: the half ring's step tiles, each holding other
    #: entries, so an entry's increment is the largest tile's.
    acc_tiles: int = 1
    rows_per_flush: int = 0
    max_count: int = 1
    operand_window_dtype: str = "bfloat16"
    accum_dtype: str = "float32"
    declared_rows: int = DECLARED_MAX_SITES
    projection: Callable[[int, int], int] = flush_entry_increment


@dataclass
class RangeAudit:
    """One kernel's range/exactness audit: findings + machine facts."""

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


def _emit(audit: RangeAudit, rule_id: str, detail: str) -> None:
    audit.findings.append(Finding(rule_id, audit.name, 0, 0, detail))


def _refined_increment(interp: Interpreter) -> Optional[float]:
    """Per-call per-entry accumulator increment from the recorded events:
    plain adds sum (× trips); dynamic_update_slice groups whose disjoint
    column-slice pattern is proven (same modulus/width/base, k values
    pairwise distinct mod D) count ONE dot partial per group; unproven dus
    events fall back to trips × growth. None = unprovable."""
    consumed = {e.update_id for e in interp.dus}
    total = 0.0
    for add in interp.adds:
        if add.out_id in consumed:
            continue
        if not math.isfinite(add.t_hi):
            return None
        total += add.t_hi * add.trips
    groups: Dict[Tuple[int, int, Tuple[int, ...]], List[DusEvent]] = {}
    loose: List[DusEvent] = []
    for ev in interp.dus:
        if ev.pattern is None:
            loose.append(ev)
        else:
            modulus, width, base_key, _ = ev.pattern
            groups.setdefault((modulus, width, base_key), []).append(ev)
    for (modulus, _w, _b), events in groups.items():
        ks: List[int] = []
        for ev in events:
            assert ev.pattern is not None
            ks.extend(ev.pattern[3])
        residues = [k % modulus for k in ks]
        if len(set(residues)) == len(residues):
            hi = max(ev.t_hi for ev in events)
            if not math.isfinite(hi):
                return None
            # One update per entry per RING PASS. A site's k values
            # enumerate exactly the scan iterations the pattern consumed
            # (one per proven-disjoint slice), so executions / |k values|
            # is the pass count of the scans OUTSIDE the pattern — the
            # enclosing block loop for the flat ring, the top level for
            # the two-radix hierarchical ring (whose k values already
            # span BOTH loop levels; multiplying by the outer scan's
            # trips would double-count its iterations).
            passes = max(
                -(-ev.trips // max(1, len(ev.pattern[3]))) for ev in events
            )
            total += hi * passes
        else:
            loose.extend(events)
    for ev in loose:
        if not math.isfinite(ev.t_hi):
            return None
        total += ev.t_hi * ev.trips
    return total


def audit_range_kernel(
    spec: RangeKernelSpec, traced: Optional[Any] = None
) -> RangeAudit:
    """Trace one kernel (or reuse a caller-supplied ``traced`` ClosedJaxpr
    of the SAME build — how the plan validator shares one trace between
    the IR and range audits) and prove its range/exactness contracts."""
    import jax

    audit = RangeAudit(spec.name)
    if traced is not None:
        closed = traced
    else:
        try:
            with jax.enable_x64(True):
                fn, args = spec.build()
                closed = jax.make_jaxpr(fn)(*args)
        except Exception as e:  # noqa: BLE001 — the trace failure is the finding
            _emit(
                audit,
                "GR000",
                f"kernel failed to trace: {type(e).__name__}: {e}",
            )
            return audit

    in_vals: List[AbsVal] = []
    for i, _ in enumerate(closed.jaxpr.invars):
        contract = (
            spec.input_contracts[i] if i < len(spec.input_contracts) else None
        )
        val = contract_val(contract)
        if (
            spec.acc_invar is not None
            and spec.acc_invar <= i < spec.acc_invar + spec.acc_tiles
        ):
            # The accumulator is abstracted as zero with delta (0,0): every
            # claim about it is RELATIVE (the per-call per-entry increment);
            # its absolute magnitude across a run is the geometry arithmetic
            # (GR001/GR005), not the jaxpr's business.
            val = replace(
                val,
                lo=0.0,
                hi=0.0,
                integer=True,
                delta=(0.0, 0.0),
                contracted=True,
            )
        in_vals.append(val)

    interp = Interpreter(spec.axis_sizes)
    outs = interp.run(closed, in_vals)
    if traced is None:
        del closed  # zero live arrays after the audit (test-asserted)

    audit.facts["input_contracts"] = [
        c.name if c is not None else None for c in spec.input_contracts
    ]
    audit.facts["accum_dtype"] = spec.accum_dtype

    # ---- GR004: uncontracted inputs reaching a dot --------------------
    for dot in interp.dots:
        if dot.uncontracted:
            _emit(
                audit,
                "GR004",
                "a dot_general consumes an operand with no declared range "
                "contract (ops/contracts.py) — interval "
                f"[{dot.operands[0].lo}, {dot.operands[0].hi}] × "
                f"[{dot.operands[1].lo}, {dot.operands[1].hi}]; no "
                "exactness claim about this kernel can be made",
            )

    # ---- GR002 / per-dispatch partial windows -------------------------
    accum_window = exact_int_window(spec.accum_dtype) or 0
    operand_window = exact_int_window(spec.operand_window_dtype) or 0
    partial_hi = 0.0
    accum_is_float = not _is_int_dtype(spec.accum_dtype)
    for dot in interp.dots:
        if dot.uncontracted:
            continue
        partial_hi = max(partial_hi, dot.out.magnitude)
        for op in dot.operands:
            if op.integer and op.magnitude > operand_window:
                _emit(
                    audit,
                    "GR002" if accum_is_float else "GR001",
                    f"dot operand interval [{op.lo:g}, {op.hi:g}] exceeds "
                    f"the {spec.operand_window_dtype} exact-integer window "
                    f"({operand_window}) — operands would round before the "
                    "multiply",
                )
        if not dot.out.integer:
            continue
        if dot.out.magnitude > accum_window:
            _emit(
                audit,
                "GR002" if accum_is_float else "GR001",
                f"per-dispatch partial can reach {dot.out.magnitude:g} "
                f"(contraction {dot.contraction} × operand bounds), past "
                f"the {spec.accum_dtype} exact window ({accum_window}) — "
                "exactness is lost BEFORE the conversion point can fire",
            )
    audit.facts["dot_partial_bound"] = partial_hi

    # ---- GR003: lossy narrowing casts ---------------------------------
    for conv in interp.converts:
        if not conv.src.integer:
            continue
        if np.dtype(conv.src_dtype).kind in ("i", "u"):
            info = np.iinfo(np.dtype(conv.src_dtype))
            if conv.src.lo <= info.min and conv.src.hi >= info.max:
                # Full-dtype-range source: pure bit entropy (hash/RNG
                # mixing), carrying no magnitude claim a narrowing could
                # lose — the int→int truncation IS the modular semantics
                # there. A magnitude that matters downstream still reaches
                # the accumulator dot and is bounded (or flagged) by
                # GR001/GR002/GR004.
                continue
        src_window = exact_int_window(conv.src_dtype)
        effective = conv.src.magnitude
        if src_window is not None:
            effective = min(effective, float(src_window))
        dst_window = exact_int_window(conv.dst_dtype)
        if dst_window is not None and effective > dst_window:
            _emit(
                audit,
                "GR003",
                f"convert_element_type {conv.src_dtype}→{conv.dst_dtype} "
                f"with inferred operand magnitude {effective:g} past the "
                f"destination's exact window ({dst_window}) — integer "
                "values would round or wrap",
            )

    # ---- per-dispatch entry increment + GR005 -------------------------
    if spec.acc_invar is not None:
        acc_out_deltas = [out.delta for out in outs if out.delta is not None][
            : spec.acc_tiles
        ]
        conservative = (
            max(d[1] for d in acc_out_deltas)
            if acc_out_deltas
            and all(math.isfinite(d[1]) for d in acc_out_deltas)
            else None
        )
        refined = _refined_increment(interp)
        increment = (
            min(x for x in (conservative, refined) if x is not None)
            if (conservative is not None or refined is not None)
            else None
        )
        audit.facts["entry_increment"] = increment
        audit.facts["entry_increment_conservative"] = conservative
        projection = spec.projection(spec.rows_per_flush, spec.max_count)
        audit.facts["flush_projection"] = projection
        if increment is None:
            _emit(
                audit,
                "GR005",
                "the per-dispatch accumulator entry increment is "
                "unprovable from the traced jaxpr (accumulator dataflow "
                "left the tracked forms) — the conversion trigger's "
                "projection cannot be verified conservative",
            )
        elif projection < increment:
            _emit(
                audit,
                "GR005",
                f"the runtime conversion trigger projects {projection} per "
                f"flush (ops/contracts.py:flush_entry_increment with rows="
                f"{spec.rows_per_flush}, max_count={spec.max_count}) but "
                f"the traced kernel can add {increment:g} to one entry "
                "per dispatch — the f32→int32 conversion could fire late",
            )

    # ---- GR001: declared-geometry accumulation ------------------------
    int32_window = exact_int_window(np.int32) or 0
    entry_bound = flush_entry_increment(spec.declared_rows, spec.max_count)
    audit.facts["gramian_entry_bound"] = entry_bound
    audit.facts["declared_rows"] = spec.declared_rows
    audit.facts["exactness_headroom_sites"] = {
        "float32": exactness_headroom_sites(np.float32, spec.max_count),
        "int32": exactness_headroom_sites(np.int32, spec.max_count),
    }
    if entry_bound > int32_window:
        _emit(
            audit,
            "GR001",
            f"declared geometry ({spec.declared_rows} rows × max_count "
            f"{spec.max_count}²) bounds an entry at {entry_bound}, past "
            f"int32's exact window ({int32_window}) — the terminal ladder "
            "rung can overflow; shrink the geometry contract",
        )
    if interp.unknown_prims:
        audit.facts["unhandled_primitives"] = sorted(interp.unknown_prims)
    return audit


# --------------------------------------------------------------------------
# The shipped audit matrix (the REAL kernels, via check/ir.py's builders).
# --------------------------------------------------------------------------

#: Mirrors check/ir.py's mesh matrix.
DEFAULT_MESHES: Tuple[Tuple[int, int], ...] = ((1, 2), (1, 4), (2, 2))


def dense_range_spec(
    data: int, num_samples: int, block_size: int
) -> RangeKernelSpec:
    from spark_examples_tpu.check.ir import dense_kernel_spec

    ir_spec = dense_kernel_spec(data, num_samples, block_size)
    return RangeKernelSpec(
        name=f"ranges:{ir_spec.name}",
        build=ir_spec.build,
        input_contracts=(None, PACKED_BYTE),
        rows_per_flush=data * block_size,
        max_count=HAS_VARIATION.hi,
        operand_window_dtype="bfloat16",
        accum_dtype="float32",
    )


def stacked_range_spec(
    jobs: int, num_samples: int, block_size: int
) -> RangeKernelSpec:
    """The fused batch groups' stacked-jobs update under the packed-byte
    contract. Unlike the dense spec's data axis, the jobs axis lanes are
    INDEPENDENT accumulators that never sum together at finalize (each
    job takes its own slice), so one drain step grows any single entry by
    at most ``block_size`` rows — not ``jobs * block_size``."""
    from spark_examples_tpu.check.ir import stacked_kernel_spec

    ir_spec = stacked_kernel_spec(jobs, num_samples, block_size)
    return RangeKernelSpec(
        name=f"ranges:{ir_spec.name}",
        build=ir_spec.build,
        input_contracts=(None, PACKED_BYTE),
        rows_per_flush=block_size,
        max_count=HAS_VARIATION.hi,
        operand_window_dtype="bfloat16",
        accum_dtype="float32",
    )


def counts_range_spec(
    data: int, num_samples: int, block_size: int
) -> RangeKernelSpec:
    from spark_examples_tpu.check.ir import counts_kernel_spec

    ir_spec = counts_kernel_spec(data, num_samples, block_size)
    return RangeKernelSpec(
        name=f"ranges:{ir_spec.name}",
        build=ir_spec.build,
        input_contracts=(None, COUNT_ROW),
        rows_per_flush=data * block_size,
        max_count=COUNT_ROW.hi,
        operand_window_dtype="bfloat16",
        accum_dtype="float32",
    )


def ring_range_spec(
    data: int,
    samples: int,
    num_samples: int,
    block_size: int,
    pack: bool,
    exact_int: bool,
    counts: bool = False,
) -> RangeKernelSpec:
    """``counts=True`` audits the UNPACKED ring under the count-valued
    contract: same-set-join flushes (entries up to ``COUNT_ROW.hi``) ride
    the unpacked kernel per flush regardless of ``--ring-pack-bits``
    (``ShardedGramianAccumulator._flush``), so the sharded join path's
    exactness needs its own proof — packed-[0,1] operands do not cover it."""
    from spark_examples_tpu.check.ir import ring_kernel_spec
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS

    if counts:
        pack = False  # count-valued blocks cannot bit-pack
    ir_spec = ring_kernel_spec(
        data, samples, num_samples, block_size, pack, exact_int=exact_int
    )
    contract = (
        COUNT_ROW if counts else (PACKED_BYTE if pack else HAS_VARIATION)
    )
    flavor = "int8" if exact_int else "bf16"
    return RangeKernelSpec(
        name=(
            f"ranges:{ir_spec.name}"
            f"[{flavor}{',counts' if counts else ''}]"
        ),
        build=ir_spec.build,
        input_contracts=(None, contract),
        axis_sizes={DATA_AXIS: data, SAMPLES_AXIS: samples},
        rows_per_flush=data * block_size,
        max_count=contract.hi if counts else HAS_VARIATION.hi,
        operand_window_dtype="int8" if exact_int else "bfloat16",
        accum_dtype="int32" if exact_int else "float32",
    )


def hier_range_spec(
    hosts: int,
    devices_per_host: int,
    num_samples: int,
    block_size: int,
    pack: bool,
    exact_int: bool,
    data: int = 1,
) -> RangeKernelSpec:
    """The hierarchical two-level ring under the same contracts as the
    flat ring (``graftcheck ranges --topology H,D``). The per-dispatch
    entry increment is refined by the TWO-RADIX disjoint-slice proof
    (``Interpreter._peel_two_radix``): every update slice's owner index is
    ``((h + k) mod H) * D + ((d + j) mod D)`` with the (k, j) pairs
    pairwise distinct across the double loop, so one entry still takes
    exactly ONE dot partial per pass and GR005 holds with the same
    runtime projection the flat ring uses."""
    from spark_examples_tpu.check.ir import hier_kernel_spec
    from spark_examples_tpu.parallel.mesh import (
        DATA_AXIS,
        HOST_AXIS,
        SAMPLES_AXIS,
    )

    ir_spec = hier_kernel_spec(
        data, hosts, devices_per_host, num_samples, block_size, pack,
        exact_int=exact_int,
    )
    contract = PACKED_BYTE if pack else HAS_VARIATION
    flavor = "int8" if exact_int else "bf16"
    return RangeKernelSpec(
        name=f"ranges:{ir_spec.name}[{flavor}]",
        build=ir_spec.build,
        input_contracts=(None, contract),
        axis_sizes={
            DATA_AXIS: data,
            HOST_AXIS: hosts,
            SAMPLES_AXIS: devices_per_host,
        },
        rows_per_flush=data * block_size,
        max_count=HAS_VARIATION.hi,
        operand_window_dtype="int8" if exact_int else "bfloat16",
        accum_dtype="int32" if exact_int else "float32",
    )


def devicegen_range_spec(
    data: int,
    samples: int,
    num_samples: int,
    block_size: int,
    blocks_per_dispatch: int = 2,
    pack: bool = True,
) -> RangeKernelSpec:
    """The fused generate-and-ring-accumulate dispatch
    (``ops/devicegen.py:_ring_update``) under the flat schedule. The
    genotype operands are GENERATED on device — their {0,1} range is not a
    declared input contract but the comparison lattice's own inference
    (``Interpreter``: a compare yields [0, 1] integer), so the dot
    operands arrive contracted without any input declaration. The flat
    ring is the half ring: its state is ⌊D/2⌋+1 step tiles, each taking
    one plain dot partial per pass, so GR005 reads the largest tile's
    increment. The scalar invars (row counters, kept-site counts,
    dispatch offsets, valid-site counts) carry the SITE_INDEX contract —
    all are bounded by the declared production geometry."""
    from spark_examples_tpu.check.ir import devicegen_ring_spec
    from spark_examples_tpu.parallel.mesh import (
        DATA_AXIS,
        SAMPLES_AXIS,
        half_ring_steps,
    )

    ir_spec = devicegen_ring_spec(
        data, samples, num_samples, block_size, blocks_per_dispatch, pack
    )
    steps = half_ring_steps(samples)
    return RangeKernelSpec(
        name=f"ranges:{ir_spec.name}",
        build=ir_spec.build,
        input_contracts=(None,) * steps + (SITE_INDEX,) * 4,
        acc_tiles=steps,
        axis_sizes={DATA_AXIS: data, SAMPLES_AXIS: samples},
        rows_per_flush=data * blocks_per_dispatch * block_size,
        max_count=HAS_VARIATION.hi,
        operand_window_dtype="int8",
        accum_dtype="int32",
    )


def devicegen_hier_range_spec(
    hosts: int,
    devices_per_host: int,
    num_samples: int,
    block_size: int,
    blocks_per_dispatch: int = 2,
    pack: bool = True,
    data: int = 1,
) -> RangeKernelSpec:
    """The fused generation ring under the hierarchical two-level
    schedule (``graftcheck ranges --topology H,D``): the same two-radix
    owner index ``((h + k) mod H) * D + ((d + j) mod D)`` as the host-fed
    hier kernel (``Interpreter._peel_two_radix``), so one Gramian entry
    still takes exactly ONE dot partial per ring pass (GR005) — the
    devicegen/hier seam is proven, not assumed."""
    from spark_examples_tpu.check.ir import devicegen_hier_spec
    from spark_examples_tpu.parallel.mesh import (
        DATA_AXIS,
        HOST_AXIS,
        SAMPLES_AXIS,
    )

    ir_spec = devicegen_hier_spec(
        data, hosts, devices_per_host, num_samples, block_size,
        blocks_per_dispatch, pack,
    )
    return RangeKernelSpec(
        name=f"ranges:{ir_spec.name}",
        build=ir_spec.build,
        input_contracts=(None, SITE_INDEX, SITE_INDEX, SITE_INDEX, SITE_INDEX),
        axis_sizes={
            DATA_AXIS: data,
            HOST_AXIS: hosts,
            SAMPLES_AXIS: devices_per_host,
        },
        rows_per_flush=data * blocks_per_dispatch * block_size,
        max_count=HAS_VARIATION.hi,
        operand_window_dtype="int8",
        accum_dtype="int32",
    )


def default_specs(
    num_samples: int = 64,
    block_size: int = 8,
    meshes: Sequence[Tuple[int, int]] = DEFAULT_MESHES,
    topologies: Sequence[Tuple[int, int]] = (),
) -> List[RangeKernelSpec]:
    """The shipped matrix: dense + counts per data-axis size, the ring
    kernel over every mesh shape × {packed, unpacked} × {int8, bf16}, the
    count-valued (same-set-join) unpacked ring per mesh shape, and the
    fused device-generation ring per mesh shape. ``topologies`` append the
    hierarchical two-level kernel per declared ``hosts,devices_per_host``
    pair (packed × {int8, bf16}) plus the hier devicegen ring."""
    specs: List[RangeKernelSpec] = []
    for data in sorted({d for d, _ in meshes}):
        specs.append(dense_range_spec(data, num_samples, block_size))
        specs.append(counts_range_spec(data, num_samples, block_size))
    # The fused batch groups' stacked program, same group sizes as the
    # ir matrix.
    for jobs in (2, 4):
        specs.append(stacked_range_spec(jobs, num_samples, block_size))
    for data, samples in meshes:
        if samples < 2:
            continue
        for pack in (True, False):
            for exact_int in (True, False):
                specs.append(
                    ring_range_spec(
                        data, samples, num_samples, block_size, pack, exact_int
                    )
                )
        specs.append(
            ring_range_spec(
                data, samples, num_samples, block_size, False, False,
                counts=True,
            )
        )
        specs.append(
            devicegen_range_spec(data, samples, num_samples, block_size)
        )
    for hosts, per_host in topologies:
        if hosts * per_host < 2:
            continue
        for exact_int in (True, False):
            specs.append(
                hier_range_spec(
                    hosts, per_host, num_samples, block_size, True, exact_int
                )
            )
        specs.append(
            devicegen_hier_range_spec(
                hosts, per_host, num_samples, block_size
            )
        )
    return specs


@dataclass
class RangesReport:
    audits: List[RangeAudit] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(a.ok for a in self.audits)

    @property
    def findings(self) -> List[Finding]:
        return [f for a in self.audits for f in a.findings]

    def to_json(self) -> str:
        return json.dumps(
            {
                "tool": "graftcheck-ranges",
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
                head = a.facts.get("exactness_headroom_sites", {})
                lines.append(
                    f"  proved: {a.name}: partial ≤ "
                    f"{a.facts.get('dot_partial_bound', 0):g}, entry "
                    f"increment ≤ {a.facts.get('entry_increment', 0):g}"
                    f"/flush (projection "
                    f"{a.facts.get('flush_projection', 0)}), headroom "
                    f"f32 {head.get('float32', 0)} / int32 "
                    f"{head.get('int32', 0)} sites"
                )
            else:
                for f in a.findings:
                    lines.append(f"  {f.format()}")
        verdict = "clean" if self.ok else f"{len(self.findings)} finding(s)"
        lines.append(
            f"graftcheck ranges: {len(self.audits)} kernel(s), {verdict}"
        )
        return "\n".join(lines)


def run_audit(specs: Optional[Sequence[RangeKernelSpec]] = None) -> RangesReport:
    """Audit ``specs`` (default: the shipped matrix). Pure tracing — zero
    device buffers survive the call (test-asserted)."""
    report = RangesReport()
    for spec in specs if specs is not None else default_specs():
        report.audits.append(audit_range_kernel(spec))
    return report


__all__ = [
    "AbsVal",
    "DEFAULT_MESHES",
    "Interpreter",
    "RangeAudit",
    "RangeKernelSpec",
    "RangesReport",
    "TOP",
    "audit_range_kernel",
    "contract_val",
    "counts_range_spec",
    "default_specs",
    "dense_range_spec",
    "devicegen_hier_range_spec",
    "devicegen_range_spec",
    "hier_range_spec",
    "ring_range_spec",
    "stacked_range_spec",
    "run_audit",
]
