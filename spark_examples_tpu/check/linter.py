"""AST-walking JAX-pitfall linter (the ``graftcheck lint`` engine).

Design: one :class:`_LintVisitor` pass per file, no type inference — every
rule is a syntactic pattern plus *scope* (which package subtree it applies
to, ``rules.py``) plus a small amount of dataflow that stays inside one
function body (names assigned from ``jnp.*`` expressions). The rules are
deliberately tuned to THIS repo's idioms; anything legitimately outside
them carries a ``# graftcheck: disable=ID -- why`` escape hatch, so the
merged tree lints clean and the linter can gate CI (``ci.sh``).

Import-alias resolution makes the patterns robust to import style:
``import jax.numpy as jnp``, ``from jax import numpy as jnp``,
``from jax import jit``, and ``from threading import Lock`` all resolve to
their canonical dotted names before matching.
"""

from __future__ import annotations

import ast
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from spark_examples_tpu.check.rules import (
    RULES,
    Finding,
    apply_disables,
    parse_disables,
)

#: Call roots that convert a device value to host (GC001 sinks).
_HOST_SINKS = ("float", "int", "numpy.asarray", "numpy.array", "numpy.float64")

#: Lock constructors that demand the lock-ordering idiom (GC006). Event is
#: excluded: it is a flag, not a mutual-exclusion primitive, and cannot
#: participate in a lock-ordering deadlock by itself.
_LOCK_CTORS = (
    "threading.Lock",
    "threading.RLock",
    "threading.Condition",
    "threading.Semaphore",
    "threading.BoundedSemaphore",
)

#: How far above a lock construction the ``# lock order:`` comment may sit.
_LOCK_COMMENT_WINDOW = 3

#: Spellings for GC009's finding text (the common augmented operators).
_AUG_OPS = {"Add": "+", "Sub": "-", "Mult": "*", "BitOr": "|"}

#: Canonical dotted names that resolve to shard_map (GC010's second
#: decoration context — a shard_map body executes per device under trace,
#: where a host numpy call is just as wrong as under jit).
_SHARD_MAP_NAMES = (
    "shard_map",
    "jax.shard_map",
)

#: GC011: cast targets narrow enough that the Gramian dtype ladder's
#: integer-exactness can silently break (anything with an exact-integer
#: window below f64's). A cast to one of these in ops/ must carry a
#: `# range:` comment (on the line, or within _RANGE_COMMENT_WINDOW lines
#: above — the `# lock order:` layout) stating why the operand range fits,
#: ideally naming its ops/contracts.py contract.
_NARROW_CAST_TARGETS = frozenset(
    {"int8", "uint8", "int16", "uint16", "int32", "uint32",
     "float16", "bfloat16", "float32"}
)

#: How far above a narrowing cast the `# range:` justification may sit —
#: wider than the lock-order window because the cast often sits mid-way
#: down a multi-line chained expression whose node anchors a few lines in.
_RANGE_COMMENT_WINDOW = 6

#: Canonical dotted names of the explicit cast function (GC011's second
#: spelling besides the .astype method).
_CONVERT_FNS = ("jax.lax.convert_element_type", "lax.convert_element_type")

#: GC012: callables whose result is a file handle. A READ-mode handle in
#: ``sources/``/``pipeline/`` may only live inside the one windowed stream
#: abstraction (``sources/stream.py``) — anywhere else, iterating it or
#: calling ``.read*()`` on it is the raw-ingest shape the hostmem totality
#: proof exists to keep out of the tree.
_FILE_OPEN_FNS = ("open", "io.open", "gzip.open", "bz2.open", "lzma.open")

#: The one module allowed to touch raw read handles (it IS the stream
#: abstraction), exempt from GC012 by construction.
_STREAM_MODULE = "sources/stream.py"

#: The one module allowed to construct journal protocol records (it IS
#: the protocol: its record constructors are the shapes `graftcheck
#: proto` proves the coordination protocol against), exempt from GC013
#: by construction.
_JOURNAL_MODULE = "serve/journal.py"

#: GC013: the protocol event names whose dict-literal construction is
#: reserved to serve/journal.py.
_JOURNAL_EVENTS = ("accepted", "began", "terminal", "lease")

#: numpy calls that are trace-time constants, not host compute: dtype
#: constructors used as astype/array arguments. These run on Python
#: scalars/metadata, never on traced values, and are pervasive legitimate
#: idiom in kernel signatures (``operand_dtype=np.int8``).
_NP_DTYPE_CTORS = frozenset(
    {"numpy.dtype", "numpy.int8", "numpy.int32", "numpy.int64",
     "numpy.uint8", "numpy.uint32", "numpy.uint64", "numpy.float32",
     "numpy.bool_"}
)


def _dotted(node: ast.AST, alias: Dict[str, str]) -> Optional[str]:
    """Canonical dotted name of a Name/Attribute chain, with the leading
    segment resolved through the file's import aliases; ``None`` for
    anything else (subscripts, calls, literals)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    parts.reverse()
    head = alias.get(parts[0], parts[0])
    return ".".join([head] + parts[1:])


def _collect_aliases(tree: ast.Module) -> Dict[str, str]:
    """Map local names to canonical dotted module paths, normalizing the
    numpy/jax spellings the rules match against."""
    alias: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for item in node.names:
                alias[item.asname or item.name.split(".")[0]] = (
                    item.name if item.asname else item.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            for item in node.names:
                alias[item.asname or item.name] = f"{node.module}.{item.name}"
    # Canonical spellings for the matchers (jnp/np import styles collapse).
    resolved = {}
    for name, target in alias.items():
        if target == "jax.numpy":
            resolved[name] = "jax.numpy"
        elif target in ("numpy", "np"):
            resolved[name] = "numpy"
        else:
            resolved[name] = target
    return resolved


def _is_jnp_rooted(node: ast.AST, alias: Dict[str, str]) -> bool:
    """Whether an expression's outermost call/attr chain starts at
    ``jax.numpy`` (covers ``jnp.sum(x)``, ``jnp.linalg.eigh(x)``)."""
    if isinstance(node, ast.Call):
        node = node.func
    name = _dotted(node, alias)
    return bool(name and name.startswith("jax.numpy."))


class _JitContext:
    """One jit-decorated function on the stack: its traced (non-static)
    parameter names, for GC002's branch test."""

    def __init__(self, traced_params: Set[str], fn_name: str):
        self.traced_params = traced_params
        self.fn_name = fn_name


def _jit_decoration(
    dec: ast.expr, alias: Dict[str, str]
) -> Optional[Dict[str, ast.expr]]:
    """If ``dec`` applies ``jax.jit``, return its keyword arguments
    (empty dict for the bare form); else ``None``. Recognized forms::

        @jax.jit                      @jit
        @functools.partial(jax.jit, static_argnames=...)
        @partial(jit, donate_argnums=...)
        @jax.jit(static_argnums=...)   (decorator-factory form)
    """
    name = _dotted(dec, alias)
    if name in ("jax.jit", "jax.jit.jit", "jit"):
        return {}
    if isinstance(dec, ast.Call):
        fn_name = _dotted(dec.func, alias)
        kwargs = {k.arg: k.value for k in dec.keywords if k.arg}
        if fn_name in ("jax.jit", "jit"):
            return kwargs
        if fn_name in ("functools.partial", "partial") and dec.args:
            inner = _dotted(dec.args[0], alias)
            if inner in ("jax.jit", "jit"):
                return kwargs
    return None


def _shard_map_decoration(dec: ast.expr, alias: Dict[str, str]) -> bool:
    """Whether ``dec`` applies shard_map (bare, factory, or partial form) —
    the traced-body context GC010 shares with jit."""
    if _dotted(dec, alias) in _SHARD_MAP_NAMES:
        return True
    if isinstance(dec, ast.Call):
        fn_name = _dotted(dec.func, alias)
        if fn_name in _SHARD_MAP_NAMES:
            return True
        if fn_name in ("functools.partial", "partial") and dec.args:
            if _dotted(dec.args[0], alias) in _SHARD_MAP_NAMES:
                return True
    return False


def _static_param_names(
    args: ast.arguments, jit_kwargs: Dict[str, ast.expr]
) -> Set[str]:
    """Resolve static_argnames/static_argnums to parameter names (constant
    specs only — dynamic specs conservatively leave params traced)."""
    posonly = [a.arg for a in getattr(args, "posonlyargs", [])]
    names = posonly + [a.arg for a in args.args]
    static: Set[str] = set()
    spec = jit_kwargs.get("static_argnames")
    if spec is not None:
        for node in ast.walk(spec):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                static.add(node.value)
    spec = jit_kwargs.get("static_argnums")
    if spec is not None:
        for node in ast.walk(spec):
            if isinstance(node, ast.Constant) and isinstance(node.value, int):
                if 0 <= node.value < len(names):
                    static.add(names[node.value])
    return static


class _LintVisitor(ast.NodeVisitor):
    def __init__(
        self,
        relpath: str,
        source_lines: Sequence[str],
        alias: Dict[str, str],
    ):
        self.relpath = relpath
        self.lines = source_lines
        self.alias = alias
        self.findings: List[Finding] = []
        self._loop_depth = 0
        self._func_depth = 0
        self._jit_stack: List[_JitContext] = []
        self._shard_map_depth = 0
        #: Per-function-scope set of names assigned from jnp expressions.
        self._jnp_names: List[Set[str]] = []
        #: Per-scope read-mode file-handle names (GC012); index 0 is the
        #: module scope.
        self._read_handles: List[Set[str]] = [set()]

    # ------------------------------------------------------------- plumbing

    def emit(self, rule_id: str, node: ast.AST, detail: str) -> None:
        rule = RULES[rule_id]
        if not rule.applies_to(self.relpath):
            return
        self.findings.append(
            Finding(
                rule_id,
                self.relpath,
                getattr(node, "lineno", 0),
                getattr(node, "col_offset", 0) + 1,
                detail,
            )
        )

    def _has_lock_order_comment(self, lineno: int) -> bool:
        lo = max(0, lineno - 1 - _LOCK_COMMENT_WINDOW)
        window = self.lines[lo:lineno]
        return any("lock order:" in line for line in window)

    def _has_range_comment(self, lineno: int) -> bool:
        lo = max(0, lineno - 1 - _RANGE_COMMENT_WINDOW)
        window = self.lines[lo:lineno]
        return any(
            "range:" in line or "ops/contracts" in line for line in window
        )

    # ------------------------------------------------------ GC012 (raw file)

    def _read_mode_open(self, node: ast.expr) -> bool:
        """Whether a call opens a file for READING (default mode counts;
        an unresolvable dynamic mode is conservatively read — the stream
        abstraction is where dynamic file plumbing belongs anyway)."""
        if not isinstance(node, ast.Call):
            return False
        if _dotted(node.func, self.alias) not in _FILE_OPEN_FNS:
            return False
        mode = None
        if len(node.args) >= 2:
            mode = node.args[1]
        else:
            for kw in node.keywords:
                if kw.arg == "mode":
                    mode = kw.value
        if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
            return not any(c in mode.value for c in "wax")
        return True

    def _bind_read_handles(self, value: ast.expr, target: ast.expr) -> None:
        if (
            self.relpath != _STREAM_MODULE
            and self._read_mode_open(value)
            and isinstance(target, ast.Name)
        ):
            self._read_handles[-1].add(target.id)

    def _is_raw_handle_iter(self, node: ast.expr) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self._read_handles[-1]
        if isinstance(node, ast.Call) and _dotted(node.func, self.alias) in (
            "enumerate",
            "zip",
            "iter",
            "reversed",
        ):
            return any(self._is_raw_handle_iter(arg) for arg in node.args)
        return False

    # ------------------------------------------------------------ functions

    def _visit_function(self, node) -> None:
        jit_kwargs = None
        for dec in getattr(node, "decorator_list", []):
            jit_kwargs = _jit_decoration(dec, self.alias)
            if jit_kwargs is not None:
                break
        sm_decorated = any(
            _shard_map_decoration(dec, self.alias)
            for dec in getattr(node, "decorator_list", [])
        )
        if sm_decorated:
            self._shard_map_depth += 1
        ctx = None
        if jit_kwargs is not None:
            static = _static_param_names(node.args, jit_kwargs)
            params = {a.arg for a in node.args.args} | {
                a.arg for a in getattr(node.args, "posonlyargs", [])
            }
            ctx = _JitContext(params - static - {"self"}, node.name)
            self._jit_stack.append(ctx)
            self._check_donation(node, jit_kwargs)
        self._func_depth += 1
        self._jnp_names.append(set())
        self._read_handles.append(set())
        # Loops outside don't lexically contain this body's dispatches.
        outer_loop_depth, self._loop_depth = self._loop_depth, 0
        self.generic_visit(node)
        self._loop_depth = outer_loop_depth
        self._read_handles.pop()
        self._jnp_names.pop()
        self._func_depth -= 1
        if ctx is not None:
            self._jit_stack.pop()
        if sm_decorated:
            self._shard_map_depth -= 1

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    def visit_Lambda(self, node: ast.Lambda) -> None:
        # A lambda body runs at CALL time: module-level `f = lambda x:
        # jnp.sum(x)` must not trip the import-time rule (GC004).
        self._func_depth += 1
        self._read_handles.append(set())
        self.generic_visit(node)
        self._read_handles.pop()
        self._func_depth -= 1

    def visit_With(self, node: ast.With) -> None:
        for item in node.items:
            if isinstance(item.optional_vars, ast.Name):
                self._bind_read_handles(
                    item.context_expr, item.optional_vars
                )
        self.generic_visit(node)

    visit_AsyncWith = visit_With  # type: ignore[assignment]

    def _check_donation(self, node, jit_kwargs: Dict[str, ast.expr]) -> None:
        """GC005: jitted accumulator-shaped updates must donate (or carry a
        justification disable). Heuristic: the function name says it updates
        state in place (update/accum/flush) and takes at least two params."""
        name = node.name.lower()
        if not any(tag in name for tag in ("update", "accum", "flush")):
            return
        n_params = len(node.args.args) + len(
            getattr(node.args, "posonlyargs", [])
        )
        if n_params < 2:
            return
        if {"donate_argnums", "donate_argnames"} & set(jit_kwargs):
            return
        self.emit(
            "GC005",
            node,
            f"jitted accumulator update {node.name!r} has no "
            "donate_argnums/donate_argnames; donating the accumulator "
            "halves its peak memory (disable with a justification if "
            "non-donation is a measured win)",
        )

    # ---------------------------------------------------------------- loops

    def _visit_loop(self, node) -> None:
        if isinstance(
            node, (ast.For, ast.AsyncFor)
        ) and self._is_raw_handle_iter(node.iter):
            self.emit(
                "GC012",
                node,
                "iterating a raw read-mode file handle outside the stream "
                "abstraction; route the read through sources/stream.py "
                "(iter_text_lines/iter_byte_windows) so the hostmem "
                "totality proof covers it",
            )
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop

    def visit_While(self, node: ast.While) -> None:
        self._check_branch_on_traced(node, "while")
        self._visit_loop(node)

    def visit_If(self, node: ast.If) -> None:
        self._check_branch_on_traced(node, "if")
        self.generic_visit(node)

    # ------------------------------------------------------- GC002 (branch)

    def _check_branch_on_traced(self, node, kind: str) -> None:
        if not self._jit_stack:
            return
        ctx = self._jit_stack[-1]
        test = node.test
        # `x is None` / `x is not None` and isinstance() never call a
        # tracer's __bool__; only value comparisons and bare names do.
        traced = self._traced_names_in_bool_test(test, ctx.traced_params)
        if traced:
            names = ", ".join(sorted(traced))
            self.emit(
                "GC002",
                node,
                f"Python `{kind}` on traced value(s) {names} inside jitted "
                f"{ctx.fn_name!r}; use lax.cond/lax.select/lax.while_loop "
                "or mark the argument static",
            )

    def _traced_names_in_bool_test(
        self, test: ast.expr, traced_params: Set[str]
    ) -> Set[str]:
        """Traced parameter names whose runtime VALUE the test branches on.

        Conservative by construction: identity tests (``is``/``is not``),
        ``isinstance``/callable probes, and attribute accesses (``x.ndim``,
        ``x.shape``) are trace-time Python values, not tracers — only bare
        names, value comparisons, boolean combinations, and negations of
        those convert a tracer to bool.
        """
        if isinstance(test, ast.Name):
            return {test.id} & traced_params
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._traced_names_in_bool_test(test.operand, traced_params)
        if isinstance(test, ast.BoolOp):
            out: Set[str] = set()
            for value in test.values:
                out |= self._traced_names_in_bool_test(value, traced_params)
            return out
        if isinstance(test, ast.Compare):
            if any(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
                return set()
            out = set()
            for operand in [test.left, *test.comparators]:
                if isinstance(operand, ast.Name):
                    out |= {operand.id} & traced_params
                elif isinstance(operand, ast.BinOp):
                    for sub in ast.walk(operand):
                        if isinstance(sub, ast.Name):
                            out |= {sub.id} & traced_params
            return out
        return set()

    # ----------------------------------------------------------- assignment

    def visit_Assign(self, node: ast.Assign) -> None:
        if self._jnp_names and _is_jnp_rooted(node.value, self.alias):
            for target in node.targets:
                if isinstance(target, ast.Name):
                    self._jnp_names[-1].add(target.id)
        for target in node.targets:
            self._bind_read_handles(node.value, target)
        self.generic_visit(node)

    # ------------------------------------------------- GC009 (stats bypass)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        """GC009: ``x.y += n`` where ``x`` is a stats/counters object —
        the mutation bypasses the owner's lock/registry-backed methods.
        Matched on the holder's name (any dotted segment named ``stats``/
        ``counters`` or suffixed ``_stats``/``_counters``), so the rule
        follows the objects wherever they are threaded."""
        target = node.target
        if isinstance(target, ast.Attribute):
            base = _dotted(target.value, self.alias)
            if base is not None and any(
                seg in ("stats", "counters")
                or seg.endswith("_stats")
                or seg.endswith("_counters")
                for seg in base.split(".")
            ):
                self.emit(
                    "GC009",
                    node,
                    f"direct `{base}.{target.attr} {_AUG_OPS.get(type(node.op).__name__, 'op')}= ...` "
                    "bypasses the stats object's accounting methods (lock "
                    "+ metrics registry); use its add_*() method so the "
                    "count is thread-safe and lands in the run manifest",
                )
        self.generic_visit(node)

    # ------------------------------------------- GC013 (journal records)

    def visit_Dict(self, node: ast.Dict) -> None:
        """GC013: a journal protocol record built as a dict literal
        outside serve/journal.py — matched on the shape itself (an
        ``"event"`` key naming a protocol event), so the rule catches a
        hand-rolled record whatever it is assigned to or passed into."""
        if self.relpath != _JOURNAL_MODULE:
            for key, value in zip(node.keys, node.values):
                if (
                    isinstance(key, ast.Constant)
                    and key.value == "event"
                    and isinstance(value, ast.Constant)
                    and value.value in _JOURNAL_EVENTS
                ):
                    self.emit(
                        "GC013",
                        node,
                        f"journal {value.value!r} record constructed as a "
                        "dict literal outside serve/journal.py; use "
                        f"journal.{value.value}_record(...) (or the "
                        "JobJournal method) so the record shape stays one "
                        "`graftcheck proto` has proven",
                    )
                    break
        self.generic_visit(node)

    # ----------------------------------------------------------------- call

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func, self.alias)

        # GC003: jit construction inside a loop body.
        if self._loop_depth > 0:
            jit_built = name in ("jax.jit", "jit") or (
                name in ("functools.partial", "partial")
                and node.args
                and _dotted(node.args[0], self.alias) in ("jax.jit", "jit")
            )
            if jit_built:
                self.emit(
                    "GC003",
                    node,
                    "jax.jit constructed inside a loop — every iteration "
                    "pays a cache lookup on a fresh callable (recompile "
                    "storm); hoist the jit out of the loop",
                )

        # GC004: jnp at import time (module/class body, not inside a def).
        if self._func_depth == 0 and name and name.startswith("jax.numpy."):
            self.emit(
                "GC004",
                node,
                f"{name.replace('jax.numpy', 'jnp')}(...) executed at import "
                "time initializes the JAX backend as an import side effect; "
                "move into a function or use numpy",
            )

        # GC006: bare lock construction in ingest code.
        if name in _LOCK_CTORS and not self._has_lock_order_comment(
            node.lineno
        ):
            self.emit(
                "GC006",
                node,
                f"{name}() in ingest code without the lock-ordering idiom; "
                "add a `# lock order: ...` comment on or just above this "
                "line stating what may be held when taking it",
            )

        # GC007: per-iteration device sync.
        if self._loop_depth > 0:
            syncs = name == "jax.block_until_ready" or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            )
            if syncs:
                self.emit(
                    "GC007",
                    node,
                    "block_until_ready inside a loop serializes dispatch "
                    "against compute; sync once after the loop or bound "
                    "the in-flight window",
                )

        # GC008: trace-time print under jit.
        if self._jit_stack and name == "print":
            self.emit(
                "GC008",
                node,
                f"print() inside jitted {self._jit_stack[-1].fn_name!r} "
                "runs at trace time with tracers; use jax.debug.print",
            )

        # GC010: host numpy call inside a traced kernel body.
        if (
            (self._jit_stack or self._shard_map_depth)
            and name
            and name.startswith("numpy.")
            and name not in _NP_DTYPE_CTORS
        ):
            where = (
                f"jitted {self._jit_stack[-1].fn_name!r}"
                if self._jit_stack
                else "a shard_map-decorated kernel"
            )
            self.emit(
                "GC010",
                node,
                f"{name.replace('numpy', 'np')}(...) inside {where} runs "
                "on the HOST at trace time: it crashes on tracers or "
                "silently bakes a trace-time constant into the compiled "
                "program; use the jnp equivalent",
            )

        # GC012: .read*() on a raw read-mode handle outside stream.py.
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in ("read", "read1", "readline", "readlines")
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in self._read_handles[-1]
        ):
            self.emit(
                "GC012",
                node,
                f"`{node.func.value.id}.{node.func.attr}()` on a raw "
                "read-mode file handle outside the stream abstraction; "
                "route the read through sources/stream.py "
                "(open_binary/iter_byte_windows) so the hostmem totality "
                "proof covers it",
            )

        # GC013: a journal appender's private _append outside journal.py
        # (the public record methods are the protocol surface; _append
        # would smuggle an arbitrary record past the proven shapes).
        if (
            self.relpath != _JOURNAL_MODULE
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "_append"
            and "journal" in (_dotted(node.func.value, self.alias) or "").lower()
        ):
            self.emit(
                "GC013",
                node,
                "journal._append() called outside serve/journal.py — the "
                "appender's private seam bypasses the record constructors "
                "`graftcheck proto` proves the protocol against; use the "
                "JobJournal record methods",
            )

        # GC011: narrowing cast without a range justification.
        self._check_narrowing_cast(node, name)

        # GC001: implicit device→host sync in hot paths.
        self._check_host_sink(node, name)

        # .item() on anything in a hot path is a per-call sync.
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "item"
            and not node.args
            and not node.keywords
        ):
            self.emit(
                "GC001",
                node,
                ".item() forces a device→host sync per call in hot-path "
                "code; batch values and fetch once (see "
                "parallel/mesh.py:packed_host_fetch)",
            )

        self.generic_visit(node)

    def _check_narrowing_cast(
        self, node: ast.Call, name: Optional[str]
    ) -> None:
        """GC011: ``.astype(<narrow dtype>)`` / ``lax.convert_element_type``
        in ops/ must carry a ``# range:`` justification (or an
        ``ops/contracts`` reference) within the comment window — the
        operand-range claim behind a narrowing cast belongs next to the
        cast, where ``graftcheck ranges`` (check/ranges.py) can hold the
        prose against the proven interval. Dynamic targets (a dtype held in
        a variable, e.g. ``operand_dtype``) are skipped: their range story
        lives at the variable's producer."""
        target = None
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "astype"
            and len(node.args) == 1
            and not node.keywords
        ):
            target = node.args[0]
        elif name in _CONVERT_FNS:
            if len(node.args) >= 2:
                target = node.args[1]
            else:
                for kw in node.keywords:
                    if kw.arg == "new_dtype":
                        target = kw.value
        if target is None:
            return
        dotted = _dotted(target, self.alias)
        if dotted is None:
            return  # dtype variable / np.dtype(...) call — producer's story
        leaf = dotted.rsplit(".", 1)[-1]
        if leaf not in _NARROW_CAST_TARGETS:
            return
        if self._has_range_comment(node.lineno):
            return
        self.emit(
            "GC011",
            node,
            f"narrowing cast to {leaf} without a range justification; add "
            "a `# range: ...` comment (or reference the operand's "
            "ops/contracts.py contract) stating why every value fits the "
            "destination's exact window",
        )

    def _check_host_sink(self, node: ast.Call, name: Optional[str]) -> None:
        if name not in _HOST_SINKS or len(node.args) != 1:
            return
        arg = node.args[0]
        jnp_value = _is_jnp_rooted(arg, self.alias) or (
            isinstance(arg, ast.Name)
            and any(arg.id in scope for scope in self._jnp_names)
        )
        if jnp_value:
            self.emit(
                "GC001",
                node,
                f"{name}() on a jnp value forces an implicit device→host "
                "sync in hot-path code; keep the value on device or batch "
                "the fetch (parallel/mesh.py:packed_host_fetch)",
            )


def lint_source(
    source: str, relpath: str, honor_disables: bool = True
) -> List[Finding]:
    """Lint one file's text; ``relpath`` (package-relative, '/'-separated)
    drives rule scoping. Returns findings sorted by (line, rule)."""
    tree = ast.parse(source, filename=relpath)
    alias = _collect_aliases(tree)
    visitor = _LintVisitor(relpath, source.splitlines(), alias)
    visitor.visit(tree)
    findings = visitor.findings
    if honor_disables:
        per_line, whole_file = parse_disables(source)
        findings = apply_disables(findings, per_line, whole_file)
    return sorted(findings, key=lambda f: (f.line, f.rule_id, f.col))


def _package_relpath(path: str) -> str:
    """Scope-resolvable relpath of one file: relative to the topmost
    enclosing package root (the highest ancestor chain of directories that
    all carry ``__init__.py``), so ``graftcheck lint <pkg>/ops/gramian.py``
    sees the same ``ops/gramian.py`` relpath — and therefore the same
    scoped rules — as a whole-tree lint."""
    path = os.path.abspath(path)
    top = cur = os.path.dirname(path)
    while os.path.exists(os.path.join(cur, "__init__.py")):
        top = cur  # the highest dir that is itself a package
        parent = os.path.dirname(cur)
        if parent == cur:
            break
        cur = parent
    return os.path.relpath(path, top).replace(os.sep, "/")


def _iter_py_files(root: str) -> Iterable[Tuple[str, str]]:
    """Yield ``(abs_path, relpath)`` for package .py files under ``root``
    (or the single file itself), skipping caches."""
    if os.path.isfile(root):
        yield root, _package_relpath(root)
        return
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [
            d for d in dirnames if d not in ("__pycache__", ".git")
        ]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                full = os.path.join(dirpath, fn)
                yield full, os.path.relpath(full, root).replace(os.sep, "/")


def lint_paths(paths: Sequence[str]) -> Tuple[List[Finding], int]:
    """Lint files/trees; returns ``(findings, files_checked)``."""
    findings: List[Finding] = []
    checked = 0
    for root in paths:
        for full, relpath in _iter_py_files(root):
            with open(full, "r", encoding="utf-8") as f:
                source = f.read()
            try:
                findings.extend(lint_source(source, relpath))
            except SyntaxError as e:
                findings.append(
                    Finding(
                        "GC000",
                        relpath,
                        e.lineno or 0,
                        (e.offset or 0),
                        f"syntax error: {e.msg}",
                    )
                )
            checked += 1
    return findings, checked


def json_report(findings: Sequence[Finding], checked: int) -> str:
    """Machine-readable report (one stable schema for CI tooling)."""
    return json.dumps(
        {
            "tool": "graftcheck",
            "checked_files": checked,
            "finding_count": len(findings),
            "findings": [f.to_json() for f in findings],
        },
        indent=2,
    )


__all__ = ["lint_source", "lint_paths", "json_report"]
