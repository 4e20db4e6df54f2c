"""The graftcheck rule catalogue.

Each rule is one silent-failure class of this codebase's hot paths: the
linter (``linter.py``) walks the package AST and anchors findings to these
IDs. Scope globs keep repo-tuned rules out of code where the pattern is
legitimate (e.g. host-sync calls are fine in tests and the host oracle).

Adding a rule (see DESIGN.md §"graftcheck"):

1. register a :class:`Rule` here with a fresh ``GCnnn`` id;
2. implement its visitor hook in ``linter.py:_LintVisitor`` (emit via
   ``self.emit(RULE_ID, node, detail)``);
3. add a violation fixture + a clean fixture to
   ``tests/test_graftcheck.py`` asserting the id and line number.

Every rule honors the escape hatch::

    something_flagged()  # graftcheck: disable=GC001  -- justification

on the finding's line, or ``# graftcheck: disable-file=GC001`` anywhere in
the file (comma-separate multiple ids; ``disable=all`` silences the line).
"""

from __future__ import annotations

import fnmatch
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple


#: Directories (package-relative glob prefixes) that are "hot path" for
#: device-sync rules: per-block work that runs once per genotype block or
#: per shard, where one stray sync serializes the pipeline. ``analyses/*``
#: joined with the population-genetics subsystem: its per-window/per-block
#: device fetches are deliberate (host-sequential prune/chi-square) and
#: carry justified GC001 disables — new ones must justify themselves too.
HOT_PATH_GLOBS = ("ops/*", "pipeline/*", "analyses/*")

#: Ingest-concurrency scope: modules where threads share parse state, so
#: bare lock creation must carry the documented lock-ordering idiom
#: (a ``# lock order:`` comment on or just above the creation line).
#: ``serve/*`` joined when the resident service landed: its admission
#: queue, job table, and HTTP threads share state across the worker.
INGEST_GLOBS = (
    "sources/*",
    "pipeline/datasets.py",
    "utils/native.py",
    "serve/*",
    "analyses/*",
)

#: Telemetry scope: pipeline code whose counters must flow through the
#: metrics registry (``obs/metrics.py``) via the owning object's methods —
#: a bare ``stats.x += n`` bypasses both the lock and the manifest. The
#: service's control plane (``serve/*``) and the analyses layer
#: (``analyses/*``) carry the same obligation.
TELEMETRY_GLOBS = ("ops/*", "pipeline/*", "sources/*", "serve/*", "analyses/*")


@dataclass(frozen=True)
class Rule:
    """One lint rule: identity, scope, and the one-line rationale."""

    id: str
    name: str
    summary: str
    #: Package-relative path globs the rule applies to; empty = everywhere.
    scope: Tuple[str, ...] = ()

    def applies_to(self, relpath: str) -> bool:
        if not self.scope:
            return True
        return any(fnmatch.fnmatch(relpath, g) for g in self.scope)


RULES: Dict[str, Rule] = {
    rule.id: rule
    for rule in [
        Rule(
            "GC000",
            "unparseable-file",
            "The file does not parse as Python; the linter cannot vouch "
            "for it (and neither can the interpreter).",
        ),
        Rule(
            "GC001",
            "host-sync-in-hot-path",
            "Implicit device→host sync (.item()/float()/int()/np.asarray on "
            "a jnp value) inside per-block hot-path code stalls the dispatch "
            "pipeline once per call.",
            scope=HOT_PATH_GLOBS,
        ),
        Rule(
            "GC002",
            "python-branch-on-traced",
            "Python if/while on a traced value inside a jitted function "
            "raises TracerBoolConversionError at runtime (or silently "
            "specializes); use lax.cond/lax.while_loop or mark the argument "
            "static.",
        ),
        Rule(
            "GC003",
            "jit-in-loop",
            "jax.jit constructed inside a loop builds a fresh cache entry "
            "per iteration — a recompilation storm; hoist the jit (or "
            "functools.partial it) out of the loop.",
        ),
        Rule(
            "GC004",
            "jnp-at-import-time",
            "jnp.* executed at module import time initializes the backend "
            "(and can allocate device memory) as a side effect of `import`; "
            "move it into a function or use numpy for module constants.",
        ),
        Rule(
            "GC005",
            "accumulator-update-without-donation",
            "A jitted accumulator update without donate_argnums holds two "
            "live copies of the accumulator per step; donate the buffer or "
            "document why not (e.g. measured pipelining win).",
            scope=("ops/*",),
        ),
        Rule(
            "GC006",
            "undocumented-lock-in-ingest",
            "A bare threading lock in ingest code without the documented "
            "lock-ordering idiom (`# lock order:` comment) — the "
            "GIL-released parse pool makes ordering violations real "
            "deadlocks, not theoretical ones.",
            scope=INGEST_GLOBS,
        ),
        Rule(
            "GC007",
            "sync-inside-loop",
            "block_until_ready inside a loop syncs every iteration, "
            "serializing dispatch against compute; sync once after the "
            "loop, or bound the in-flight window instead.",
            scope=HOT_PATH_GLOBS,
        ),
        Rule(
            "GC008",
            "print-under-jit",
            "print() inside a jitted function runs at trace time only "
            "(once per compilation, with tracers, not values); use "
            "jax.debug.print for runtime values.",
        ),
        Rule(
            "GC009",
            "ad-hoc-stats-mutation",
            "Direct augmented assignment on a stats/counters object "
            "(`io_stats.requests += n`, `self.counters.x += 1`) bypasses "
            "the owner's accounting methods — and with them the lock and "
            "the metrics registry, so the mutation races concurrent "
            "workers and never reaches the run manifest; route it through "
            "an add_*() method.",
            scope=TELEMETRY_GLOBS,
        ),
        Rule(
            "GC011",
            "unjustified-narrowing-cast",
            "A narrowing astype/convert_element_type (int8/uint8/int16/"
            "uint16/int32/uint32/float16/bfloat16/float32 target) in ops/ "
            "without a range-justifying `# range:` comment or contract "
            "reference — the Gramian dtype ladder's exactness rests on "
            "every narrowing cast's operand range being an explicit, "
            "checkable claim (ops/contracts.py), not an unstated "
            "assumption graftcheck ranges cannot see.",
            scope=("ops/*",),
        ),
        Rule(
            "GC012",
            "raw-file-iteration-outside-stream",
            "A read-mode file handle (open/gzip.open/bz2.open/lzma.open) "
            "is iterated or .read*()-consumed directly in ingest/pipeline "
            "code instead of through the one windowed stream abstraction "
            "(sources/stream.py: iter_byte_windows/iter_text_lines/"
            "open_binary) — a raw handle is exactly where O(file) staging "
            "regrows; route the read through sources/stream.py so the "
            "hostmem totality proof keeps covering it.",
            scope=("sources/*", "pipeline/*"),
        ),
        Rule(
            "GC013",
            "journal-record-outside-journal",
            "A journal protocol record (a dict literal with an `event` "
            "key naming accepted/began/terminal/lease) is constructed — "
            "or a journal appender's `_append` is called — outside "
            "serve/journal.py. The record constructors there are the "
            "protocol's ONLY writers: `graftcheck proto` proves the "
            "coordination protocol against exactly those shapes, so a "
            "hand-rolled record elsewhere is a write the proof does not "
            "cover. Route it through journal.accepted_record/"
            "began_record/terminal_record/lease_record (or the JobJournal "
            "methods).",
        ),
        Rule(
            "GC010",
            "host-numpy-under-jit",
            "A host `np.*` call inside a jit/shard_map-decorated kernel "
            "either crashes on tracers (TracerArrayConversionError) or "
            "silently runs once at trace time on the host, baking its "
            "result into the compiled program; use the jnp equivalent "
            "(or hoist the host computation out of the kernel).",
            scope=("ops/*",),
        ),
    ]
}


#: ``graftcheck ir`` rule catalogue (``check/ir.py``): audits of the TRACED
#: jaxpr of the real Gramian kernels — contracts the AST layer cannot see.
#: GI findings anchor to a kernel name, not a source line, so their
#: ``path`` is the kernel's audit name and ``line`` is 0; justification
#: happens through the cross-checked GC005 AST disables (GI002), not
#: per-line escape hatches.
IR_RULES: Dict[str, Rule] = {
    rule.id: rule
    for rule in [
        Rule(
            "GI000",
            "kernel-trace-failure",
            "The kernel fails to trace to a jaxpr at all under the audit "
            "geometry; none of its IR contracts can be vouched for.",
        ),
        Rule(
            "GI001",
            "ring-overlap-broken",
            "A ring step's ppermute and that step's dot_general share a "
            "data dependency, so XLA must serialize the ICI transfer "
            "against the MXU matmul — the communication/compute overlap "
            "the double-buffered ring exists for silently vanishes.",
        ),
        Rule(
            "GI002",
            "accumulator-donation-contract",
            "A jitted accumulator update neither donates the accumulator "
            "buffer nor carries the justified GC005 AST disable (or "
            "carries a disable that no longer matches the traced "
            "donation) — the IR and AST layers have drifted.",
        ),
        Rule(
            "GI003",
            "packed-wire-upcast",
            "A bit-packed uint8 wire tile is widened or consumed by "
            "compute before the designated unpack "
            "(shift-and-mask), so the ring/PCIe wire silently loses its "
            "8-genotypes-per-byte format — 8x the traffic, or wrong math.",
        ),
        Rule(
            "GI004",
            "f64-in-kernel",
            "A float64 value appears inside a device kernel: some input "
            "promoted through a silent weak-type/x64 rule. f64 halves MXU "
            "throughput and doubles HBM; every kernel dtype is an "
            "explicit f32/int32/uint8 contract.",
        ),
        Rule(
            "GI005",
            "ring-traffic-mismatch",
            "The ICI bytes the traced jaxpr actually moves (ppermute "
            "operand bytes x scan trip counts x devices) disagree with "
            "the audited formula parallel/mesh.py:ring_traffic_bytes — "
            "the telemetry/plan numbers no longer describe the kernel.",
        ),
        Rule(
            "GI006",
            "ring-permute-count",
            "A ring pass does not execute exactly samples_axis - 1 "
            "ppermutes; an extra permute (the old return-to-owner step) "
            "wastes one full tile circulation per block, a missing one "
            "drops a device's columns.",
        ),
    ]
}


#: ``graftcheck ranges`` rule catalogue (``check/ranges.py``): an abstract
#: interpreter over the TRACED kernel jaxprs with an interval × exact-in-
#: dtype lattice, seeded from the declared input contracts
#: (``ops/contracts.py``) — the machine proof of the Gramian dtype ladder's
#: exactness chain (bf16×bf16→f32 partials exact < 2^24, int8×int8→int32
#: exact < 2^31, lossless conversion point). GR findings anchor to kernel
#: audit names (line 0), like the GI rules.
RANGES_RULES: Dict[str, Rule] = {
    rule.id: rule
    for rule in [
        Rule(
            "GR000",
            "kernel-range-trace-failure",
            "The kernel fails to trace to a jaxpr under the audit "
            "geometry; none of its range/exactness contracts can be "
            "vouched for.",
        ),
        Rule(
            "GR001",
            "int32-accumulator-overflow",
            "The int32 accumulator can overflow for the declared max "
            "geometry: declared rows x max_count² exceeds int32's 2^31-1 "
            "window, and the ladder has no wider in-accumulator rung — "
            "shrink the geometry contract or split the accumulation.",
        ),
        Rule(
            "GR002",
            "f32-partial-past-exact-window",
            "A per-dispatch f32 partial (a dot_general's output interval, "
            "derived from the declared input contracts) can exceed the "
            "2^24 exact-integer window BEFORE the accumulator conversion "
            "point ever sees it — the bf16/f32 path's exactness claim is "
            "false for this geometry.",
        ),
        Rule(
            "GR003",
            "lossy-narrowing-cast",
            "A convert_element_type whose inferred operand range is wider "
            "than the destination dtype's exact-integer window: integer "
            "values would round or wrap, silently corrupting the count "
            "semantics the dtype ladder promises to preserve.",
        ),
        Rule(
            "GR004",
            "uncontracted-dot-input",
            "A kernel input with no declared range contract "
            "(ops/contracts.py) reaches a dot_general: the prover has no "
            "interval to propagate, so no exactness claim about this "
            "kernel's partials or accumulator can be made at all.",
        ),
        Rule(
            "GR005",
            "conversion-trigger-not-conservative",
            "The runtime conversion trigger's projected per-flush "
            "increment (ops/contracts.py:flush_entry_increment, fed to "
            "_maybe_switch_accumulator) is SMALLER than the per-dispatch "
            "entry increment proven from the traced jaxpr — the f32→int32 "
            "conversion could fire after an entry already left the exact "
            "window.",
        ),
    ]
}


#: ``graftcheck hostmem`` scope: the host-staging layers whose ingest and
#: consume paths must be provably bounded-window (or carry a justified
#: ``hostmem(unbounded)`` declaration) — the host-RAM analog of the
#: HBM/ring-traffic bounds the plan validator already proves. ``serve/*``
#: joined with the resident service: a daemon that buffers request bodies
#: or job backlogs unboundedly would OOM exactly like an O(file) ingest.
#: ``analyses/*`` joined with the population-genetics subsystem: its
#: per-site (M-sized) outputs are exactly the shape an accidental O(M)
#: host list would silently break — the windowed writer discipline is
#: machine-checked from birth.
HOSTMEM_GLOBS = ("sources/*", "pipeline/*", "ops/*", "serve/*", "analyses/*")

#: ``graftcheck hostmem`` rule catalogue (``check/hostmem.py``): an AST
#: dataflow audit classifying every host ingest/consume path as
#: bounded-window or O(file). The audit is a TOTALITY proof: the
#: ``hostmem(unbounded)`` escape hatch that used to DECLARE a site::
#:
#:     raw = f.read()  # graftcheck: hostmem(unbounded) -- why this path is honestly O(file)
#:
#: is itself a finding now (GH006) — the declared inventory hit zero when
#: every source moved onto the windowed stream abstraction
#: (``sources/stream.py``), and the tree must PROVE boundedness, not
#: declare its absence. A hatch still routes its underlying GH00x finding
#: into the report's ``declared_unbounded`` inventory (so the report says
#: WHAT the hatch hides), but the hatch line fails the audit regardless.
HOSTMEM_RULES: Dict[str, Rule] = {
    rule.id: rule
    for rule in [
        Rule(
            "GH001",
            "whole-file-read",
            "A no-size .read()/.readlines() on a file handle stages the "
            "entire file in host RAM at once; read a bounded window in a "
            "loop, or declare the site hostmem(unbounded) with its "
            "justification.",
            scope=HOSTMEM_GLOBS,
        ),
        Rule(
            "GH002",
            "unbounded-stream-accumulation",
            "A list/buffer accumulates file- or stream-derived items "
            "inside the read loop, so peak host memory grows with the "
            "input instead of the window; consume per window, or declare "
            "the site hostmem(unbounded).",
            scope=HOSTMEM_GLOBS,
        ),
        Rule(
            "GH003",
            "stream-materialization",
            "list()/tuple() over a file handle or a streaming block "
            "producer materializes the whole stream the producer exists "
            "to keep windowed; iterate it, or declare the site "
            "hostmem(unbounded).",
            scope=HOSTMEM_GLOBS,
        ),
        Rule(
            "GH004",
            "whole-buffer-decompress",
            "A one-shot decompress (gzip/zlib/bz2/lzma .decompress) holds "
            "compressed AND decompressed copies of the payload at once; "
            "stream through the module's file interface (e.g. gzip.open "
            "windowed reads), or declare the site hostmem(unbounded).",
            scope=HOSTMEM_GLOBS,
        ),
        Rule(
            "GH005",
            "whole-buffer-numpy-staging",
            "np.frombuffer/np.packbits/np.concatenate/np.stack over a "
            "whole-file buffer (or a stream-accumulated list) stages an "
            "O(file) array on host; stage per chunk/block, or declare the "
            "site hostmem(unbounded).",
            scope=HOSTMEM_GLOBS,
        ),
        Rule(
            "GH006",
            "declared-unbounded-forbidden",
            "A `# graftcheck: hostmem(unbounded)` escape hatch — the "
            "declared-inventory era ended when the last O(file) site "
            "moved onto the windowed stream abstraction "
            "(sources/stream.py); the tree proves boundedness now, and a "
            "hatch (justified or not) is a finding, not a declaration. "
            "Refactor the site through "
            "iter_byte_windows/iter_text_lines/SpooledRecordTable/"
            "ChunkedArrayBuilder instead.",
            scope=HOSTMEM_GLOBS,
        ),
    ]
}


#: ``graftcheck sched`` rule catalogue (``check/sched.py``): schedule-level
#: audits of the collective reduction on a DECLARED topology
#: (``parallel/mesh.py:Topology`` — hosts x devices_per_host + per-link
#: bandwidths, proven against before the pod exists). The schedule is
#: extracted from the TRACED kernel jaxprs (every ppermute site with its
#: bytes, trip counts, mesh axis, and overlap flag) and simulated per link
#: class. GS findings anchor to a schedule subject name (line 0), like
#: the GI rules.
SCHED_RULES: Dict[str, Rule] = {
    rule.id: rule
    for rule in [
        Rule(
            "GS001",
            "flat-ring-on-dcn",
            "A flat ring is SELECTED on a multi-host topology: a ppermute "
            "over one flat mesh axis carries no host-boundary structure, "
            "so no hop is provably intra-host and the whole circulation "
            "rides the slow inter-host link — past the hierarchical "
            "schedule's proven DCN bound. Use --reduce-schedule hier (or "
            "auto) when the samples axis spans hosts.",
        ),
        Rule(
            "GS002",
            "schedule-formula-mismatch",
            "The per-level traffic simulated from the traced kernel's "
            "schedule disagrees with the audited closed forms "
            "(parallel/mesh.py:ring_traffic_bytes / "
            "hierarchical_traffic_bytes) — telemetry, the manifest's "
            "schedule block, and the plan validator no longer describe "
            "the schedule the kernel executes.",
        ),
        Rule(
            "GS003",
            "overlap-hole",
            "A link-bound schedule step has no concurrent compute proven "
            "dependency-free of it in the jaxpr: the transfer adds to the "
            "critical path instead of hiding behind the MXU — the "
            "schedule-level generalization of GI001, applied to BOTH "
            "levels of the hierarchical ring.",
        ),
        Rule(
            "GS004",
            "schedule-liveness-past-hbm",
            "The schedule's static per-device peak liveness (buffer-"
            "lifetime walk over the per-device shard_map body) exceeds "
            "the HBM fraction budget — the schedule cannot run at this "
            "geometry regardless of its traffic profile.",
        ),
        Rule(
            "GS005",
            "critical-path-past-budget",
            "The predicted schedule-limited critical path (per-level link "
            "time over the declared topology's bandwidths, overlap-aware) "
            "exceeds the declared --sched-budget-seconds — the plan "
            "cannot be proven to fit its time budget on this topology.",
        ),
    ]
}


#: ``graftcheck lockgraph`` rule catalogue (``check/lockgraph.py``): static
#: lock-acquisition-order analysis of the threaded ingest/telemetry layer.
#: GL findings anchor to real source lines, so the standard
#: ``# graftcheck: disable=GLnnn -- why`` escape hatch applies.
LOCK_RULES: Dict[str, Rule] = {
    rule.id: rule
    for rule in [
        Rule(
            "GL001",
            "lock-order-cycle",
            "The static lock-acquisition graph contains a cycle: two "
            "threads taking the member locks in opposite orders deadlock. "
            "Break the cycle or document a single global order.",
        ),
        Rule(
            "GL002",
            "device-sync-under-lock",
            "A lock is held across block_until_ready: every thread "
            "needing the lock stalls behind a device round-trip. Sync first, then take the "
            "lock.",
        ),
        Rule(
            "GL003",
            "blocking-queue-op-under-lock",
            "A lock is held across a blocking queue put/get: if the "
            "consumer that would drain the queue needs the same lock, the "
            "backpressure becomes a deadlock. Move the queue op outside "
            "the critical section (or use the _nowait form).",
        ),
        Rule(
            "GL004",
            "self-reacquire",
            "A non-reentrant threading.Lock is (possibly) acquired while "
            "already held on the same call path — an immediate "
            "self-deadlock. Use RLock only if the recursion is "
            "intentional; otherwise split the critical section.",
        ),
    ]
}


#: ``graftcheck proto`` rule catalogue (``check/proto.py``): invariants of
#: the replica coordination protocol, checked by exhaustive explicit-state
#: exploration with the SHIPPED serve/journal.py fold and lease arbitration
#: as the transition oracle. GP findings anchor to a witness trace (a
#: concrete crash/steal/append history), not a source line, so their
#: ``path`` is the protocol model's name and ``line`` is 0. There is no
#: escape hatch for a GP finding: a protocol counterexample is fixed, not
#: justified.
PROTO_RULES: Dict[str, Rule] = {
    rule.id: rule
    for rule in [
        Rule(
            "GP001",
            "double-effective-terminal",
            "One job reaches two terminal records that BOTH survive the "
            "fold's epoch fencing (or two replicas both publish its "
            "result): the journal's truth about the job's outcome is "
            "ambiguous — a deposed replica's late write settled a job "
            "its stealer also settled.",
        ),
        Rule(
            "GP002",
            "device-began-reexecution",
            "A job whose `began` record is journaled executes device "
            "work a second time in a later replica life: the "
            "requeue-once boundary is violated — device state under a "
            "crashed update cannot be trusted for a silent retry.",
        ),
        Rule(
            "GP003",
            "acked-job-lost",
            "A job whose admission was acknowledged (202 sent after the "
            "durable accepted record) becomes invisible: no journal "
            "record folds it as pending, no effective terminal exists, "
            "and no replica holds it in memory — after every crash is "
            "recovered, nobody will ever settle it.",
        ),
        Rule(
            "GP004",
            "lease-epoch-reissued",
            "A journaled lease record re-issues the job's highest "
            "already-journaled lease epoch under a DIFFERENT replica "
            "(the min-epoch claim guard failed): fold fencing cannot "
            "order same-epoch writers, so a zombie terminal would "
            "survive fencing. A lower-than-max straggler append is "
            "benign — the max-fold absorbs it.",
        ),
        Rule(
            "GP005",
            "steal-of-live-owner",
            "A replica successfully link-claims a fencing epoch over a "
            "lease that is still live — or expired but within the grace "
            "window — while its owner is alive: the grace asymmetry "
            "(owners abandon at expiry, stealers wait past expiry+grace) "
            "is violated and owner and stealer can run concurrently.",
        ),
        Rule(
            "GP006",
            "uncovered-crash-transition",
            "The model reaches a crash transition in a protocol window "
            "that no registered utils/faults.py KILL_POINT covers: the "
            "chaos matrix cannot rehearse this crash, so its recovery "
            "story is proven only in the model, never on the real "
            "daemon. Register a kill-point for the window (and enroll "
            "it in the chaos matrix) in the same change.",
        ),
    ]
}


#: Every rule id any graftcheck layer can emit, for Finding.rule lookup.
ALL_RULES: Dict[str, Rule] = {
    **RULES,
    **IR_RULES,
    **RANGES_RULES,
    **SCHED_RULES,
    **LOCK_RULES,
    **HOSTMEM_RULES,
    **PROTO_RULES,
}


@dataclass
class Finding:
    """One lint finding, JSON-serializable for the machine report."""

    rule_id: str
    path: str
    line: int
    col: int
    detail: str

    @property
    def rule(self) -> Rule:
        return ALL_RULES[self.rule_id]

    def format(self) -> str:
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule_id} "
            f"[{self.rule.name}] {self.detail}"
        )

    def to_json(self) -> Dict:
        return {
            "rule": self.rule_id,
            "name": self.rule.name,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "detail": self.detail,
        }


def parse_disables(
    source: str,
) -> Tuple[Dict[int, set], set]:
    """Extract the escape hatches from source text.

    Returns ``(per_line, whole_file)``: ``per_line`` maps 1-based line
    numbers to the set of rule ids disabled on that line (``{"all"}``
    disables every rule), ``whole_file`` is the set disabled for the file.
    Comment grammar::

        # graftcheck: disable=GC001,GC006  -- optional justification
        # graftcheck: disable-file=GC004   -- optional justification
    """
    per_line: Dict[int, set] = {}
    whole_file: set = set()
    for lineno, line in enumerate(source.splitlines(), start=1):
        marker = "# graftcheck:"
        at = line.find(marker)
        if at < 0:
            continue
        directive = line[at + len(marker) :].strip()
        for key, sink in (("disable-file=", whole_file), ("disable=", None)):
            if directive.startswith(key):
                ids = directive[len(key) :].split("--")[0]
                parsed = {
                    token.strip()
                    for token in ids.split(",")
                    if token.strip()
                }
                if sink is None:
                    per_line.setdefault(lineno, set()).update(parsed)
                else:
                    sink.update(parsed)
                break
    return per_line, whole_file


def apply_disables(
    findings: Sequence[Finding],
    per_line: Dict[int, set],
    whole_file: set,
) -> List[Finding]:
    """Drop findings silenced by an escape hatch."""

    def silenced(f: Finding) -> bool:
        if "all" in whole_file or f.rule_id in whole_file:
            return True
        ids = per_line.get(f.line, ())
        return "all" in ids or f.rule_id in ids

    return [f for f in findings if not silenced(f)]


__all__ = [
    "Rule",
    "Finding",
    "RULES",
    "IR_RULES",
    "RANGES_RULES",
    "SCHED_RULES",
    "LOCK_RULES",
    "HOSTMEM_RULES",
    "PROTO_RULES",
    "ALL_RULES",
    "HOT_PATH_GLOBS",
    "HOSTMEM_GLOBS",
    "INGEST_GLOBS",
    "TELEMETRY_GLOBS",
    "parse_disables",
    "apply_disables",
]
