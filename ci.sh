#!/usr/bin/env bash
# CI gate, staged:
#   1. tier-1 tests — the exact command from ROADMAP.md, unchanged: exits
#      non-zero on any test failure and prints the DOTS_PASSED count the
#      growth driver tracks (this stage's semantics are a contract).
#   2. lint  — graftcheck lint (JAX-pitfall linter; the tree must be
#      clean or carry justified disables) + the mypy baseline gate
#      (skips with a notice when mypy is not installed).
#   2b. ir — graftcheck ir (jaxpr-level audit of the real Gramian kernels:
#      ring overlap schedule, donation contract, packed-wire dtype flow,
#      jaxpr ring bytes == ring_traffic_bytes) + graftcheck lockgraph
#      (static lock-acquisition-order graph of the ingest/obs layer must
#      be acyclic and free of sync/queue-under-lock); the DOT graph
#      artifact is left under the stage's run dir (path echoed).
#   2c. ranges — graftcheck ranges (abstract-interpretation overflow &
#      exactness prover over the real kernel jaxprs: bf16/f32 per-dispatch
#      partials < 2^24, int32 accumulation < 2^31, lossy casts, contract
#      coverage, conversion-trigger conservativeness) across the full
#      mesh/dtype audit matrix — the Gramian dtype ladder is PROVEN on
#      every build, not asserted.
#   2c2. sched — graftcheck sched (device-free collective-schedule prover:
#      the schedule extracted from the traced kernel jaxprs is simulated
#      per link class over the topology matrix incl. the 32x8 pod —
#      per-level traffic == the closed forms, overlap clean, liveness in
#      budget) + the 4-virtual-device hier-vs-flat smoke: the same sharded
#      run through --reduce-schedule flat and hier (2 "hosts" x 2 devices
#      via SPARK_EXAMPLES_TPU_HIER_HOSTS) must produce byte-identical
#      result rows, valid manifest schedule blocks with predicted ==
#      measured ring bytes, and hier DCN bytes strictly below flat's.
#   2c3. multihost — a REAL 2-process x 2-virtual-device gloo fleet
#      (parallel/multihost.py): coordinator-connected child checks (global
#      mesh, cross-process ring, hierarchical ring) all byte-identical to
#      the host oracle, then the full variants-pca CLI as a fleet with
#      HOST-SHARDED ingest — per-process ingested reference bases ~1/H of
#      the solo oracle's (summing exactly to it), PC rows byte-identical
#      to solo, per-host conformance bounds ok in every process manifest,
#      and the per-process flight-recorder segments merged into one
#      validate_chrome_trace-clean Chrome trace.
#   2d. hostmem — graftcheck hostmem (AST host-memory audit: ZERO
#      findings and an EMPTY declared_unbounded inventory — the
#      escape-hatch era is over, GH006 flags the syntax itself) + the
#      --host-mem-budget smoke on the 4-virtual-device synthetic config
#      (a generous budget must plan OK, a 1 MiB budget must exit 2 — the
#      static bound, parallel/mesh.py:host_peak_bytes, is enforced, not
#      just printed) + the wire-ingest budget smoke: generated JSONL and
#      SAM inputs plan OK under an 8 GiB budget (the retired
#      "unprovable" class) and the JSONL run's measured peak RSS must
#      sit under its manifest's static bound.
#   3. obs smoke — a tiny synthetic PCA run with --metrics-json and a
#      1 s heartbeat; the produced run manifest must validate against the
#      schema (obs/manifest.py:validate_manifest), carry I/O stats, and
#      prove measured peak RSS <= the static host-memory bound (the
#      runtime half of the hostmem contract). A second tiny run with
#      --ingest packed --check-ranges asserts the manifest's
#      gramian_exactness pair: measured max |accumulator entry| <= the
#      statically-projected bound (the runtime half of the ranges
#      contract). Both runs must also carry the v2-additive conformance
#      block (prover-conformance pairs) with ok=true for hostmem (and
#      ranges on the second run); the sharded-ring smoke below asserts
#      the sched pair the same way.
#   4. sharded-ring smoke — a 4-virtual-device sharded run (tiny synthetic
#      cohort) twice: packed ring (--ring-pack-bits on) vs the unpacked
#      oracle (off). Result rows must be byte-identical and the manifests'
#      gramian_ring_bytes must show the >= 8x packed traffic reduction —
#      the ring path can never regress silently on a CPU-only runner.
#   4b. analyses smoke — the population-genetics analyses (analyses/) end
#      to end on CPU: plan entries accept valid GRM/LD/assoc configs and
#      exit-2 reject doomed ones; a tiny synthetic GRM run's kinship TSV
#      byte-compares against the full-matrix NumPy oracle; a 2-contig LD
#      prune is deterministic across runs and oracle-exact; an assoc scan
#      with a planted signal (phenotype = one site's carrier vector) ranks
#      that site top. Every run's manifest validates with the v2-additive
#      analysis block.
#   5. serve smoke — the resident daemon (serve/) end to end on CPU: start
#      `python -m spark_examples_tpu serve` with a synthetic source, assert
#      a plan-invalid request returns a structured 400 carrying the plan
#      finding, an accepted tiny job completes with a valid per-job
#      schema-v2 manifest, the identical resubmit reports a warm
#      compile-cache hit (hit counter >= 1 in /metrics), and SIGTERM
#      drains gracefully: the in-flight job finishes, new jobs get 503,
#      the daemon exits 0.
#   5b. serve concurrency smoke — the executor-slice daemon on 4 virtual
#      CPU devices (--executor-slices 1): a small job (via the
#      `submit --wait` verb) completes WHILE a large job is still on the
#      large slice (no head-of-line blocking); a second large job queued
#      mid-run survives `kill -9` of the daemon — the restarted daemon
#      replays the job journal, finishes the queued job, fails the
#      mid-device job with a structured daemon-restarted error, and
#      serves a repeat-geometry job warm from the run-dir persistent
#      state. Then the serve-load harness (bench.py --config serve-load)
#      drives mixed traffic through the HTTP API and asserts small-job
#      P99 under concurrent large-job load stays within ~2x its unloaded
#      P99 and below the large job's wall-clock.
#   5c. multi-replica serving smoke — two replica daemons (--replica-id
#      a/b) on ONE run dir: a large job lands on a, whose fault plan
#      SIGKILLs it the moment device work begins (`kill -9 ... mid-
#      device`); small jobs keep flowing through b throughout; b steals
#      the orphaned job under a fencing epoch and — per the journaled
#      device_began rule — settles it with the structured
#      replica-failover error instead of silently re-running the
#      devices; the comma-separated client endpoint list fails over off
#      the dead replica; the run dir's flight-recorder segments + journal
#      are then merged by `trace export` into one Chrome-trace JSON that
#      must validate well-formed (obs/trace.py:validate_chrome_trace) with
#      the stolen job's span tree complete across BOTH replica processes:
#      the killed owner's span closed as truncated, a whole steal flow
#      arrow, lease epochs and the fenced terminal state present, zero
#      orphan spans; `graftcheck lockgraph` stays acyclic with the
#      lease-substrate locks. Then the full two-replica chaos matrix
#      (tests/test_serve_replicas_chaos.py): SIGKILL at every registered
#      serve kill-point, survivor results byte-compared against a
#      single-replica oracle.
#   6. faults — the robustness smoke, CPU-pinned: an oracle run, the same
#      run SIGKILLed by a deterministic fault plan at the
#      checkpoint.post-save kill-point (exit must be 137), then
#      --resume-from — resumed eigenvectors must be byte-identical to the
#      oracle and the manifest's resume block must show a real
#      fast-forward. Then the serve watchdog end to end in-process: an
#      injected worker crash mid-job must leave the job `failed` with a
#      structured worker-crashed error, the daemon healthy, the next job
#      completing, and the drain clean.
#   7. sanitize (opt-in: `ci.sh --sanitize`) — ASAN/UBSAN/TSAN replay of
#      the VCF fuzz corpus against the native parser; skips gracefully
#      when no C++ compiler is available.
# Run from the repo root. Exit code: first failing stage wins, tier-1 first.
set -o pipefail

SANITIZE=0
for arg in "$@"; do
  case "$arg" in
    --sanitize) SANITIZE=1 ;;
    *) echo "ci.sh: unknown flag: $arg" >&2; exit 2 ;;
  esac
done

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

echo "== lint stage (graftcheck) =="
lint_rc=0
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck lint spark_examples_tpu || lint_rc=$?
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck typecheck || lint_rc=$?

echo "== proto stage (graftcheck proto: replica-protocol model checking) =="
proto_rc=0
# The declared 2-replica / 2-job / 2-crash matrix, exhaustively (the
# report echoes its bounds and explored-state count). stalls=0 here;
# the lease expiry/steal/adoption dimension follows at jobs=1 —
# together the two exhaustive runs reach every transition type the
# model has (see check/proto.py:check_protocol).
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck proto || proto_rc=$?
PROTO_TMP=$(mktemp -d)
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck proto \
  --jobs 1 --stalls 2 --json > "$PROTO_TMP/stall.json" || proto_rc=$?
env JAX_PLATFORMS=cpu python - "$PROTO_TMP/stall.json" <<'PYEOF' || proto_rc=$?
import json, sys
doc = json.load(open(sys.argv[1]))
bounds = ", ".join(f"{k}={v}" for k, v in sorted(doc["bounds"].items()))
if not doc["exhausted"] or doc["states"] <= 0:
    print(f"proto stall run NOT exhaustive at [{bounds}]"); sys.exit(1)
if doc["uncovered_windows"]:
    print("proto stall run uncovered crash windows:",
          doc["uncovered_windows"]); sys.exit(1)
if not doc["ok"]:
    print("proto stall run findings:")
    for f in doc["findings"]:
        print(" ", f)
    sys.exit(1)
print(f"proto stall run OK: {doc['states']} states explored at "
      f"[{bounds}], 0 findings, 0 uncovered crash windows")
PYEOF
rm -rf "$PROTO_TMP"
# The checker's own test suite: every planted single-decision protocol
# bug must be caught by its matching GP rule at its witness bounds.
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck proto \
  --mutations || proto_rc=$?

echo "== ir stage (graftcheck ir + lockgraph) =="
ir_rc=0
IR_TMP=$(mktemp -d /tmp/graftcheck-ir.XXXXXX)
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck ir || ir_rc=$?
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck lockgraph \
  --dot "$IR_TMP/lockgraph.dot" || ir_rc=$?
if [ -s "$IR_TMP/lockgraph.dot" ]; then
  echo "lock-order DOT artifact: $IR_TMP/lockgraph.dot"
else
  echo "lockgraph DOT artifact missing"; ir_rc=1
fi

echo "== ranges stage (graftcheck ranges) =="
rg_rc=0
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck ranges || rg_rc=$?

echo "== sched stage (graftcheck sched + hier-vs-flat smoke) =="
sched_rc=0
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck sched || sched_rc=$?
SCHED_TMP=$(mktemp -d)
# Hier-vs-flat parity on 4 virtual devices: the same sharded run through
# the flat ring and the two-level schedule (2 "hosts" x 2 devices via the
# rehearsal override) must produce BYTE-IDENTICAL result rows, and both
# manifests must carry a valid schedule block whose predicted bytes match
# the per-flush accounting (delta 0 on an all-packed run).
sched_flags="--num-samples 64 --references 1:0:400000 --mesh-shape 1,4 \
  --similarity-strategy sharded --block-size 64 --ingest packed"
for mode in flat hier; do
  env JAX_PLATFORMS=cpu \
      SPARK_EXAMPLES_TPU_NO_CACHE=1 SPARK_EXAMPLES_TPU_HIER_HOSTS=2 \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m spark_examples_tpu variants-pca $sched_flags \
      --reduce-schedule "$mode" --metrics-json "$SCHED_TMP/$mode.json" \
      > "$SCHED_TMP/$mode.out" 2> "$SCHED_TMP/$mode.err" || sched_rc=$?
done
if [ "$sched_rc" -eq 0 ]; then
  grep -P "\t" "$SCHED_TMP/flat.out" > "$SCHED_TMP/flat.tsv"
  grep -P "\t" "$SCHED_TMP/hier.out" > "$SCHED_TMP/hier.tsv"
  if ! cmp -s "$SCHED_TMP/flat.tsv" "$SCHED_TMP/hier.tsv"; then
    echo "hier result rows DIFFER from the flat-ring oracle"
    sched_rc=1
  fi
fi
if [ "$sched_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python - "$SCHED_TMP/flat.json" "$SCHED_TMP/hier.json" <<'PYEOF' || sched_rc=$?
import sys
from spark_examples_tpu.obs.manifest import read_manifest, validate_manifest
docs = {}
for path in sys.argv[1:3]:
    doc = read_manifest(path)
    errors = validate_manifest(doc)
    if errors:
        print("schedule manifest INVALID:\n  " + "\n  ".join(errors))
        sys.exit(1)
    docs[path] = doc["schedule"]
flat, hier = docs[sys.argv[1]], docs[sys.argv[2]]
for name, blk in (("flat", flat), ("hier", hier)):
    if blk is None:
        print(f"{name} run carries no schedule block"); sys.exit(1)
    if blk["predicted_ring_bytes"] != blk["measured_ring_bytes"]:
        print(f"{name} predicted != measured ring bytes: {blk}"); sys.exit(1)
if flat["kind"] != "flat" or hier["kind"] != "hier":
    print(f"schedule kinds wrong: {flat['kind']}/{hier['kind']}"); sys.exit(1)
if not (0 < hier["predicted_dcn_bytes"] < flat["predicted_dcn_bytes"]):
    print("hier DCN bytes not strictly below flat DCN bytes: "
          f"hier={hier['predicted_dcn_bytes']} flat={flat['predicted_dcn_bytes']}")
    sys.exit(1)
print(f"sched smoke OK: hier==flat rows byte-identical, predicted==measured, "
      f"DCN {flat['predicted_dcn_bytes']} -> {hier['predicted_dcn_bytes']} B "
      f"({flat['predicted_dcn_bytes'] / hier['predicted_dcn_bytes']:.1f}x less "
      "on the slow link)")
PYEOF
else
  echo "sched smoke failed (rc=$sched_rc):"; tail -20 "$SCHED_TMP"/*.err
fi
rm -rf "$SCHED_TMP"

echo "== multihost stage (2-process gloo fleet: host-sharded ingest parity) =="
mh_rc=0
MH_TMP=$(mktemp -d)
env JAX_PLATFORMS=cpu python -m spark_examples_tpu.parallel.multihost \
    --num-processes 2 --local-devices 2 --artifact "$MH_TMP/report.json" \
    > "$MH_TMP/report.out" 2> "$MH_TMP/report.err" || mh_rc=$?
if [ "$mh_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python - "$MH_TMP/report.json" <<'PYEOF' || mh_rc=$?
import json, sys
doc = json.load(open(sys.argv[1]))
checks = ("gramian_ok", "ring_gramian_ok", "hier_gramian_ok",
          "result_spans_processes", "cli_ok", "cli_outputs_identical",
          "fleet_host_sharded", "fleet_io_ok", "fleet_conformance_ok",
          "fleet_trace_ok", "ok")
bad = [k for k in checks if doc.get(k) is not True]
if bad:
    print(f"multihost report failed checks: {bad}")
    print(json.dumps({k: doc.get(k) for k in checks}))
    sys.exit(1)
bases = doc["fleet_io_reference_bases"]
solo, per = bases["solo"], bases["per_process"]
H = doc["num_processes"]
# ~1/H of solo per process: the fair share plus at most the one contig
# that closes a partition (the split rule's documented overshoot), and
# the partition property exact — local reads sum to the solo total.
if sum(per) != solo or any(
        not (0 < b <= solo * (1.0 / H + 0.26)) for b in per):
    print(f"per-process ingest not ~1/{H} of solo: {per} vs {solo}")
    sys.exit(1)
shares = [round(b / solo, 3) for b in per]
print(f"multihost smoke OK: {H} processes, PC rows byte-identical to the "
      f"solo oracle, per-host ingest {shares} of solo ({solo} bases), "
      "hier ring exact, merged fleet trace valid")
PYEOF
else
  echo "multihost fleet run failed (rc=$mh_rc):"
  tail -20 "$MH_TMP/report.err"; tail -5 "$MH_TMP/report.out"
fi
rm -rf "$MH_TMP"

echo "== hostmem stage (graftcheck hostmem + host-memory budget) =="
hm_rc=0
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck hostmem || hm_rc=$?
# TOTAL: the declared-unbounded inventory must be EMPTY — a hatch is a
# GH006 finding now, and this assert catches any report-plumbing drift.
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck hostmem --json \
  | python -c '
import json, sys
doc = json.load(sys.stdin)
if doc["declared_unbounded"] != []:
    print("hostmem inventory NOT empty:", doc["declared_unbounded"])
    sys.exit(1)
if doc["finding_count"] != 0:
    print("hostmem findings present:", doc["findings"]); sys.exit(1)
print("hostmem totality OK (0 findings, declared_unbounded == [])")
' || hm_rc=$?
hm_flags="--num-samples 64 --references 1:0:400000 --mesh-shape 1,4 \
  --similarity-strategy sharded --block-size 64 --plan-devices 4"
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck plan $hm_flags \
  --host-mem-budget 8589934592 > /dev/null || {
    echo "hostmem budget smoke: in-budget plan REJECTED"; hm_rc=1; }
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck plan $hm_flags \
  --host-mem-budget 1048576 > /dev/null
if [ "$?" -ne 2 ]; then
  echo "hostmem budget smoke: over-budget plan did not exit 2"; hm_rc=1
else
  echo "hostmem budget smoke OK (in-budget plan OK, over-budget exit 2)"
fi

# Wire-ingest budget smoke: JSONL and SAM inputs under --host-mem-budget
# were the exit-2 "unprovable" class; with the total resolver a real file
# proves a tight bound from its bytes on disk and the plan exits 0. The
# JSONL conf then RUNS, and its manifest's measured peak RSS must sit
# under the same static bound the plan proved (the e2e conformance leg).
WIRE_TMP=$(mktemp -d)
python - "$WIRE_TMP" <<'PYEOF'
import json, sys
root = sys.argv[1]
with open(f"{root}/cohort.jsonl", "w") as f:
    for i in range(64):
        f.write(json.dumps({
            "referenceName": "17", "start": 100 + 10 * i, "end": 101 + 10 * i,
            "referenceBases": "A", "alternateBases": ["G"],
            "info": {"AF": ["0.5"]},
            "calls": [
                {"callSetId": f"j-{s}", "callSetName": f"S{s}",
                 "genotype": [1, 0] if (i + s) % 2 else [0, 0]}
                for s in range(4)
            ],
        }) + "\n")
with open(f"{root}/reads.sam", "w") as f:
    f.write("@HD\tVN:1.6\n@SQ\tSN:21\tLN:48129895\n")
    for i in range(20):
        f.write(f"r{i:03d}\t0\t21\t{1000 + 5 * i}\t60\t40M\t*\t0\t0\t"
                f"{'ACGT' * 10}\t{'F' * 40}\n")
PYEOF
for wire_input in "$WIRE_TMP/cohort.jsonl" "$WIRE_TMP/reads.sam"; do
  env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck plan \
    --source file --input-files "$wire_input" --ingest wire \
    --num-samples 4 --references 17:0:1000 \
    --host-mem-budget 8589934592 > /dev/null || {
      echo "wire budget smoke: $(basename "$wire_input") plan not provable"
      hm_rc=1; }
done
wire_rc=0
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
  python -m spark_examples_tpu variants-pca \
    --source file --input-files "$WIRE_TMP/cohort.jsonl" --ingest wire \
    --references 17:0:1000 --metrics-json "$WIRE_TMP/manifest.json" \
    > /dev/null 2> "$WIRE_TMP/wire.err" || wire_rc=$?
if [ "$wire_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python - "$WIRE_TMP/manifest.json" <<'PYEOF' || hm_rc=$?
import sys
from spark_examples_tpu.obs.manifest import read_manifest
hm = read_manifest(sys.argv[1])["host_memory"]
if not hm["peak_rss_bytes"] or not hm["static_bound_bytes"]:
    print(f"wire manifest host_memory incomplete: {hm}"); sys.exit(1)
if hm["peak_rss_bytes"] > hm["static_bound_bytes"]:
    print("wire run measured peak RSS EXCEEDS the static bound: "
          f"{hm['peak_rss_bytes']} > {hm['static_bound_bytes']}")
    sys.exit(1)
print(f"wire budget smoke OK (JSONL+SAM provable; measured "
      f"{hm['peak_rss_bytes'] >> 20} MiB <= bound "
      f"{hm['static_bound_bytes'] >> 20} MiB)")
PYEOF
else
  echo "wire budget smoke run failed (rc=$wire_rc):"
  tail -10 "$WIRE_TMP/wire.err"; hm_rc=1
fi
rm -rf "$WIRE_TMP"

echo "== observability smoke (run manifest schema) =="
obs_rc=0
OBS_TMP=$(mktemp -d)
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
  python -m spark_examples_tpu variants-pca \
    --num-samples 8 --references 1:0:50000 \
    --metrics-json "$OBS_TMP/manifest.json" --heartbeat-seconds 1 \
    > "$OBS_TMP/stdout.log" 2> "$OBS_TMP/stderr.log" || obs_rc=$?
if [ "$obs_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python - "$OBS_TMP/manifest.json" <<'PYEOF' || obs_rc=$?
import sys
from spark_examples_tpu.obs.manifest import read_manifest, validate_manifest
doc = read_manifest(sys.argv[1])
errors = validate_manifest(doc)
if errors:
    print("manifest INVALID:\n  " + "\n  ".join(errors))
    sys.exit(1)
if doc["io_stats"] is None or doc["io_stats"]["variants"] <= 0:
    print("manifest has no I/O stats from the smoke run")
    sys.exit(1)
hm = doc["host_memory"]
if not hm["peak_rss_bytes"] or not hm["static_bound_bytes"]:
    print(f"manifest host_memory incomplete: {hm}")
    sys.exit(1)
if hm["peak_rss_bytes"] > hm["static_bound_bytes"]:
    print("measured peak RSS EXCEEDS the static host-memory bound: "
          f"{hm['peak_rss_bytes']} > {hm['static_bound_bytes']} "
          "(parallel/mesh.py:host_peak_bytes no longer describes reality)")
    sys.exit(1)
conf = (doc.get("conformance") or {}).get("hostmem")
if not conf or conf.get("ok") is not True:
    print(f"manifest conformance block missing/failed for hostmem: {conf}")
    sys.exit(1)
print(f"manifest OK ({len(doc['metrics'])} metrics, "
      f"{len(doc['spans'])} root spans; host peak RSS "
      f"{hm['peak_rss_bytes'] >> 20} MiB <= bound "
      f"{hm['static_bound_bytes'] >> 20} MiB; hostmem conformance ok)")
PYEOF
else
  echo "obs smoke run failed (rc=$obs_rc):"; tail -20 "$OBS_TMP/stderr.log"
fi
if [ "$obs_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
    python -m spark_examples_tpu variants-pca \
      --num-samples 8 --references 1:0:50000 \
      --ingest packed --check-ranges \
      --metrics-json "$OBS_TMP/ranges.json" \
      > /dev/null 2> "$OBS_TMP/ranges.err" || obs_rc=$?
  if [ "$obs_rc" -eq 0 ]; then
    env JAX_PLATFORMS=cpu python - "$OBS_TMP/ranges.json" <<'PYEOF' || obs_rc=$?
import sys
from spark_examples_tpu.obs.manifest import read_manifest, validate_manifest
doc = read_manifest(sys.argv[1])
errors = validate_manifest(doc)
if errors:
    print("check-ranges manifest INVALID:\n  " + "\n  ".join(errors))
    sys.exit(1)
ge = doc.get("gramian_exactness")
if not ge or ge.get("entry_max") is None or not ge.get("static_entry_bound"):
    print(f"--check-ranges run carries no gramian_exactness pair: {ge}")
    sys.exit(1)
if ge["entry_max"] > ge["static_entry_bound"]:
    print("measured accumulator entry EXCEEDS the static bound: "
          f"{ge['entry_max']} > {ge['static_entry_bound']} "
          "(the GR005-proven projection no longer describes reality)")
    sys.exit(1)
conf = doc.get("conformance") or {}
for prover in ("hostmem", "ranges"):
    pair = conf.get(prover)
    if not pair or pair.get("ok") is not True:
        print(f"conformance pair missing/failed for {prover}: {pair}")
        sys.exit(1)
print(f"check-ranges smoke OK (entry max {ge['entry_max']} <= "
      f"projected bound {ge['static_entry_bound']}; hostmem+ranges "
      "conformance ok)")
PYEOF
  else
    echo "check-ranges smoke run failed (rc=$obs_rc):"
    tail -20 "$OBS_TMP/ranges.err"
  fi
fi
rm -rf "$OBS_TMP"

echo "== sharded-ring smoke (4 virtual devices, packed vs oracle) =="
ring_rc=0
RING_TMP=$(mktemp -d)
# N=64 over a samples axis of 4 keeps the local width (16) a multiple of 8
# in BOTH wire formats, so the two runs do identical work and the traffic
# ratio is exactly 8 (no ragged-byte slack in the assertion).
ring_flags="--num-samples 64 --references 1:0:400000 --mesh-shape 1,4 \
  --similarity-strategy sharded --block-size 64"
for mode in on off; do
  env JAX_PLATFORMS=cpu \
      SPARK_EXAMPLES_TPU_NO_CACHE=1 \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m spark_examples_tpu variants-pca $ring_flags \
      --ring-pack-bits "$mode" --metrics-json "$RING_TMP/$mode.json" \
      > "$RING_TMP/$mode.out" 2> "$RING_TMP/$mode.err" || ring_rc=$?
done
if [ "$ring_rc" -eq 0 ]; then
  # Result rows only (lines with tabs): the manifest-path echo differs.
  grep -P "\t" "$RING_TMP/on.out" > "$RING_TMP/on.tsv"
  grep -P "\t" "$RING_TMP/off.out" > "$RING_TMP/off.tsv"
  if ! cmp -s "$RING_TMP/on.tsv" "$RING_TMP/off.tsv"; then
    echo "packed ring result rows DIFFER from the --ring-pack-bits off oracle"
    ring_rc=1
  fi
fi
if [ "$ring_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python - "$RING_TMP/on.json" "$RING_TMP/off.json" <<'PYEOF' || ring_rc=$?
import sys
from spark_examples_tpu.obs.manifest import manifest_metric_value, read_manifest
from spark_examples_tpu.obs.metrics import GRAMIAN_RING_BYTES
packed, oracle = (
    manifest_metric_value(read_manifest(path), GRAMIAN_RING_BYTES)
    for path in sys.argv[1:3]
)
if not packed or not oracle:
    print(f"manifest missing {GRAMIAN_RING_BYTES} (packed={packed}, oracle={oracle})")
    sys.exit(1)
if oracle < 8 * packed:
    print(f"packed ring traffic not >= 8x smaller: packed={packed} oracle={oracle}")
    sys.exit(1)
for path in sys.argv[1:3]:
    pair = (read_manifest(path).get("conformance") or {}).get("sched")
    if not pair or pair.get("ok") is not True:
        print(f"sched conformance pair missing/failed in {path}: {pair}")
        sys.exit(1)
print(f"ring smoke OK: parity exact, ring bytes {int(oracle)} -> {int(packed)} "
      f"({oracle / packed:.1f}x reduction)")
PYEOF
else
  echo "sharded-ring smoke failed (rc=$ring_rc):"; tail -20 "$RING_TMP"/*.err
fi
rm -rf "$RING_TMP"

echo "== analyses smoke (GRM oracle, LD determinism, assoc signal) =="
an_rc=0
AN_TMP=$(mktemp -d)
an_flags="--num-samples 8 --references 1:0:60000"

# Plan entries: every analysis verb validates device-free, and a doomed
# configuration is an exit-2 reject (the admission contract of analyses/).
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck plan \
  --analysis grm $an_flags > /dev/null || {
    echo "analyses smoke: grm plan REJECTED"; an_rc=1; }
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck plan \
  --analysis ld $an_flags > /dev/null || {
    echo "analyses smoke: ld plan REJECTED"; an_rc=1; }
env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck plan \
  --analysis ld $an_flags --ld-r2-threshold 1.5 > /dev/null 2>&1
if [ "$?" -ne 2 ]; then
  echo "analyses smoke: bad LD threshold did not exit 2"; an_rc=1
fi

# 1. GRM: tiny synthetic CLI run; the written kinship TSV must be
#    BYTE-IDENTICAL to the full-matrix NumPy oracle over the same stream,
#    and the manifest must validate with the analysis block.
grm_rc=0
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
  python -m spark_examples_tpu grm $an_flags \
    --grm-out "$AN_TMP/kin.tsv" --metrics-json "$AN_TMP/grm.json" \
    > "$AN_TMP/grm.out" 2> "$AN_TMP/grm.err" || grm_rc=$?
if [ "$grm_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python - "$AN_TMP" $an_flags <<'PYEOF' || grm_rc=$?
import sys
import numpy as np
from spark_examples_tpu.analyses.grm import format_grm_rows, grm_reference
from spark_examples_tpu.config import GrmConf
from spark_examples_tpu.obs.manifest import read_manifest, validate_manifest
from spark_examples_tpu.pipeline.pca_driver import make_source

tmp, flags = sys.argv[1], sys.argv[2:]
conf = GrmConf.parse(flags)
src = make_source(conf)
names = [cs["name"] for cs in src.search_callsets(conf.variant_set_id)]
rows = [
    block["has_variation"]
    for contig in conf.get_contigs(src, conf.variant_set_id)
    for block in src.genotype_blocks(
        conf.variant_set_id[0], contig, block_size=conf.block_size,
        min_allele_frequency=conf.min_allele_frequency)
]
oracle = grm_reference(np.concatenate(rows), len(names))
expected = ["\t".join(["name", *names])] + [
    "\t".join(str(field) for field in row)
    for row in format_grm_rows(names, oracle)
]
actual = open(f"{tmp}/kin.tsv").read().splitlines()
if actual != expected:
    print("GRM kinship TSV differs from the NumPy oracle")
    sys.exit(1)
doc = read_manifest(f"{tmp}/grm.json")
errors = validate_manifest(doc)
if errors:
    print("GRM manifest INVALID:\n  " + "\n  ".join(errors)); sys.exit(1)
analysis = doc["analysis"]
if analysis["kind"] != "grm" or analysis["sites_tested"] != len(
        np.concatenate(rows)):
    print(f"GRM manifest analysis block wrong: {analysis}"); sys.exit(1)
print(f"GRM smoke OK: {analysis['sites_tested']} sites, kinship "
      "byte-identical to the NumPy oracle, manifest valid")
PYEOF
else
  echo "GRM smoke run failed (rc=$grm_rc):"; tail -10 "$AN_TMP/grm.err"
fi
[ "$grm_rc" -eq 0 ] || an_rc=1

# 2. LD prune on a 2-contig synthetic, twice: the kept-site mask must be
#    deterministic (byte-identical across runs) and match the windowed
#    NumPy oracle. Runs on its own step rc: a failure upstream must not
#    skip this coverage or masquerade as an LD failure.
ld_rc=0
ld_flags="--num-samples 8 --references 1:0:40000,2:0:40000 \
  --ld-r2-threshold 0.2 --ld-window-sites 64"
for run in a b; do
  env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
    python -m spark_examples_tpu ld-prune $ld_flags \
      --ld-out "$AN_TMP/kept-$run.tsv" --metrics-json "$AN_TMP/ld-$run.json" \
      > /dev/null 2> "$AN_TMP/ld-$run.err" || ld_rc=$?
done
if [ "$ld_rc" -ne 0 ]; then
  echo "LD smoke run failed:"; tail -10 "$AN_TMP"/ld-*.err
elif ! cmp -s "$AN_TMP/kept-a.tsv" "$AN_TMP/kept-b.tsv"; then
  echo "LD kept-site mask is NOT deterministic across identical runs"
  ld_rc=1
else
  env JAX_PLATFORMS=cpu python - "$AN_TMP" $ld_flags <<'PYEOF' || ld_rc=$?
import sys
import numpy as np
from spark_examples_tpu.analyses.ld import ld_prune_reference
from spark_examples_tpu.config import LdConf
from spark_examples_tpu.obs.manifest import read_manifest, validate_manifest
from spark_examples_tpu.pipeline.pca_driver import make_source

tmp, flags = sys.argv[1], sys.argv[2:]
conf = LdConf.parse(flags)
src = make_source(conf)
expected = ["contig\tpos\tkept"]
kept_total = tested_total = 0
for contig in conf.get_contigs(src, conf.variant_set_id):
    rows = [
        (block["positions"], block["has_variation"])
        for block in src.genotype_blocks(
            conf.variant_set_id[0], contig, block_size=conf.block_size,
            min_allele_frequency=conf.min_allele_frequency)
    ]
    positions = np.concatenate([p for p, _ in rows])
    hv = np.concatenate([h for _, h in rows])
    W = conf.ld_window_sites
    windows = [
        (positions[i:i + W], hv[i:i + W])
        for i in range(0, len(positions), W)
    ]
    for pos, kept in ld_prune_reference(
            windows, conf.num_samples, conf.ld_r2_threshold):
        expected.append(f"{contig.reference_name}\t{pos}\t{int(kept)}")
        kept_total += int(kept)
        tested_total += 1
actual = open(f"{tmp}/kept-a.tsv").read().splitlines()
if actual != expected:
    print("LD kept mask differs from the windowed NumPy oracle")
    sys.exit(1)
doc = read_manifest(f"{tmp}/ld-a.json")
errors = validate_manifest(doc)
if errors:
    print("LD manifest INVALID:\n  " + "\n  ".join(errors)); sys.exit(1)
analysis = doc["analysis"]
if analysis != {"kind": "ld", "sites_kept": kept_total,
                "sites_tested": tested_total}:
    print(f"LD manifest analysis block wrong: {analysis} vs "
          f"kept={kept_total} tested={tested_total}")
    sys.exit(1)
print(f"LD smoke OK: deterministic kept mask ({kept_total}/{tested_total} "
      "sites), oracle-exact, manifest valid")
PYEOF
fi
[ "$ld_rc" -eq 0 ] || an_rc=1

# 3. Association scan with a PLANTED signal: phenotypes are the carrier
#    vector of one polymorphic site, so that site's chi-square is the
#    theoretical maximum (n) and must rank top. Own step rc, like LD.
assoc_rc=0
env JAX_PLATFORMS=cpu python - "$AN_TMP" $an_flags <<'PYEOF' > /dev/null || assoc_rc=$?
import sys
import numpy as np
from spark_examples_tpu.config import AssocConf
from spark_examples_tpu.pipeline.pca_driver import make_source

tmp, flags = sys.argv[1], sys.argv[2:]
conf = AssocConf.parse(flags + ["--phenotypes", "unused"])
src = make_source(conf)
names = [cs["name"] for cs in src.search_callsets(conf.variant_set_id)]
for contig in conf.get_contigs(src, conf.variant_set_id):
    for block in src.genotype_blocks(
            conf.variant_set_id[0], contig, block_size=conf.block_size,
            min_allele_frequency=conf.min_allele_frequency):
        carriers = block["has_variation"].sum(axis=1)
        target = np.nonzero(
            (carriers >= 2) & (carriers <= len(names) - 2))[0]
        if len(target):
            i = int(target[0])
            with open(f"{tmp}/pheno.tsv", "w") as f:
                for name, status in zip(names, block["has_variation"][i]):
                    f.write(f"{name}\t{int(status)}\n")
            with open(f"{tmp}/signal.txt", "w") as f:
                f.write(
                    f"{contig.reference_name}\t{int(block['positions'][i])}"
                )
            sys.exit(0)
print("no polymorphic site found for the planted signal")
sys.exit(1)
PYEOF
if [ "$assoc_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
    python -m spark_examples_tpu assoc-scan $an_flags \
      --phenotypes "$AN_TMP/pheno.tsv" --assoc-out "$AN_TMP/scan.tsv" \
      --metrics-json "$AN_TMP/assoc.json" \
      > "$AN_TMP/assoc.out" 2> "$AN_TMP/assoc.err" || assoc_rc=$?
fi
if [ "$assoc_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python - "$AN_TMP" <<'PYEOF' || assoc_rc=$?
import sys
from spark_examples_tpu.obs.manifest import read_manifest, validate_manifest

tmp = sys.argv[1]
signal_contig, signal_pos = open(f"{tmp}/signal.txt").read().split()
best = None
with open(f"{tmp}/scan.tsv") as f:
    next(f)  # header
    for line in f:
        contig, pos, a, t, chi2 = line.rstrip("\n").split("\t")
        if best is None or float(chi2) > best[2]:
            best = (contig, pos, float(chi2))
if best is None or best[0] != signal_contig or best[1] != signal_pos:
    print(f"planted signal {signal_contig}:{signal_pos} NOT top-ranked "
          f"(top was {best})")
    sys.exit(1)
doc = read_manifest(f"{tmp}/assoc.json")
errors = validate_manifest(doc)
if errors:
    print("assoc manifest INVALID:\n  " + "\n  ".join(errors)); sys.exit(1)
if doc["analysis"]["kind"] != "assoc" or \
        doc["analysis"]["sites_tested"] <= 0:
    print(f"assoc manifest analysis block wrong: {doc['analysis']}")
    sys.exit(1)
print(f"assoc smoke OK: planted signal {signal_contig}:{signal_pos} "
      f"top-ranked (chi2 {best[2]:g}), manifest valid")
PYEOF
else
  echo "assoc smoke failed:"; tail -10 "$AN_TMP/assoc.err" 2>/dev/null
fi
[ "$assoc_rc" -eq 0 ] || an_rc=1
rm -rf "$AN_TMP"

echo "== serve smoke (resident daemon: admit, reject, warm cache, drain) =="
serve_rc=0
SERVE_TMP=$(mktemp -d)
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
  python -m spark_examples_tpu serve --port 0 \
    --run-dir "$SERVE_TMP/run" --endpoint-file "$SERVE_TMP/endpoint" \
    > "$SERVE_TMP/daemon.out" 2> "$SERVE_TMP/daemon.err" &
SERVE_PID=$!
for _ in $(seq 1 150); do [ -f "$SERVE_TMP/endpoint" ] && break; sleep 0.2; done
if [ ! -f "$SERVE_TMP/endpoint" ]; then
  echo "serve smoke: daemon never published its endpoint"; serve_rc=1
  kill "$SERVE_PID" 2>/dev/null
  wait "$SERVE_PID" 2>/dev/null
else
  env JAX_PLATFORMS=cpu python - "$(cat "$SERVE_TMP/endpoint")" "$SERVE_PID" <<'PYEOF' || serve_rc=$?
import os, signal, sys, time, urllib.error
from spark_examples_tpu.obs.manifest import read_manifest, validate_manifest
from spark_examples_tpu.obs.metrics import COMPILE_CACHE_GEOMETRY_HITS
from spark_examples_tpu.serve.client import ServeClient, ServeError

url, daemon_pid = sys.argv[1], int(sys.argv[2])
client = ServeClient(url)
flags = ["--num-samples", "8", "--references", "1:0:50000"]

# 1. plan-invalid request -> structured 400 carrying the plan finding.
try:
    client.submit(flags + ["--num-pc", "99"])
    print("plan-invalid submit was ACCEPTED"); sys.exit(1)
except ServeError as e:
    codes = [i["code"] for i in e.body.get("plan", {}).get("issues", [])]
    if e.status != 400 or e.code != "plan-rejected" \
            or "num-pc-exceeds-cohort" not in codes:
        print(f"rejection not structured: {e.status} {e.code} {codes}")
        sys.exit(1)

# 2. accepted synthetic job -> done, valid per-job schema-v2 manifest.
job = client.wait(client.submit(flags)["job"]["id"], timeout=300)["job"]
if job["status"] != "done" or job["compile_cache"] != "cold":
    print(f"first job not a clean cold run: {job['status']} "
          f"{job['compile_cache']} {job.get('error')}"); sys.exit(1)
errors = validate_manifest(read_manifest(job["manifest_path"]))
if errors:
    print("per-job manifest INVALID:\n  " + "\n  ".join(errors)); sys.exit(1)

# 3. identical resubmit -> warm compile-cache hit, visible in /metrics.
job2 = client.wait(client.submit(flags)["job"]["id"], timeout=300)["job"]
if job2["status"] != "done" or job2["compile_cache"] != "warm":
    print(f"identical resubmit not warm: {job2['status']} "
          f"{job2['compile_cache']}"); sys.exit(1)
hits = [l for l in client.metrics().splitlines()
        if l.startswith(COMPILE_CACHE_GEOMETRY_HITS + " ")]
if not hits or float(hits[0].split()[1]) < 1:
    print(f"/metrics shows no warm-geometry hit: {hits}"); sys.exit(1)

# 4. deadline below the calibrated estimate -> structured 413 carrying
#    both numbers; a feasible resubmit completes and its per-job manifest
#    lands the predicted-vs-measured cost block.
try:
    client.submit(flags, deadline_seconds=0.001)
    print("infeasible-deadline submit was ACCEPTED"); sys.exit(1)
except ServeError as e:
    if e.status != 413 or e.code != "deadline-infeasible":
        print(f"infeasible deadline not a structured 413: "
              f"{e.status} {e.code}"); sys.exit(1)
    cost = e.body.get("cost") or {}
    predicted = cost.get("predicted_seconds")
    message = (e.body.get("error") or {}).get("message") or ""
    if not predicted or cost.get("requested_deadline_seconds") != 0.001 \
            or "0.001" not in message or f"{predicted:.4g}" not in message:
        print(f"413 body does not name predicted vs requested: {e.body}")
        sys.exit(1)
job3 = client.wait(client.submit(flags)["job"]["id"], timeout=300)["job"]
cost_doc = read_manifest(job3["manifest_path"]).get("cost")
if not cost_doc or cost_doc.get("compile") not in ("warm", "cold") \
        or not isinstance(cost_doc.get("measured_seconds"), (int, float)) \
        or not isinstance(cost_doc.get("predicted_seconds"), (int, float)) \
        or not isinstance(cost_doc.get("queue_wait_seconds"), (int, float)):
    print(f"done job's manifest has no cost block: {cost_doc}"); sys.exit(1)

# 5. SIGTERM drain: a fresh-geometry job holds the worker (cold compile),
#    new submissions get 503, the in-flight job still finishes.
inflight = client.submit(["--num-samples", "12",
                          "--references", "1:0:50000"])["job"]
os.kill(daemon_pid, signal.SIGTERM)
drain_seen = False
for _ in range(20):
    try:
        client.submit(flags)
        time.sleep(0.05)
    except ServeError as e:
        if e.status == 503 and e.code == "draining":
            drain_seen = True
        break
    except urllib.error.URLError:
        break
if not drain_seen:
    print("drain window never returned 503 draining"); sys.exit(1)
manifest = os.path.join(os.path.dirname(os.path.dirname(
    job["manifest_path"])), inflight["id"], "manifest.json")
for _ in range(300):
    if os.path.exists(manifest):
        break
    time.sleep(0.2)
else:
    print(f"in-flight job never finished its manifest: {manifest}")
    sys.exit(1)
print(f"serve smoke OK: structured rejection, cold {job['seconds']:.2f}s "
      f"-> warm {job2['seconds']:.2f}s, per-job manifests valid, "
      "drain returned 503 and finished the in-flight job")
PYEOF
  kill -TERM "$SERVE_PID" 2>/dev/null
  if wait "$SERVE_PID"; then
    echo "serve smoke: daemon drained cleanly (exit 0)"
  else
    echo "serve smoke: daemon exited nonzero"; serve_rc=1
  fi
fi
if [ "$serve_rc" -ne 0 ]; then
  echo "serve smoke failed (rc=$serve_rc):"; tail -20 "$SERVE_TMP/daemon.err"
fi
rm -rf "$SERVE_TMP"

echo "== serve concurrency smoke (slices, journal replay, warm restart, load) =="
sc_rc=0
SC_TMP=$(mktemp -d)
sc_daemon() {
  rm -f "$SC_TMP/endpoint"
  env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m spark_examples_tpu serve --port 0 \
      --run-dir "$SC_TMP/run" --endpoint-file "$SC_TMP/endpoint" \
      --executor-slices 1 --serve-small-site-limit 5000 \
      >> "$SC_TMP/daemon.out" 2>> "$SC_TMP/daemon.err" &
  SC_PID=$!
  for _ in $(seq 1 150); do [ -f "$SC_TMP/endpoint" ] && break; sleep 0.2; done
  [ -f "$SC_TMP/endpoint" ]
}
if ! sc_daemon; then
  echo "serve concurrency smoke: daemon never published its endpoint"; sc_rc=1
  kill "$SC_PID" 2>/dev/null; wait "$SC_PID" 2>/dev/null
else
  # Phase 1: a large job in flight must NOT head-block a small job — the
  # small job (via the `submit --wait` verb, Retry-After-paced) completes
  # on its own slice while the large job is still on the devices. Then
  # queue a second large job behind the first and SIGKILL the daemon
  # mid-queue (the journal's moment of truth).
  env JAX_PLATFORMS=cpu python - "$(cat "$SC_TMP/endpoint")" "$SC_TMP" <<'PYEOF' || sc_rc=$?
import subprocess, sys, time
from spark_examples_tpu.serve.client import ServeClient

url, tmp = sys.argv[1], sys.argv[2]
client = ServeClient(url)
SMALL = ["--num-samples", "8", "--references", "1:0:50000"]
LARGE = ["--num-samples", "512", "--references", "1:0:20000000"]

# Warm the small geometry (its compile is the daemon's startup cost).
first = client.wait(client.submit(SMALL)["job"]["id"], timeout=300)["job"]
if first["status"] != "done" or first["slice"] != "small-0":
    print(f"small job not served by the small slice: {first}"); sys.exit(1)

large1 = client.submit(LARGE)["job"]
if large1["class"] != "large":
    print(f"large job misclassified: {large1}"); sys.exit(1)
t0 = time.perf_counter()
wait = subprocess.run(
    [sys.executable, "-m", "spark_examples_tpu", "submit", "--url", url,
     "--wait", "--json", "--"] + SMALL,
    capture_output=True, text=True, timeout=300)
small_seconds = time.perf_counter() - t0
if wait.returncode != 0:
    print(f"submit --wait failed: {wait.stdout}\n{wait.stderr}"); sys.exit(1)
inflight = client.status(large1["id"])["job"]
if inflight["status"] not in ("queued", "running"):
    print(f"large job already {inflight['status']} after "
          f"{small_seconds:.2f}s small job: no concurrency"); sys.exit(1)
large1_done = client.wait(large1["id"], timeout=600)["job"]
if large1_done["status"] != "done":
    print(f"large job failed: {large1_done}"); sys.exit(1)

# Mid-queue kill setup: large2 running, large3 queued behind it.
large2 = client.submit(LARGE)["job"]
deadline = time.monotonic() + 60
while client.status(large2["id"])["job"]["status"] == "queued":
    if time.monotonic() > deadline:
        print("large2 never started"); sys.exit(1)
    time.sleep(0.1)
large3 = client.submit(LARGE)["job"]
with open(tmp + "/ids", "w") as f:
    f.write(f"{large2['id']}\n{large3['id']}\n")
print(f"serve concurrency phase 1 OK: small {small_seconds:.2f}s beside "
      f"large ({large1_done['seconds']:.2f}s), large2 running + "
      "large3 queued for the kill")
PYEOF
  if [ "$sc_rc" -eq 0 ]; then
    kill -9 "$SC_PID" 2>/dev/null
    wait "$SC_PID" 2>/dev/null
    # Phase 2: the restarted daemon must replay the journal — the queued
    # job finishes, the mid-device job fails structurally, and a
    # repeat-geometry job is warm from the run-dir persistent state.
    if ! sc_daemon; then
      echo "serve concurrency smoke: daemon did not restart"; sc_rc=1
    else
      env JAX_PLATFORMS=cpu python - "$(cat "$SC_TMP/endpoint")" "$SC_TMP" <<'PYEOF' || sc_rc=$?
import sys
from spark_examples_tpu.serve.client import ServeClient

url, tmp = sys.argv[1], sys.argv[2]
client = ServeClient(url)
large2_id, large3_id = open(tmp + "/ids").read().split()

health = client.healthz()
if health["warm_state"]["journal_replayed"] < 2:
    print(f"journal replayed too few jobs: {health['warm_state']}")
    sys.exit(1)
crashed = client.wait(large2_id, timeout=60)["job"]
if crashed["status"] != "failed" or "daemon-restarted" not in (crashed["error"] or ""):
    print(f"mid-device job not failed structurally: {crashed}"); sys.exit(1)
replayed = client.wait(large3_id, timeout=600)["job"]
if replayed["status"] != "done":
    print(f"journaled queued job did not finish after restart: {replayed}")
    sys.exit(1)
SMALL = ["--num-samples", "8", "--references", "1:0:50000"]
repeat = client.wait(client.submit(SMALL)["job"]["id"], timeout=300)["job"]
if repeat["compile_cache"] != "warm":
    print(f"repeat-geometry job not warm after restart: {repeat}")
    sys.exit(1)
# The calibration ledger is append-only and fsync'd: the kill -9 above
# must not have cost the pre-kill measured samples. The restarted daemon
# alone completed only 2 jobs (large3 + repeat; large2 failed, failures
# are never recorded) — more than 2 folded samples proves the pre-kill
# rows survived the crash.
from spark_examples_tpu.obs.calibration import calibration_path, fold_calibration
fold = fold_calibration(calibration_path(tmp + "/run"))
if fold.overall.n <= 2:
    print(f"calibration ledger lost pre-kill samples: n={fold.overall.n}")
    sys.exit(1)
print(f"serve concurrency phase 2 OK: {health['warm_state']['journal_replayed']} "
      f"jobs replayed, queued job finished ({replayed['seconds']:.2f}s), "
      "mid-device job failed structurally, repeat geometry warm from the "
      f"persistent run-dir state, calibration ledger kept {fold.overall.n} "
      "samples across kill -9")
PYEOF
      kill -TERM "$SC_PID" 2>/dev/null
      if ! wait "$SC_PID"; then
        echo "serve concurrency smoke: restarted daemon exited nonzero"; sc_rc=1
      fi
    fi
  else
    kill -9 "$SC_PID" 2>/dev/null; wait "$SC_PID" 2>/dev/null
  fi
fi
if [ "$sc_rc" -eq 0 ]; then
  # Phase 3: the serve-load harness — mixed small/large traffic through
  # the HTTP API; small-job P99 under concurrent large-job load must stay
  # within ~2x its unloaded P99 (a 2 s absolute floor absorbs shared-CI
  # scheduler noise on a 2-core container) and far below the large job's
  # own wall-clock (the head-block detector).
  env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python bench.py --config serve-load > "$SC_TMP/load.json" \
      2> "$SC_TMP/load.err" || sc_rc=$?
  if [ "$sc_rc" -eq 0 ]; then
    env JAX_PLATFORMS=cpu python - "$SC_TMP/load.json" <<'PYEOF' || sc_rc=$?
import json, sys
doc = json.load(open(sys.argv[1]))
d = doc["details"]
if not d["sliced"]:
    print(f"serve-load ran unsliced: {d['slices']}"); sys.exit(1)
unloaded = d["small_unloaded_seconds"]["p99"]
loaded = d["small_loaded_seconds"]["p99"]
large = d["large_job_seconds"]
if loaded > max(2.0 * unloaded, unloaded + 2.0):
    print(f"small-job P99 degraded past 2x under load: "
          f"{loaded:.3f}s vs {unloaded:.3f}s unloaded"); sys.exit(1)
if loaded >= large:
    print(f"small-job P99 {loaded:.3f}s >= large job {large:.3f}s: "
          "head-of-line blocking"); sys.exit(1)
# The /v1/fleet/stats document the bench fetched over HTTP must be
# valid and carry nonzero small-class quantiles + a calibration fold.
fs = d["fleet_stats"]
wall = ((fs.get("classes") or {}).get("small") or {}).get("wall_seconds") or {}
if not wall.get("count") or not wall.get("p99") or wall["p99"] <= 0:
    print(f"/v1/fleet/stats has no nonzero small wall quantiles: {fs}")
    sys.exit(1)
if not (fs.get("calibration") or {}).get("samples"):
    print(f"/v1/fleet/stats calibration fold empty: {fs}"); sys.exit(1)
# Fused-batch phase: the one-program group must be byte-identical to
# the same jobs back to back and at least 2x their group throughput
# (the acceptance bound; BENCH_r07 records ~5.8x on this host).
fb = d["fused_batch"]
if not fb["byte_identical"]:
    print("fused-batch phase lost byte parity"); sys.exit(1)
if fb["fused"]["dispatch"]["fused_groups"] < 1 \
        or fb["serial"]["dispatch"]["fused_groups"] != 0:
    print(f"fused-batch dispatch counters wrong: fused ran "
          f"{fb['fused']['dispatch']}, serial ran {fb['serial']['dispatch']}")
    sys.exit(1)
ratio = fb["group_throughput_ratio"]
if not ratio or ratio < 2.0:
    print(f"fused group throughput below the 2x bound: {ratio}")
    sys.exit(1)
# Cost-ordered scheduling: cheap jobs queued behind an expensive one
# must finish ahead of it (SJF within the class lane) and cut the
# cheap-job P99 relative to FIFO on the identical load.
co = d["cost_ordering"]
if co["cost"]["cheap_p99_seconds"] >= co["cost"]["expensive_latency_seconds"]:
    print(f"cost ordering left cheap jobs behind the expensive one: "
          f"{co['cost']}"); sys.exit(1)
if not co["fifo_over_cost_p99"] or co["fifo_over_cost_p99"] <= 1.0:
    print(f"cost ordering did not beat FIFO: {co}"); sys.exit(1)
print(f"serve-load OK: small P99 {unloaded:.3f}s unloaded -> "
      f"{loaded:.3f}s beside a {large:.2f}s large job "
      f"({doc['value']}x, bound 2x); fleet stats: small wall p99 "
      f"{wall['p99']:.3f}s over {wall['count']} jobs, calibration "
      f"n={fs['calibration']['samples']}; fused group {ratio:.1f}x "
      f"serial (byte-identical), cost ordering cut cheap P99 "
      f"{co['fifo_over_cost_p99']:.2f}x vs FIFO")
PYEOF
  else
    echo "serve-load bench failed:"; tail -10 "$SC_TMP/load.err"
  fi
fi
if [ "$sc_rc" -ne 0 ]; then
  echo "serve concurrency smoke failed (rc=$sc_rc):"
  tail -20 "$SC_TMP/daemon.err" 2>/dev/null
fi
rm -rf "$SC_TMP"

echo "== fused batch + cost-ordering smoke (one device program per group) =="
fb_rc=0
FB_TMP=$(mktemp -d)
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  python -m spark_examples_tpu serve --port 0 \
    --run-dir "$FB_TMP/run" --endpoint-file "$FB_TMP/endpoint" \
    --executor-slices 0 --batch-max-jobs 3 --batch-linger-seconds 2.0 \
    --serve-small-site-limit 500000 \
    > "$FB_TMP/daemon.out" 2> "$FB_TMP/daemon.err" &
FB_PID=$!
for _ in $(seq 1 150); do [ -f "$FB_TMP/endpoint" ] && break; sleep 0.2; done
if [ ! -f "$FB_TMP/endpoint" ]; then
  echo "fused smoke: daemon never published its endpoint"; fb_rc=1
  kill "$FB_PID" 2>/dev/null; wait "$FB_PID" 2>/dev/null
else
  env JAX_PLATFORMS=cpu python - "$(cat "$FB_TMP/endpoint")" <<'PYEOF' || fb_rc=$?
import json, sys, urllib.request
from spark_examples_tpu.serve.client import ServeClient, ServeError

url = sys.argv[1]
client = ServeClient(url)
SMALL = ["--num-samples", "8", "--references", "1:0:50000"]

# 1. Three identical small jobs land inside the linger window -> the
#    daemon runs the group as ONE stacked device program and every
#    member envelope records the group size it rode in.
ids = [client.submit(SMALL)["job"]["id"] for _ in range(3)]
fused = [client.wait(j, timeout=600)["job"] for j in ids]
for job in fused:
    if job["status"] != "done" or job["fused_size"] != 3:
        print(f"group member not fused: {job['status']} "
              f"fused_size={job['fused_size']} {job.get('error')}")
        sys.exit(1)

# 2. Serial resubmits of the SAME geometry (one at a time — a
#    singleton batch never fuses) must be byte-identical to the fused
#    group's results.
serial = [client.wait(client.submit(SMALL)["job"]["id"], timeout=600)["job"]
          for _ in range(2)]
reference = serial[0]["result"]["pc_lines"]
for job in serial[1:] + fused:
    if job["result"]["pc_lines"] != reference:
        print("fused group results diverged from serial resubmits")
        sys.exit(1)
for job in serial:
    if job["fused_size"] != 1:
        print(f"singleton batch fused anyway: {job['fused_size']}")
        sys.exit(1)

# 3. /v1/fleet/stats partitions every executed job fused vs serial.
with urllib.request.urlopen(url + "/v1/fleet/stats", timeout=30) as resp:
    dispatch = json.loads(resp.read().decode("utf-8"))["dispatch"]
if dispatch["fused_groups"] < 1 or dispatch["fused_jobs"] < 3 \
        or dispatch["serial_jobs"] < 2:
    print(f"dispatch counters wrong: {dispatch}"); sys.exit(1)

# 4. An over-HBM fused group is a structured 413 at admission: the
#    plan charges K stacked accumulators against the HBM budget
#    device-free and names the cohort's fused-group ceiling.
try:
    client.submit(["--num-samples", "20000", "--references", "1:0:50000",
                   "--pca-backend", "tpu", "--fused-jobs", "12"])
    print("over-HBM fused group was ACCEPTED"); sys.exit(1)
except ServeError as e:
    codes = [i["code"] for i in e.body.get("plan", {}).get("issues", [])]
    if e.status != 413 or e.code != "plan-rejected" \
            or "fused-group-exceeds-hbm" not in codes:
        print(f"over-HBM group not a structured 413: "
              f"{e.status} {e.code} {codes}")
        sys.exit(1)
    ceiling = e.body["plan"]["geometry"].get("max_fused_jobs")
    if not ceiling or ceiling >= 12:
        print(f"413 geometry does not carry a real fused ceiling: {ceiling}")
        sys.exit(1)

# 5. Cost ordering: a cheap job admitted BEHIND an expensive one
#    completes first. The blocker's geometry differs from the
#    expensive job's so they can never coalesce into one group.
BLOCKER = ["--num-samples", "144", "--references", "1:0:10000000"]
EXPENSIVE = ["--num-samples", "128", "--references", "1:0:10000000"]
blocker = client.submit(BLOCKER)["job"]["id"]
expensive = client.submit(EXPENSIVE)["job"]["id"]
cheap = client.submit(SMALL)["job"]["id"]
cheap_done = client.wait(cheap, timeout=600)["job"]
expensive_done = client.wait(expensive, timeout=600)["job"]
client.wait(blocker, timeout=600)
if cheap_done["status"] != "done" or expensive_done["status"] != "done":
    print(f"ordering smoke jobs failed: {cheap_done.get('error')} "
          f"{expensive_done.get('error')}"); sys.exit(1)
if cheap_done["finished_unix"] >= expensive_done["finished_unix"]:
    print(f"cheap job did not overtake the expensive one: cheap finished "
          f"at +{cheap_done['finished_unix'] - expensive_done['finished_unix']:.3f}s")
    sys.exit(1)
print(f"fused smoke OK: 3-job group one device program (byte-identical "
      f"to serial resubmits), dispatch {dispatch['fused_groups']} fused "
      f"group(s) / {dispatch['serial_jobs']} serial, over-HBM group 413 "
      f"(ceiling {ceiling}), cheap job overtook the expensive one by "
      f"{expensive_done['finished_unix'] - cheap_done['finished_unix']:.2f}s")
PYEOF
  kill -TERM "$FB_PID" 2>/dev/null
  if wait "$FB_PID"; then
    echo "fused smoke: daemon drained cleanly (exit 0)"
  else
    echo "fused smoke: daemon exited nonzero"; fb_rc=1
  fi
fi
if [ "$fb_rc" -ne 0 ]; then
  echo "fused batch smoke failed (rc=$fb_rc):"; tail -20 "$FB_TMP/daemon.err"
fi
rm -rf "$FB_TMP"

echo "== multi-replica serving smoke (lease-fenced work stealing) =="
rep_rc=0
REP_TMP=$(mktemp -d)
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
    SPARK_EXAMPLES_TPU_FAULTS='kill@serve.worker.mid-job' \
  python -m spark_examples_tpu serve --port 0 \
    --run-dir "$REP_TMP/rd" --replica-id a --executor-slices 0 \
    --no-persistent-cache --lease-seconds 1.0 --lease-grace-seconds 0.2 \
    --steal-interval-seconds 0.2 \
    --endpoint-file "$REP_TMP/endpoint.a" 2> "$REP_TMP/daemon.a.err" &
REP_A_PID=$!
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
  python -m spark_examples_tpu serve --port 0 \
    --run-dir "$REP_TMP/rd" --replica-id b --executor-slices 0 \
    --no-persistent-cache --lease-seconds 1.0 --lease-grace-seconds 0.2 \
    --steal-interval-seconds 0.2 \
    --endpoint-file "$REP_TMP/endpoint.b" 2> "$REP_TMP/daemon.b.err" &
REP_B_PID=$!
for _ in $(seq 1 600); do
  [ -f "$REP_TMP/endpoint.a" ] && [ -f "$REP_TMP/endpoint.b" ] && break
  sleep 0.1
done
if [ ! -f "$REP_TMP/endpoint.a" ] || [ ! -f "$REP_TMP/endpoint.b" ]; then
  echo "replica smoke: a replica never published its endpoint"; rep_rc=1
else
  env JAX_PLATFORMS=cpu python - \
      "$(cat "$REP_TMP/endpoint.a")" "$(cat "$REP_TMP/endpoint.b")" \
      "$REP_A_PID" <<'PYEOF' || rep_rc=$?
import sys, time
from spark_examples_tpu.serve.client import ServeClient, ServeError

a_url, b_url, a_pid = sys.argv[1], sys.argv[2], int(sys.argv[3])
small = ["--num-samples", "8", "--references", "1:0:50000"]
large = ["--num-samples", "8", "--references", "1:0:30000000"]

# The large job lands on replica a, whose fault plan SIGKILLs it the
# moment device work begins — the owning replica dies mid-device.
job_id = ServeClient(a_url, timeout=60).submit(large)["job"]["id"]
assert job_id.startswith("job-a-"), job_id

# Small jobs keep flowing through the survivor THROUGHOUT the failover.
b = ServeClient(b_url, timeout=60, max_retries=5)
small_done = 0
stolen = None
deadline = time.monotonic() + 240
while time.monotonic() < deadline:
    doc = b.wait(b.submit(small)["job"]["id"], timeout=120)
    assert doc["job"]["status"] == "done", doc
    small_done += 1
    try:
        status = b.status(job_id)["job"]
    except ServeError as e:
        if e.status != 404:
            raise
        continue  # not stolen yet
    if status["status"] in ("done", "failed", "cancelled"):
        stolen = status
        if small_done >= 3:
            break
if stolen is None:
    raise SystemExit(f"survivor never settled the orphaned job "
                     f"({small_done} small jobs served meanwhile)")
# device_began was journaled before the kill: the survivor must fail it
# structurally, never silently re-run the devices.
if stolen["status"] != "failed" or \
        not (stolen["error"] or "").startswith("replica-failover:"):
    raise SystemExit(f"stolen mid-device job not failed structurally: "
                     f"{stolen}")
health = b.healthz()
rep = health["replica"]
if rep["jobs_stolen"] < 1:
    raise SystemExit(f"survivor reports no stolen jobs: {rep}")
# The client endpoint list fails over off the dead replica.
failover = ServeClient(f"{a_url},{b_url}", timeout=60, max_retries=5)
via = failover.status(job_id)["job"]
assert via["status"] == "failed", via
print(f"replica smoke OK: owner SIGKILLed mid-device, survivor stole "
      f"the job under epoch fencing -> {stolen['error'][:40]}..., "
      f"{small_done} small jobs flowed throughout, client failed over "
      f"({rep['jobs_stolen']} stolen, {rep['alive']} alive)")
PYEOF
fi
kill -TERM "$REP_B_PID" 2>/dev/null
wait "$REP_B_PID" 2>/dev/null
wait "$REP_A_PID" 2>/dev/null
if [ "$rep_rc" -eq 0 ]; then
  # Flight-recorder trace export: the two-replica chaos run above (owner
  # SIGKILLed mid-device, survivor stole under epoch fencing) must merge
  # into ONE well-formed Chrome trace — the stolen job's span tree
  # complete across both replicas, the steal flow arrow whole, epochs
  # and the fenced terminal state present, zero orphan spans.
  env JAX_PLATFORMS=cpu python -m spark_examples_tpu trace export \
    --run-dir "$REP_TMP/rd" --out "$REP_TMP/fleet.trace.json" || rep_rc=$?
  if [ "$rep_rc" -eq 0 ]; then
    env JAX_PLATFORMS=cpu python - "$REP_TMP/fleet.trace.json" <<'PYEOF' || rep_rc=$?
import json, sys
from spark_examples_tpu.obs.trace import validate_chrome_trace

doc = json.load(open(sys.argv[1]))
errors = validate_chrome_trace(doc)
if errors:
    print("merged trace NOT well-formed:\n  " + "\n  ".join(errors))
    sys.exit(1)
jobs = doc["otherData"]["jobs"]
stolen = {j: f for j, f in jobs.items() if f.get("stolen")}
if not stolen:
    print(f"merged trace records no stolen job: {list(jobs)}")
    sys.exit(1)
job_id, facts = sorted(stolen.items())[0]
if facts["status"] != "failed":
    print(f"stolen job's fenced terminal state wrong: {facts}")
    sys.exit(1)
if facts["lease_epoch"] < 2 or not facts.get("trace"):
    print(f"stolen job missing fencing epoch or trace id: {facts}")
    sys.exit(1)
events = doc["traceEvents"]
job_events = [e for e in events
              if (e.get("args") or {}).get("job") == job_id]
pids = {e["pid"] for e in job_events}
if len(pids) < 2:
    print(f"stolen job's span tree does not cross both replicas: "
          f"pids={pids}")
    sys.exit(1)
traces = {(e.get("args") or {}).get("trace") for e in job_events}
if traces - {facts["trace"]}:
    print(f"stolen job's events carry mixed trace ids: {traces}")
    sys.exit(1)
spans = [e for e in job_events if e["ph"] == "X" and e["name"] == "job"]
if not any(s["args"].get("truncated") for s in spans):
    print("the killed owner's job span was not closed as truncated: "
          f"{spans}")
    sys.exit(1)
if not any(s["args"].get("epoch") for s in spans):
    print(f"job spans carry no lease epoch: {spans}")
    sys.exit(1)
arrows = [e for e in events
          if e["ph"] in ("s", "f") and e["name"] == f"steal {job_id}"]
if {e["ph"] for e in arrows} != {"s", "f"}:
    print(f"stolen job has no whole steal flow arrow: {arrows}")
    sys.exit(1)
terminals = [e for e in job_events if e["name"] == "terminal"
             and e["args"].get("status") == "failed"]
if not terminals:
    print("survivor's terminal event for the stolen job is missing")
    sys.exit(1)
print(f"trace export OK: {doc['otherData']['recorder_events']} events, "
      f"{len(doc['otherData']['replicas'])} replicas, stolen job "
      f"{job_id} complete across {len(pids)} processes (steal arrow + "
      f"epoch {facts['lease_epoch']} + fenced terminal "
      f"'{facts['status']}'), zero orphan spans")
PYEOF
  fi
fi
if [ "$rep_rc" -eq 0 ]; then
  # Post-mortem cost observatory: with the whole fleet dead, `obs
  # report` must reconstruct the stolen job's prediction, wall, and
  # queue-wait under its one trace id — purely from the run-dir
  # artifacts (journal + calibration ledger + recorder segments).
  env JAX_PLATFORMS=cpu python -m spark_examples_tpu obs report \
    --run-dir "$REP_TMP/rd" --json > "$REP_TMP/fleet.report.json" \
    || rep_rc=$?
  if [ "$rep_rc" -eq 0 ]; then
    env JAX_PLATFORMS=cpu python - "$REP_TMP/fleet.report.json" <<'PYEOF' || rep_rc=$?
import json, sys
doc = json.load(open(sys.argv[1]))
stolen = {j: f for j, f in doc["jobs"].items() if f.get("stolen")}
if not stolen:
    print(f"fleet report records no stolen job: {list(doc['jobs'])}")
    sys.exit(1)
job_id, facts = sorted(stolen.items())[0]
missing = [k for k in
           ("trace", "predicted_seconds", "measured_seconds",
            "queue_wait_seconds")
           if facts.get(k) is None]
if missing:
    print(f"fleet report's stolen job {job_id} lacks {missing}: {facts}")
    sys.exit(1)
if facts["status"] != "failed":
    print(f"stolen job's fenced status wrong in the report: {facts}")
    sys.exit(1)
if not doc["totals"]["ledger_samples"] or not doc["recorder"]:
    print(f"report missing ledger or recorder facts: {doc['totals']}")
    sys.exit(1)
print(f"obs report OK (fleet dead): stolen job {job_id} trace="
      f"{facts['trace'][:8]}... predicted {facts['predicted_seconds']:.2f}s,"
      f" wall {facts['measured_seconds']:.2f}s, queue wait "
      f"{facts['queue_wait_seconds']:.2f}s; "
      f"{doc['totals']['ledger_samples']} ledger samples, "
      f"{doc['recorder']['events']} recorder events")
PYEOF
  fi
fi
if [ "$rep_rc" -ne 0 ]; then
  echo "replica smoke failed (rc=$rep_rc):"
  tail -20 "$REP_TMP"/daemon.*.err 2>/dev/null
fi
rm -rf "$REP_TMP"
if [ "$rep_rc" -eq 0 ]; then
  # The lease substrate's locks must keep the acquisition graph acyclic.
  env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck lockgraph \
    || rep_rc=$?
fi
if [ "$rep_rc" -eq 0 ]; then
  # The full two-replica chaos matrix: SIGKILL at every registered serve
  # kill-point, survivor results byte-compared to a solo-replica oracle.
  env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
    python -m pytest tests/test_serve_replicas_chaos.py -q \
      -p no:cacheprovider || rep_rc=$?
fi

echo "== faults stage (kill/resume parity + serve watchdog) =="
faults_rc=0
FAULTS_TMP=$(mktemp -d)
faults_flags="--num-samples 8 --references 1:0:150000 --ingest packed \
  --checkpoint-every-sites 40"
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
  python -m spark_examples_tpu variants-pca $faults_flags \
    --gramian-checkpoint-dir "$FAULTS_TMP/ck-oracle" \
    --output-path "$FAULTS_TMP/oracle" \
    > /dev/null 2> "$FAULTS_TMP/oracle.err" || faults_rc=$?
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
    SPARK_EXAMPLES_TPU_FAULTS='kill@checkpoint.post-save#2' \
  python -m spark_examples_tpu variants-pca $faults_flags \
    --gramian-checkpoint-dir "$FAULTS_TMP/ck" \
    --output-path "$FAULTS_TMP/killed" \
    > /dev/null 2> "$FAULTS_TMP/killed.err"
kill_rc=$?
if [ "$kill_rc" -ne 137 ]; then
  echo "faults smoke: killed run exited $kill_rc, expected 137 (SIGKILL)"
  faults_rc=1
fi
env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
  python -m spark_examples_tpu variants-pca $faults_flags \
    --gramian-checkpoint-dir "$FAULTS_TMP/ck" \
    --resume-from "$FAULTS_TMP/ck" \
    --output-path "$FAULTS_TMP/resumed" \
    --metrics-json "$FAULTS_TMP/resumed.json" \
    > /dev/null 2> "$FAULTS_TMP/resumed.err" || faults_rc=$?
if [ "$faults_rc" -eq 0 ]; then
  if ! cmp -s "$FAULTS_TMP/oracle-pca.tsv/part-00000" \
              "$FAULTS_TMP/resumed-pca.tsv/part-00000"; then
    echo "faults smoke: resumed eigenvectors DIFFER from the oracle"
    faults_rc=1
  fi
fi
if [ "$faults_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python - "$FAULTS_TMP/resumed.json" <<'PYEOF' || faults_rc=$?
import sys
from spark_examples_tpu.obs.manifest import read_manifest, validate_manifest
doc = read_manifest(sys.argv[1])
errors = validate_manifest(doc)
if errors:
    print("resumed manifest INVALID:\n  " + "\n  ".join(errors))
    sys.exit(1)
resume = doc.get("resume")
if not resume or resume["sites_skipped"] <= 0:
    print(f"resumed manifest carries no resume fast-forward: {resume}")
    sys.exit(1)
print(f"kill/resume smoke OK: SIGKILL at checkpoint.post-save#2, resumed "
      f"past {resume['sites_skipped']} sites, eigenvectors byte-identical")
PYEOF
else
  echo "faults smoke failed (rc=$faults_rc):"
  tail -5 "$FAULTS_TMP"/*.err 2>/dev/null
fi
if [ "$faults_rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu SPARK_EXAMPLES_TPU_NO_CACHE=1 \
    python - "$FAULTS_TMP" <<'PYEOF' || faults_rc=$?
import sys, time
from spark_examples_tpu.serve.daemon import PcaService
from spark_examples_tpu.serve.executor import ExecutionOutcome
from spark_examples_tpu.serve.protocol import request_doc
from spark_examples_tpu.utils import faults

calls = []
def executor(job, run_dir):
    calls.append(job.id)
    return ExecutionOutcome(result={"ok": True}, manifest_path=None,
                            compile_cache="cold")

def wait_terminal(svc, job_id, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _s, doc = svc.job_status(job_id)
        if doc["job"]["status"] in ("done", "failed", "cancelled"):
            return doc["job"]
        time.sleep(0.02)
    raise SystemExit(f"job {job_id} never reached a terminal state")

flags = ["--num-samples", "8", "--references", "1:0:50000"]
faults.configure("crash@serve.worker.mid-job")
svc = PcaService(run_dir=sys.argv[1] + "/serve", executor=executor).start()
_s, doc = svc.submit(request_doc(flags))
assert _s == 202, doc
crashed = wait_terminal(svc, doc["job"]["id"])
if crashed["status"] != "failed" or \
        not (crashed["error"] or "").startswith("worker-crashed:"):
    raise SystemExit(f"crashed job not failed structurally: {crashed}")
health = svc.healthz()
if health["status"] != "ok" or not health["queue"]["worker_alive"]:
    raise SystemExit(f"daemon unhealthy after worker crash: {health}")
_s, doc2 = svc.submit(request_doc(flags))
assert _s == 202, doc2
recovered = wait_terminal(svc, doc2["job"]["id"])
if recovered["status"] != "done":
    raise SystemExit(f"post-crash job did not complete: {recovered}")
if not svc.stop(timeout=10.0):
    raise SystemExit("daemon did not drain after recovery")
print(f"serve watchdog smoke OK: crash mid-job -> failed "
      f"({crashed['error'][:40]}...), {health['queue']['worker_restarts']} "
      "restart, next job done, clean drain")
PYEOF
fi
rm -rf "$FAULTS_TMP"

san_rc=0
if [ "$SANITIZE" = "1" ]; then
  echo "== sanitizer stage (graftcheck sanitize) =="
  env JAX_PLATFORMS=cpu python -m spark_examples_tpu graftcheck sanitize || san_rc=$?
fi

if [ "$rc" -ne 0 ]; then exit "$rc"; fi
if [ "$lint_rc" -ne 0 ]; then exit "$lint_rc"; fi
if [ "$proto_rc" -ne 0 ]; then exit "$proto_rc"; fi
if [ "$ir_rc" -ne 0 ]; then exit "$ir_rc"; fi
if [ "$rg_rc" -ne 0 ]; then exit "$rg_rc"; fi
if [ "$sched_rc" -ne 0 ]; then exit "$sched_rc"; fi
if [ "$mh_rc" -ne 0 ]; then exit "$mh_rc"; fi
if [ "$hm_rc" -ne 0 ]; then exit "$hm_rc"; fi
if [ "$obs_rc" -ne 0 ]; then exit "$obs_rc"; fi
if [ "$ring_rc" -ne 0 ]; then exit "$ring_rc"; fi
if [ "$an_rc" -ne 0 ]; then exit "$an_rc"; fi
if [ "$serve_rc" -ne 0 ]; then exit "$serve_rc"; fi
if [ "$sc_rc" -ne 0 ]; then exit "$sc_rc"; fi
if [ "$fb_rc" -ne 0 ]; then exit "$fb_rc"; fi
if [ "$rep_rc" -ne 0 ]; then exit "$rep_rc"; fi
if [ "$faults_rc" -ne 0 ]; then exit "$faults_rc"; fi
exit "$san_rc"
