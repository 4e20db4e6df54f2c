"""Differential fold fuzz: the journal fold is order-insensitive.

``replay_journal``'s contract says the fold is order-insensitive across
events of one job (appenders are concurrent threads AND concurrent
replica processes, serialized only per record) — and ``graftcheck
proto``'s canonical journal ordering additionally relies on records of
DIFFERENT jobs commuting. This test checks the theorem the docstring
states: for seeded random protocol histories, folding every permutation
of the records yields the same semantic state — same pending set, same
fence epochs, same effective/fenced terminal verdicts.

Two deliberate scope notes:

- Histories only contain record multisets the protocol can produce:
  at most one ``accepted`` per job and strictly increasing lease epochs
  per job. Same-epoch lease re-issue by two replicas is exactly what
  GP004 proves impossible — outside that set the fold's ``owner`` pick
  is legitimately order-dependent.
- Presentation order (the ``terminals`` list, pending-job list order)
  follows input order by design; the comparison normalizes it. What
  must NOT vary is the semantic content.

Deterministic by construction: seeded ``random.Random``, no third-party
property-testing dependency.
"""

import itertools
import random
from dataclasses import asdict

from spark_examples_tpu.serve.journal import (
    accepted_record,
    began_record,
    compacted_records,
    fold_records,
    lease_record,
    protocol_summary,
    terminal_record,
)

_REPLICAS = ("rep-a", "rep-b", "rep-c")
_STATUSES = ("done", "failed", "cancelled")


def _random_history(rng):
    """One protocol-producible history: per job an accepted record, a
    strictly-increasing lease chain, maybe a began, and 0-2 terminals
    (epoch-less, fenced-low, or at-the-fence)."""
    records = []
    for i in range(rng.randint(1, 3)):
        job = f"job-{i:04d}"
        records.append(
            accepted_record(
                job,
                {"n": i},
                "pca",
                100.0 + i,
                None,
                replica=rng.choice(_REPLICAS),
            )
        )
        epoch = 0
        for _ in range(rng.randint(0, 2)):
            epoch += rng.randint(1, 2)
            records.append(
                lease_record(
                    job,
                    epoch,
                    replica=rng.choice(_REPLICAS),
                    stolen=rng.random() < 0.3,
                )
            )
        if epoch and rng.random() < 0.7:
            records.append(
                began_record(
                    job,
                    replica=rng.choice(_REPLICAS),
                    epoch=rng.randint(1, epoch),
                )
            )
        for _ in range(rng.randint(0, 2)):
            records.append(
                terminal_record(
                    job,
                    rng.choice(_STATUSES),
                    replica=rng.choice(_REPLICAS),
                    epoch=rng.randint(1, epoch) if epoch else None,
                )
            )
    return records


def _fold_key(records):
    """The fold's semantic content, presentation order normalized."""
    pending, max_seq = fold_records(records)
    return (
        sorted((asdict(job) for job in pending), key=lambda j: j["job_id"]),
        max_seq,
    )


def _summary_key(records):
    summary = protocol_summary(records)
    jobs = {}
    for job_id, info in summary["jobs"].items():
        info = dict(info)
        info["terminals"] = sorted(
            (
                (t["status"], -1 if t["epoch"] is None else t["epoch"],
                 t["effective"])
                for t in info["terminals"]
            )
        )
        jobs[job_id] = info
    return {"jobs": jobs, "totals": summary["totals"]}


def _permutations(records, rng, cap=150):
    """Every permutation when the factorial is small; otherwise ``cap``
    seeded shuffles (still deterministic — the rng is seeded)."""
    if len(records) <= 6:
        return list(itertools.permutations(records))
    perms = []
    for _ in range(cap):
        shuffled = list(records)
        rng.shuffle(shuffled)
        perms.append(tuple(shuffled))
    return perms


def test_fold_is_permutation_invariant():
    checked = 0
    for seed in range(40):
        rng = random.Random(seed)
        records = _random_history(rng)
        base_fold = _fold_key(records)
        base_summary = _summary_key(records)
        for perm in _permutations(records, rng):
            assert _fold_key(perm) == base_fold, (seed, perm)
            assert _summary_key(perm) == base_summary, (seed, perm)
            checked += 1
    # The loop must have actually exercised interleavings, not
    # degenerate one-record histories.
    assert checked > 1000


def test_compaction_rewrite_preserves_fold_semantics():
    # fold -> compacted_records -> re-fold keeps every pending job with
    # its began flag and fence epoch (the invariant that makes the
    # checker's compact transition and the daemon's rewrite one thing).
    for seed in range(40):
        rng = random.Random(seed ^ 0xC0FFEE)
        records = _random_history(rng)
        pending, _seq = fold_records(records)
        refolded, _seq2 = fold_records(compacted_records(pending))
        before = {
            j.job_id: (j.device_began, j.lease_epoch) for j in pending
        }
        after = {
            j.job_id: (j.device_began, j.lease_epoch) for j in refolded
        }
        assert before == after, seed


def test_no_property_testing_dependency():
    # The differential fuzz must stay importable on the bare image: a
    # hypothesis import would make this file collection-error there. The
    # check reads this module's own imports, not ``sys.modules``, which
    # holds whatever other tests in the same worker imported.
    import ast

    with open(__file__, encoding="utf-8") as handle:
        tree = ast.parse(handle.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(alias.name.split(".")[0] for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
    assert "hypothesis" not in imported
