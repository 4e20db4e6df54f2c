"""Device milliseconds per job of everything the ingest call enqueues
(site metadata, genotype generation and the int8 Gramian dot). The traced
run fences the ingest call, so every device operation inside the
benchmark's ``bench:ingest`` spans belongs to it; the program names its
update programs generically (``jit_update``), so module names cannot be
used."""


def read(run):
    trace = run.trace
    if trace is None or not trace.busy:
        return None
    spans = trace.spans_named("ingest")
    busy = trace.busy_within_s(spans)
    if not spans or busy <= 0:
        return None
    return 1000.0 * busy / len(spans)
