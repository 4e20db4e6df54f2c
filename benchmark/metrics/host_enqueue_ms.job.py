"""Host milliseconds per job of the ingest enqueue loop's own work: the
self time of the program's ``ingest/enqueue`` span, that is its duration
less the ``dispatch`` aggregate (the dispatch calls and operand uploads,
where the host waits while the device's queue is full), the ``stats``
aggregate and the ``poke`` fetch, averaged over the window's jobs."""

from benchmark.program import window_runs


def read(run):
    runs = window_runs(run, ["ingest/enqueue", "ingest/enqueue/dispatch"])
    if runs is None:
        return None
    return 1000.0 * sum(r["ingest/enqueue"]["self_seconds"] for r in runs) / len(runs)
