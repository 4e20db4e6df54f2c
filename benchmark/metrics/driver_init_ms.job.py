"""Host milliseconds per job of driver construction: the program's
``driver-init`` span (callset discovery, stats and gauges), averaged over
the window's jobs."""

from benchmark.program import window_runs


def read(run):
    runs = window_runs(run, ["driver-init"])
    if runs is None:
        return None
    return 1000.0 * sum(r["driver-init"]["seconds"] for r in runs) / len(runs)
