"""95th percentile, in ms, of the served jobs' own queue wait
(``queue_wait_seconds`` in each job record, stamped by the daemon when its
worker takes the job)."""

from benchmark import core


def read(run):
    waits = [j["queue_wait_seconds"] for j in run.jobs if j.get("queue_wait_seconds") is not None]
    if not waits:
        return None
    return 1000.0 * core.nearest_rank(waits, 0.95)
