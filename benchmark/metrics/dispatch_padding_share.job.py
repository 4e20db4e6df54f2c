"""Share of the dispatched site-grid capacity that is padding, in %:
100 · (1 − valid sites / capacity), from the ``sites_valid`` and
``sites_capacity`` attributes of the program's ``ingest`` span, summed over
the window's jobs."""

from benchmark.program import window_runs


def read(run):
    runs = window_runs(run, ["ingest"])
    if runs is None:
        return None
    attrs = [r["ingest"]["attrs"] for r in runs]
    if not all("sites_valid" in a and "sites_capacity" in a for a in attrs):
        return None
    capacity = sum(a["sites_capacity"] for a in attrs)
    if capacity <= 0:
        return None
    return 100.0 * (1.0 - sum(a["sites_valid"] for a in attrs) / capacity)
