"""The most device memory a chip had held by the end of a job's ingest, in
GB: the largest ``device_peak_bytes`` attribute of the program's ``ingest``
spans over the window's jobs (the devices' own ``peak_bytes_in_use``, read
after the ingest's closing sync)."""

from benchmark.program import window_runs


def read(run):
    runs = window_runs(run, ["ingest"])
    if runs is None:
        return None
    peaks = [r["ingest"]["attrs"].get("device_peak_bytes") for r in runs]
    if any(p is None for p in peaks):
        return None
    return max(peaks) / 1e9
