"""Device dispatches per job, from the program's own counter
(``devicegen_dispatches``), averaged over the traced run's jobs."""


def read(run):
    counts = [job["dispatches"] for job in run.jobs if "dispatches" in job]
    if not counts:
        return None
    return sum(counts) / len(counts)
