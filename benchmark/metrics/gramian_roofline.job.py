"""Share of the Gramian update's roofline, in %: the least time the chip
could take (the larger of the int8 operations bound and the bytes bound,
``roofline.py``) over the measured device time per job
(``gramian_update_ms.job``, averaged over the chips, so the least time
is the cell's chips together). Both cells are operations-bound: the
genotypes are generated on the device, so the only bytes every
implementation must move are the finished Gramian's."""

from benchmark import core, roofline

_update_ms = core.load_reader("gramian_update_ms.job")


def read(run):
    ms = _update_ms(run)
    if ms is None or not run.jobs:
        return None
    sites = run.jobs[0]["sites_scanned"]
    least, _bound = roofline.least_seconds(
        int(run.cell["config"]["num_samples"]), sites, run.device_kind, run.cell["chips"]
    )
    return 100.0 * least / (ms / 1000.0)
