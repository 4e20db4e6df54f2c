"""Device milliseconds per batch job of the centring and eigensolve
programs, found by their module names in the trace."""

#: Program (XLA module) names of the finalize layer: ``ops/centering.py``
#: ``gower_center`` and ``ops/pca.py`` ``principal_components_subspace``.
MODULES = ("jit_gower_center", "jit_principal_components_subspace")


def read(run):
    trace = run.trace
    if trace is None or not trace.modules or not run.jobs:
        return None
    seconds = trace.module_seconds(MODULES)
    if seconds <= 0:
        return None
    return 1000.0 * seconds / len(run.jobs)
