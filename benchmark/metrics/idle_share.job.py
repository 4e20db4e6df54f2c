"""Share of the traced batch window in which no operation ran on the
device: 1 − (union of device-busy intervals) / window, in %."""


def read(run):
    trace = run.trace
    if trace is None or not trace.busy or trace.window_s() <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
