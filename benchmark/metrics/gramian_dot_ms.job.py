"""Device milliseconds per job of the int8 Gramian dot: the operations the
program scopes ``int8_dot`` inside its update programs (the einsum and the
``G +`` accumulate), found through ``ops/devicegen.py:update_op_scopes``."""

from benchmark.program import scoped_device_ms


def read(run):
    return scoped_device_ms(run, "int8_dot")
