"""Device milliseconds per job of genotype generation: the operations the
program scopes ``generate`` inside its update programs (site metadata, the
genotype hash, assembling the population segments and the cast to the
int8 operand), found through ``ops/devicegen.py:update_op_scopes``."""

from benchmark.program import scoped_device_ms


def read(run):
    return scoped_device_ms(run, "generate")
