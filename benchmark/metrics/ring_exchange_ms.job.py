"""Device milliseconds per job of the ring's tile exchange: the operations
the program scopes ``ring_exchange`` (the ``ppermute`` of
``ops/gramian.py:_ring_tiles``) inside its ring update programs, found
through ``ops/devicegen.py:update_op_scopes``. The ring issues each step's
exchange before the dot that does not need it, so this is the exchange
time the dot leaves exposed."""

from benchmark.program import scoped_device_ms


def read(run):
    return scoped_device_ms(run, "ring_exchange")
