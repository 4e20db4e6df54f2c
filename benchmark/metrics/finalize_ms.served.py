"""Device milliseconds per served job of the centring and eigensolve
programs: the reading of ``finalize_ms.job`` over the served jobs."""

from benchmark import core

read = core.load_reader("finalize_ms.job")
