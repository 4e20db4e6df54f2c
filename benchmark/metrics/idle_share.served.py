"""Share of the traced served window in which no operation ran on the
device, in %: the reading of ``idle_share.job``."""

from benchmark import core

read = core.load_reader("idle_share.job")
