"""Device microseconds per bandit-round in the round's tail
(``round.finish``: the realized schedule, the failure layer,
``observe``, the cell statistics): the own time of the XLA ops the
program names so, averaged over the cell's devices
(chipbench/scopes.py). Nothing to read where the program names no round
phase."""

from chipbench import scopes


def read(ctx):
    return scopes.phase_us_per_round(ctx, "round.finish")
