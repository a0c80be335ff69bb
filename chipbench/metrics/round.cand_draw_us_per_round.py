"""Device microseconds per bandit-round in the candidate draw
(``round.cand_draw``: the Resource Request's top-k or permutation draw):
the own time of the XLA ops the program names so, averaged over the
cell's devices (chipbench/scopes.py). Nothing to read where the program
names no round phase."""

from chipbench import scopes


def read(ctx):
    return scopes.phase_us_per_round(ctx, "round.cand_draw")
