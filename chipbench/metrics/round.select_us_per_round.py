"""Device microseconds per bandit-round in scoring and selection
(``round.select``: the policy's scores, the [K] mask and tiles the
selection kernels are fed, Algorithm 1 / top-S where no kernel runs, the
segmented merge, cell scoring); the kernels themselves are
``select.us_per_round``.  Own time of the XLA ops the program names so,
averaged over the cell's devices (chipbench/scopes.py). Nothing to read
where the program names no round phase."""

from chipbench import scopes


def read(ctx):
    return scopes.phase_us_per_round(ctx, "round.select")
