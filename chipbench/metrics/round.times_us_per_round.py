"""Device microseconds per bandit-round in the resource times
(``round.times``: Eq. 8 draws, scenario multipliers, the [C] gathers and
[K] scatters of the times, churn): the own time of the XLA ops the
program names so, averaged over the cell's devices
(chipbench/scopes.py). On the batched presample path (K below
``FAST_SAMPLING_MIN_K``, the paper-grid cell's) XLA fuses the Eq. 8
draws into the transpose that the scan's batching adds outside any
phase, so there that work is counted in ``round.other``. Nothing to read
where the program names no round phase."""

from chipbench import scopes


def read(ctx):
    return scopes.phase_us_per_round(ctx, "round.times")
