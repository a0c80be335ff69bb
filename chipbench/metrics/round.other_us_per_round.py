"""Device microseconds per bandit-round in XLA ops outside the Pallas
kernels that no round phase names: loop control, the scan's output
writes, copies, the ops of other modules (the small conversion programs
beside the sweep), and on the presample path the Eq. 8 draws fused into
the scan's input transpose. With the four ``round.*`` readers it makes up
``round.xla_us_per_round`` (chipbench/scopes.py). Nothing to read where
the program names no round phase."""

from chipbench import scopes


def read(ctx):
    return scopes.phase_us_per_round(ctx, None)
